"""Shared low-level layers: RMSNorm, RoPE, embeddings, activations."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sharding.logical import ParamSpec


def rmsnorm_schema(dim: int, name: str = "scale") -> dict:
    return {name: ParamSpec((dim,), ("embed",), init="ones", dtype="float32")}


def rmsnorm(params, x, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"]).astype(dtype)


def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(name)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float, interleave: bool = False):
    """x: (b, seq, heads, head_dim) or (b, seq, head_dim);
    positions: (seq,) shared, or (b, seq) per-sequence (continuous
    batching: each request at its own decode offset).

    Rotates the pairs ``(x[i], x[i + hd/2])``; ``interleave=True`` rotates
    ``(x[2i], x[2i+1])`` instead and returns them de-interleaved (evens,
    then odds), as Hugging Face's DeepSeek-V3 does: queries and keys get
    the same layout, so their products are unchanged."""
    head_dim = x.shape[-1]
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    freqs = rope_freqs(head_dim, theta)                        # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs
    if x.ndim == 4:                                            # heads axis present
        angles = angles[..., :, None, :]                       # (..., seq, 1, hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(logits, cap: float):
    if not cap:
        return logits
    return jnp.tanh(logits / cap) * cap
