"""Wrapper: pad sequence dims to block multiples and dispatch the kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.local_attn.local_attn import (
    DEFAULT_BLK_K,
    DEFAULT_BLK_Q,
    flash_tiled,
)


def local_flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                          scale: float = 1.0, blk_q: int = DEFAULT_BLK_Q,
                          blk_k: int = DEFAULT_BLK_K, interpret=None):
    """q: (B, H, S, D); k/v: (B, KV, T, D).  Arbitrary S/T (padded here)."""
    interpret = interpret_mode(interpret)
    B, H, S, D = q.shape
    T = k.shape[2]
    blk_q = min(blk_q, max(8, S))
    blk_k = min(blk_k, max(8, T))
    pad_q = (-S) % blk_q
    pad_k = (-T) % blk_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    out = flash_tiled(q, k, v, causal=causal, window=window, scale=scale,
                      t_real=T, blk_q=blk_q, blk_k=blk_k, interpret=interpret)
    return out[:, :, :S]
