"""Public wrappers: pad to tile multiple, dispatch the DP clip+noise kernel.

This is the client-side privatization path: ``repro.privacy.dp`` flattens an
update delta, privatizes it here (or through the jnp oracle when
``use_pallas=False``), and unflattens back into the model pytree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.dp_clip_noise.dp_clip_noise import TILE, dp_clip_noise_tiled


def privatize_flat(delta: jnp.ndarray, noise: jnp.ndarray, clip,
                   noise_multiplier, *, interpret=None) -> jnp.ndarray:
    """delta, noise: flat (T,) arbitrary T; returns privatized (T,) f32.

    Zero padding is harmless on both passes: padded lanes contribute 0 to the
    sum of squares and the padded outputs are sliced off."""
    interpret = interpret_mode(interpret)
    t = delta.shape[0]
    pad = (-t) % TILE
    if pad:
        delta = jnp.pad(delta.astype(jnp.float32), (0, pad))
        noise = jnp.pad(noise.astype(jnp.float32), (0, pad))
    out = dp_clip_noise_tiled(delta.astype(jnp.float32),
                              noise.astype(jnp.float32),
                              clip, noise_multiplier, interpret=interpret)
    return out[:t]


def privatize_update(delta: jnp.ndarray, key, clip, noise_multiplier, *,
                     interpret=None) -> jnp.ndarray:
    """Draw the standard-normal noise from ``key`` and privatize ``delta``."""
    noise = jax.random.normal(key, delta.shape, jnp.float32)
    return privatize_flat(delta, noise, clip, noise_multiplier,
                          interpret=interpret)
