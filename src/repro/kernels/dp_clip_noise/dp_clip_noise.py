"""DP update-privatization kernel: clip-by-global-norm + Gaussian noise.

Privatizing a client's update delta is two streaming passes over the flat
parameter vector:

  1. ``sumsq`` reduction — accumulate ``sum(d^2)`` across the grid into one
     SMEM scalar (sequential TPU grid => safe accumulation, same shape as the
     EWC penalty scalar);
  2. fused ``d * scale + sigma * noise`` — the clip factor
     ``min(1, clip / ||d||)`` and the noise std ``sigma = noise_multiplier *
     clip`` are scalars computed between the passes, so the second pass
     streams each (delta, noise) tile through VMEM exactly once and writes
     the privatized tile.

Both passes are HBM-bandwidth-bound (< 1 FLOP/B); unfused jnp does clip-scale
and noise-add as separate passes plus an extra norm pass over the full delta.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 8 * 128 * 8  # f32 lanes per block, VPU-aligned (matches fedavg_agg)


def _sumsq_kernel(x_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[0, 0] = 0.0

    x = x_ref[...]
    o_ref[0, 0] += jnp.sum(x * x)


def _clip_noise_kernel(s_ref, x_ref, n_ref, o_ref):
    """s_ref: (1, 2) SMEM scalars [clip factor, noise std]."""
    o_ref[...] = x_ref[...] * s_ref[0, 0] + n_ref[...] * s_ref[0, 1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dp_clip_noise_tiled(delta: jnp.ndarray, noise: jnp.ndarray, clip,
                        noise_multiplier, *, interpret: bool):
    """delta, noise: flat (T,) f32 with T % TILE == 0.  Returns privatized
    (T,) f32: ``delta * min(1, clip/||delta||) + (noise_multiplier * clip) *
    noise``.  ``noise`` is a caller-supplied standard-normal vector so the
    kernel and the jnp oracle are bit-comparable under one RNG draw."""
    t = delta.shape[0]
    grid = (t // TILE,)
    vec = lambda: pl.BlockSpec((TILE,), lambda i: (i,))
    sumsq = pl.pallas_call(
        _sumsq_kernel,
        grid=grid,
        in_specs=[vec()],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=interpret,
    )(delta)
    clip = jnp.float32(clip)
    norm = jnp.sqrt(sumsq[0, 0])
    scale = jnp.minimum(jnp.float32(1.0), clip / jnp.maximum(norm, 1e-12))
    sigma = jnp.float32(noise_multiplier) * clip
    scalars = jnp.stack([scale, sigma]).reshape(1, 2)
    return pl.pallas_call(
        _clip_noise_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0),
                               memory_space=pltpu.SMEM), vec(), vec()],
        out_specs=vec(),
        out_shape=jax.ShapeDtypeStruct((t,), jnp.float32),
        interpret=interpret,
    )(scalars, delta, noise)
