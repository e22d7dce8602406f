"""Fused EWC/L2-anchor penalty + gradient kernel (continual learning, §II.E).

Computes, in one pass over parameters:
    g_out  = g_in + lam * F * (theta - theta*)          (penalty gradient)
    loss  += 0.5 * lam * sum F * (theta - theta*)^2     (scalar penalty)

Unfused this is 4 HBM reads + 1 write + a separate reduction; the kernel
streams each tile once and accumulates the scalar in SMEM across the grid
(sequential TPU grid ⇒ safe accumulation), making it purely
bandwidth-bound with ~half the unfused traffic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 8 * 128 * 8


def _ewc_kernel(lam_ref, g_ref, p_ref, a_ref, f_ref, go_ref, loss_ref):
    i = pl.program_id(0)
    lam = lam_ref[0, 0]
    d = p_ref[...] - a_ref[...]
    fd = f_ref[...] * d
    go_ref[...] = g_ref[...] + lam * fd

    @pl.when(i == 0)
    def _init():
        loss_ref[0, 0] = 0.0

    loss_ref[0, 0] += 0.5 * lam * jnp.sum(fd * d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ewc_tiled(lam, grads, params, anchor, fisher, *, interpret: bool):
    """All flat (T,) f32, T % TILE == 0.  Returns (g_out (T,), loss scalar)."""
    t = grads.shape[0]
    grid = (t // TILE,)
    vec = lambda: pl.BlockSpec((TILE,), lambda i: (i,))
    scalar = lambda: pl.BlockSpec((1, 1), lambda i: (0, 0),
                                  memory_space=pltpu.SMEM)
    go, loss = pl.pallas_call(
        _ewc_kernel,
        grid=grid,
        in_specs=[scalar(), vec(), vec(), vec(), vec()],
        out_specs=[vec(), scalar()],
        out_shape=[jax.ShapeDtypeStruct((t,), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)],
        interpret=interpret,
    )(jnp.asarray(lam, jnp.float32).reshape(1, 1), grads, params, anchor, fisher)
    return go, loss[0, 0]
