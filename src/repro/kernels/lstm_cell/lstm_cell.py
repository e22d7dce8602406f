"""Fused LSTM cell kernel (case-study forecaster hot loop).

One step does two small matmuls (x@Wx, h@Wh), a bias add, and four gate
nonlinearities.  Unfused on TPU this is 6+ HBM round-trips of (b, 4H)
intermediates; the kernel keeps the gate block resident in VMEM: both
matmuls hit the MXU back-to-back, gates are applied in-register, and only
(h', c') return to HBM.

Tiling: batch tile 8 (sublane), hidden tile = full 4H lanes (H <= 512 for
the case-study sizes, so 4H*4B <= 8 KiB/row — comfortably in VMEM).
MXU alignment: in_dim/hidden padded to 128 by the wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BATCH_TILE = 8


def _lstm_kernel(x_ref, h_ref, c_ref, wx_ref, wh_ref, b_ref, ho_ref, co_ref):
    x = x_ref[...]
    h = h_ref[...]
    c = c_ref[...]
    gates = (jnp.dot(x, wx_ref[...], preferred_element_type=jnp.float32)
             + jnp.dot(h, wh_ref[...], preferred_element_type=jnp.float32)
             + b_ref[...])
    hsz = c.shape[-1]
    i = jax.nn.sigmoid(gates[:, :hsz])
    f = jax.nn.sigmoid(gates[:, hsz:2 * hsz] + 1.0)
    g = jnp.tanh(gates[:, 2 * hsz:3 * hsz])
    o = jax.nn.sigmoid(gates[:, 3 * hsz:])
    c_new = f * c + i * g
    ho_ref[...] = o * jnp.tanh(c_new)
    co_ref[...] = c_new


@functools.partial(jax.jit, static_argnames=("interpret",))
def lstm_step_tiled(x, h, c, wx, wh, b, *, interpret: bool):
    """x: (B, I), h/c: (B, H), wx: (I, 4H), wh: (H, 4H), b: (1, 4H);
    B % BATCH_TILE == 0.  Returns (h', c')."""
    B, I = x.shape
    H = h.shape[-1]
    grid = (B // BATCH_TILE,)
    out = pl.pallas_call(
        _lstm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BATCH_TILE, I), lambda i: (i, 0)),
            pl.BlockSpec((BATCH_TILE, H), lambda i: (i, 0)),
            pl.BlockSpec((BATCH_TILE, H), lambda i: (i, 0)),
            pl.BlockSpec((I, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((H, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, 4 * H), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BATCH_TILE, H), lambda i: (i, 0)),
            pl.BlockSpec((BATCH_TILE, H), lambda i: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, H), jnp.float32),
                   jax.ShapeDtypeStruct((B, H), jnp.float32)],
        interpret=interpret,
    )(x, h, c, wx, wh, b)
    return out[0], out[1]
