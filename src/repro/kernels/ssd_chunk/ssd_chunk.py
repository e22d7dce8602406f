"""Fused intra-chunk SSD kernel (Mamba-2 state-space duality hot loop).

Per (batch, chunk, head) grid cell, computes in one VMEM-resident pass:

    dA_cum  = cumsum(dt * A)                      (l,)
    L       = exp(segsum(dA))  (lower-tri)        (l, l)
    y_diag  = ((C B^T) ∘ L) @ (x * dt)            (l, p)
    state   = B^T @ (decay_states * x * dt)       (n, p)  chunk contribution

The (l, l) decay matrix L — the memory-traffic culprit in the unfused
path (roofline: mamba2 train is HBM-bound) — never leaves VMEM: at
chunk=256, L is 256 KiB f32; inputs x/B/C tiles are (l, p)/(l, n) MXU-
aligned.  The sequential inter-chunk recurrence and the off-diagonal
output term stay in JAX (tiny einsums over (p, n) states).

TPU adaptation note: the CUDA Mamba-2 kernel relies on warp-level
parallel prefix for segsum; on TPU the cumulative sums are VPU ops over
lanes and the two contractions hit the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -2.0**30


def _ssd_kernel(xdt_ref, dA_ref, b_ref, c_ref, y_ref, st_ref):
    """Blocks: xdt (1,1,l,1,p), dA (1,1,l,1), b/c (1,1,l,n) -> y (1,1,l,1,p),
    st (1,1,1,n,p)."""
    xdt = xdt_ref[0, 0, :, 0, :].astype(jnp.float32)       # (l, p)
    dA = dA_ref[0, 0, :, 0].astype(jnp.float32)            # (l,)
    B = b_ref[0, 0, :, 0, :].astype(jnp.float32)           # (l, n)
    C = c_ref[0, 0, :, 0, :].astype(jnp.float32)           # (l, n)
    l = xdt.shape[0]

    dA_cum = jnp.cumsum(dA)                                # (l,)
    # segsum: dA_cum[i] - dA_cum[j] on the lower triangle (i >= j)
    diff = dA_cum[:, None] - dA_cum[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    L = jnp.exp(jnp.where(tri, diff, NEG_INF))             # (l, l)

    scores = jnp.dot(C, B.T, preferred_element_type=jnp.float32)  # (l, l)
    y = jnp.dot(scores * L, xdt, preferred_element_type=jnp.float32)
    y_ref[0, 0, :, 0, :] = y.astype(y_ref.dtype)

    decay_states = jnp.exp(dA_cum[-1] - dA_cum)            # (l,)
    st = jnp.dot(B.T, xdt * decay_states[:, None],
                 preferred_element_type=jnp.float32)       # (n, p)
    st_ref[0, 0, 0] = st.astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk(xdt, dA, B, C, *, interpret: bool):
    """xdt: (b,c,l,h,p); dA: (b,c,l,h); B,C: (b,c,l,h,n) (already head-
    broadcast).  Returns (y_diag (b,c,l,h,p), states (b,c,h,n,p))."""
    b, c, l, h, p = xdt.shape
    n = B.shape[-1]
    grid = (b, c, h)
    y, st = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, l, 1, p), lambda bi, ci, hi: (bi, ci, 0, hi, 0)),
            pl.BlockSpec((1, 1, l, 1), lambda bi, ci, hi: (bi, ci, 0, hi)),
            pl.BlockSpec((1, 1, l, 1, n), lambda bi, ci, hi: (bi, ci, 0, hi, 0)),
            pl.BlockSpec((1, 1, l, 1, n), lambda bi, ci, hi: (bi, ci, 0, hi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, l, 1, p), lambda bi, ci, hi: (bi, ci, 0, hi, 0)),
            pl.BlockSpec((1, 1, 1, n, p), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, c, l, h, p), jnp.float32),
            jax.ShapeDtypeStruct((b, c, h, n, p), jnp.float32),
        ],
        interpret=interpret,
    )(xdt, dA, B, C)
    return y, st
