"""N-way sample-weighted parameter aggregation kernel (FedCCL server).

The server-side FedAvg step is a pure streaming op: read N parameter
buffers, emit one convex combination.  Arithmetic intensity is
~N FLOP / (N+1)*4 bytes < 0.25 FLOP/B — firmly HBM-bandwidth-bound on TPU
(ridge point ~240 FLOP/B on v5e), so the kernel's only job is to stream
tiles through VMEM exactly once with no intermediate materialization.

Layout: models stacked (N, T) fp32, weights (N, 1) in SMEM.  The grid runs
over T-tiles of 8*128*8 lanes (VPU-aligned) and, innermost, over chunks of
at most ``ROWS`` models, so a block is at most (ROWS, TILE) whatever N is:
a whole secure round folds in one call without outgrowing scoped VMEM.
The output tile stays resident across the chunk axis and accumulates the
models one by one in index order — the same float sequence for any N.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 8 * 128 * 8  # 8192 f32 lanes per block = 32 KiB
ROWS = 32           # models per block: (32, TILE) f32 = 1 MiB of VMEM


def _agg_kernel(w_ref, x_ref, o_ref, *, n: int):
    """x_ref: (rows, TILE) block of models; w_ref: (rows, 1) weights (SMEM);
    o_ref: (TILE,) accumulator, resident across the chunk axis."""
    k = pl.program_id(1)
    rows = x_ref.shape[0]

    def fold(acc):
        for i in range(rows):               # rows is static (unrolled adds)
            x = x_ref[i, :]
            if n % rows:                    # ragged last chunk: rows past N
                # hold unspecified memory; with their zero weights they
                # add an exact 0 * 0
                x = jnp.where(k * rows + i < n, x, 0.0)
            acc = acc + x * w_ref[i, 0]
        return acc

    # the first chunk starts from a literal zero, as a single-pass sum does
    @pl.when(k == 0)
    def _first():
        o_ref[...] = fold(jnp.zeros(o_ref.shape, jnp.float32))

    @pl.when(k > 0)
    def _rest():
        o_ref[...] = fold(o_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def agg_tiled(stacked: jnp.ndarray, weights: jnp.ndarray, *, interpret: bool):
    """stacked: (N, T) f32 with T % TILE == 0; weights: (N,) f32 -> (T,)."""
    n, t = stacked.shape
    rows = min(n, ROWS)
    chunks = pl.cdiv(n, rows)
    w = jnp.zeros((chunks * rows, 1), jnp.float32).at[:n, 0].set(
        weights.astype(jnp.float32))
    return pl.pallas_call(
        functools.partial(_agg_kernel, n=n),
        grid=(t // TILE, chunks),
        in_specs=[
            pl.BlockSpec((rows, 1), lambda i, k: (k, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((rows, TILE), lambda i, k: (k, i)),
        ],
        out_specs=pl.BlockSpec((TILE,), lambda i, k: (i,)),
        out_shape=jax.ShapeDtypeStruct((t,), jnp.float32),
        interpret=interpret,
    )(w, stacked.astype(jnp.float32))
