"""Public wrappers: gather pytrees a chunk at a time, pad to the tile
multiple, run the kernel, cut the result back into the leaves.  This is the
TPU-server FedCCL aggregation path (AggregationConfig.use_pallas=True routes
Algorithm 2 through here).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.fedavg_agg.fedavg_agg import TILE, agg_tiled

#: elements of the flat parameter vector one kernel call folds, so a
#: fold's transient buffers (the N float32 sets stacked, the float32
#: result) stay at most ``CHUNK`` wide whatever the model's size: 2**26
#: floats, 256 MiB a set; a 425 M-parameter model folds in 7 calls, each
#: chunk's gather and scatter a program of its own.
CHUNK = 1 << 26


def aggregate_flat(stacked: jnp.ndarray, weights, *, interpret=None) -> jnp.ndarray:
    """stacked: (N, T) arbitrary T; returns (T,) f32 weighted sum."""
    interpret = interpret_mode(interpret)
    n, t = stacked.shape
    pad = (-t) % TILE
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    out = agg_tiled(stacked, jnp.asarray(weights, jnp.float32),
                    interpret=interpret)
    return out[:t]


def aggregate_pytrees(trees: list, weights: list, *, interpret=None):
    """Weighted sum of N identically-structured pytrees via the kernel.

    This is the coalesced server drain's kernel route: a batch of N queued
    updates costs one streaming pass, not N-1 pairwise passes.  The trees'
    flat vector is folded ``CHUNK`` elements at a time: one program gathers
    every tree's piece of a chunk into an (N, chunk) float32 block, the
    kernel sums it, one program cuts the result back into the leaves'
    pieces and dtypes.  A tree of at most ``CHUNK`` elements, as the solar
    forecaster is, takes one chunk; the kernel sums each element alone, so
    the chunking moves no bit.
    """
    if not trees:
        raise ValueError("aggregate_pytrees needs at least one pytree")
    if len(trees) != len(weights):
        raise ValueError(f"{len(trees)} pytrees vs {len(weights)} weights")
    if len(trees) == 1 and float(weights[0]) == 1.0:
        return trees[0]         # identity combination: skip the round trip
    leaves = [jax.tree.leaves(t) for t in trees]
    sizes = [int(x.size) for x in leaves[0]]
    pieces: list[list] = [[] for _ in sizes]
    for spans in _chunk_spans(sizes, CHUNK):
        idx = tuple(i for i, _, _ in spans)
        cuts = tuple((a, b) for _, a, b in spans)
        stacked = _gather_chunk(
            tuple(tuple(lv[i] for i in idx) for lv in leaves), cuts)
        flat_out = aggregate_flat(stacked, weights, interpret=interpret)
        # a leaf the chunk holds whole comes back in its shape
        shapes = tuple(leaves[0][i].shape if b - a == sizes[i] else (b - a,)
                       for i, a, b in spans)
        dtypes = tuple(jnp.dtype(leaves[0][i].dtype).name for i in idx)
        for i, piece in zip(idx, _scatter_chunk(flat_out, cuts, shapes, dtypes),
                            strict=True):
            pieces[i].append(piece)
    out = []
    for p, x in zip(pieces, leaves[0], strict=True):
        if not p:               # an empty leaf lies in no chunk
            out.append(jnp.zeros(x.shape, x.dtype))
        else:
            out.append(p[0] if len(p) == 1
                       else jnp.concatenate(p).reshape(x.shape))
    return jax.tree.unflatten(jax.tree.structure(trees[0]), out)


def _chunk_spans(sizes, chunk: int):
    """For each ``chunk``-element window of the flat vector, the leaves it
    covers as ``(leaf, start, stop)`` within each leaf's flat view."""
    total = sum(sizes)
    for lo in range(0, total, chunk):
        hi = min(lo + chunk, total)
        spans, off = [], 0
        for i, n in enumerate(sizes):
            a, b = max(lo, off), min(hi, off + n)
            if a < b:
                spans.append((i, a - off, b - off))
            off += n
        yield spans


@functools.partial(jax.jit, static_argnames=("cuts",))
def _gather_chunk(trees, cuts):
    """(N, chunk) float32: each tree's pieces of one chunk, concatenated."""
    return jnp.stack([jnp.concatenate([jnp.ravel(x)[a:b].astype(jnp.float32)
                                       for x, (a, b) in zip(t, cuts, strict=True)])
                      for t in trees])


@functools.partial(jax.jit, static_argnames=("cuts", "shapes", "dtypes"))
def _scatter_chunk(flat, cuts, shapes, dtypes):
    """One chunk's folded vector cut into its leaves' pieces, shapes and
    dtypes."""
    out, off = [], 0
    for (a, b), shape, dt in zip(cuts, shapes, dtypes, strict=True):
        out.append(flat[off:off + b - a].astype(dt).reshape(shape))
        off += b - a
    return tuple(out)
