"""Public wrappers: flatten pytrees, pad to tile multiple, run the kernel,

unflatten.  This is the TPU-server FedCCL aggregation path
(AggregationConfig.use_pallas=True routes Algorithm 2 through here).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.fedavg_agg.fedavg_agg import TILE, agg_tiled
from repro.utils.tree import unflatten_params


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
    updates costs one flatten + one streaming pass, not N-1 pairwise passes.
    """
    if not trees:
        raise ValueError("aggregate_pytrees needs at least one pytree")
    if len(trees) != len(weights):
        raise ValueError(f"{len(trees)} pytrees vs {len(weights)} weights")
    if len(trees) == 1 and float(weights[0]) == 1.0:
        return trees[0]         # identity combination: skip the round trip
    flats = [jnp.concatenate([jnp.ravel(x).astype(jnp.float32)
                              for x in jax.tree.leaves(t)]) for t in trees]
    stacked = jnp.stack(flats)
    flat_out = aggregate_flat(stacked, weights, interpret=interpret)
    return unflatten_params(flat_out, trees[0])
