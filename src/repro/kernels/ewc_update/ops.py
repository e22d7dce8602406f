"""Wrapper: pad + dispatch the fused EWC penalty/gradient kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.ewc_update.ewc_update import TILE, ewc_tiled


def ewc_penalty_grad_flat(lam, grads, params, anchor, fisher=None, *,
                          interpret=None):
    """Flat (T,) tensors; fisher=None means L2-SP (F=1).
    Returns (g_out, penalty_loss)."""
    interpret = interpret_mode(interpret)
    t = grads.shape[0]
    if fisher is None:
        fisher = jnp.ones_like(grads, jnp.float32)
    pad = (-t) % TILE
    arrs = [jnp.pad(a.astype(jnp.float32), (0, pad))
            for a in (grads, params, anchor, fisher)]
    go, loss = ewc_tiled(jnp.float32(lam), *arrs, interpret=interpret)
    return go[:t], loss
