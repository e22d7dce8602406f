"""Wrapper: pad batch to the tile, reshape bias, dispatch the fused cell.

Drop-in for ``repro.models.lstm.lstm_cell`` (params dict with wx/wh/b).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.lstm_cell.lstm_cell import BATCH_TILE, lstm_step_tiled


def lstm_cell_fused(p: dict, x, h, c, *, interpret=None):
    interpret = interpret_mode(interpret)
    B = x.shape[0]
    pad = (-B) % BATCH_TILE
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        h = jnp.pad(h, ((0, pad), (0, 0)))
        c = jnp.pad(c, ((0, pad), (0, 0)))
    hn, cn = lstm_step_tiled(
        x.astype(jnp.float32), h.astype(jnp.float32), c.astype(jnp.float32),
        p["wx"].astype(jnp.float32), p["wh"].astype(jnp.float32),
        p["b"].reshape(1, -1).astype(jnp.float32), interpret=interpret)
    return hn[:B], cn[:B]
