"""Analytic work of the benchmark's kernels and steps, from shapes alone.

These are the numerators of the rooflines and of MFU.  They count the work
the algorithm needs, at the dtype the configuration states, never what a
particular implementation happens to move: padding, recomputation and
extra copies are the implementation's cost and show as a lower share.
"""

from __future__ import annotations


def lstm_step_flops(in_dim: int, hidden: int) -> int:
    """One LSTM cell on one sample: the fused gate matmuls
    ``x @ wx`` (in x 4H) and ``h @ wh`` (H x 4H), two FLOPs per MAC."""
    return 2 * in_dim * 4 * hidden + 2 * hidden * 4 * hidden


def forward_flops(model: dict) -> int:
    """Forward FLOPs of the forecaster on one window: the encoder over the
    history, the decoder over the horizon, and the linear head."""
    h = model["hidden_size"]
    enc = model["history_steps"] * lstm_step_flops(model["history_channels"], h)
    dec = model["horizon_steps"] * lstm_step_flops(model["forecast_channels"], h)
    head = 2 * h * model["horizon_steps"]
    return enc + dec + head


def train_flops(model: dict) -> int:
    """Forward and backward on one window: three times the forward."""
    return 3 * forward_flops(model)


def param_count(model: dict) -> int:
    h = model["hidden_size"]
    lstm = lambda i: i * 4 * h + h * 4 * h + 4 * h  # noqa: E731
    return (lstm(model["history_channels"]) + lstm(model["forecast_channels"])
            + h + 1)


def dtype_bytes(model: dict) -> int:
    return {"float32": 4, "bfloat16": 2}[model["dtype"]]


def fold_bytes(n_sets: int, n_params: int, itemsize: int) -> int:
    """One N-way fold: each of the ``n_sets`` parameter sets that carry
    weight (the base included) is read once and the result written once."""
    return (n_sets + 1) * n_params * itemsize


def dp_bytes(n_params: int, itemsize: int) -> int:
    """One clip-and-noise release: the delta and the noise read, the
    release written."""
    return 3 * n_params * itemsize


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> float | None:
    """The least time the chip could take for this work (the larger of
    FLOPs over peak FLOP/s and bytes over peak bytes/s) over the time it
    took, in percent.  None where no time was measured."""
    if seconds <= 0:
        return None
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
