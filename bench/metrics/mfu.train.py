"""Model FLOP utilisation of client training: forward and backward FLOPs
per window (``costs.train_flops``) times the windows trained in the
window, over the traced window and the chip's bf16 peak (%)."""

from costs import train_flops


def read(run):
    n = run.counters.get("windows_trained")
    if not n or not run.trace or not run.peak or run.trace.window_s <= 0:
        return None
    return (100.0 * train_flops(run.model) * n / run.trace.window_s
            / run.peak["bf16_flops_per_s"])
