"""Model FLOP utilisation of serving: forward FLOPs per window
(``costs.forward_flops``) times the forecasts served, over the traced
window and the chip's bf16 peak (%)."""

from costs import forward_flops


def read(run):
    n = run.counters.get("forecasts")
    if not n or not run.trace or not run.peak or run.trace.window_s <= 0:
        return None
    return (100.0 * forward_flops(run.model) * n / run.trace.window_s
            / run.peak["bf16_flops_per_s"])
