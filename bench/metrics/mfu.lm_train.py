"""Model FLOP utilisation of the silo fine-tuning: the FLOPs of the tokens
trained in the window (the program's ``tokens_trained`` counter, in
sequences of ``seq_len``; the routed experts' work from its
``moe_assignments_held.*`` counters) over the traced window and the chip's
bf16 peak (%)."""

from lm_costs import held_assignments, train_flops
from program_telemetry import _metrics


def read(run):
    counters = _metrics(run).get("counters", {})
    tokens = counters.get("tokens_trained")
    n = held_assignments(counters)
    if not tokens or n is None or not run.trace or not run.peak \
            or run.trace.window_s <= 0:
        return None
    flops = train_flops(run.model, tokens // run.model["seq_len"], n)
    return 100.0 * flops / run.trace.window_s / run.peak["bf16_flops_per_s"]
