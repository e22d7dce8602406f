"""Share of the compute roofline of the LM training step: the FLOPs its
runs needed (``lm_costs.train_flops``: one sequence a run, the routed
experts' work from the program's ``moe_assignments_held.*`` counters) over
the device time of the step's program (``jit_lm_sgd_step``) and the chip's
bf16 peak (%).  The step's kernels (the held experts' grouped matmuls, the
blocked causal attention) run inside it."""

from lm_costs import held_assignments, train_flops
from program_telemetry import _metrics


def read(run):
    t = run.trace.modules.get("jit_lm_sgd_step") if run.trace else None
    n = held_assignments(_metrics(run).get("counters", {}))
    if not t or not t[1] or n is None or not run.peak or t[0] <= 0:
        return None
    return (100.0 * train_flops(run.model, t[1], n)
            / run.peak["bf16_flops_per_s"] / t[0])
