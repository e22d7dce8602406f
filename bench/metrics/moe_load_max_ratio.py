"""Balance of the routing over the held experts: the most assignments any
held expert took in the window over the held experts' mean, from the
program's ``moe_assignments_held.*`` counters (1 is even)."""

from program_telemetry import _metrics


def read(run):
    counters = _metrics(run).get("counters", {})
    held = [v for k, v in counters.items()
            if k.startswith("moe_assignments_held.")]
    if not held or not sum(held):
        return None
    return max(held) * len(held) / sum(held)
