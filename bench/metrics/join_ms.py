"""Host time of one new installation's ``FedCCL.join`` (Predict & Evolve:
incremental clustering and the model it is given), from the benchmark's
span around the call (ms)."""


def read(run):
    d = run.spans.get("join")
    if not d:
        return None
    return 1e3 * sum(d) / len(d)
