"""Device time of one training step of the silo fine-tuning cell: the
program's ``jit_lm_sgd_step`` runs in the trace, per run (ms)."""


def read(run):
    t = run.trace.modules.get("jit_lm_sgd_step") if run.trace else None
    if not t or not t[1]:
        return None
    return 1e3 * t[0] / t[1]
