"""Device time of one forecast: the program's ``jit_predict`` runs in the
trace, per run (ms)."""


def read(run):
    t = run.trace.modules.get("jit_predict") if run.trace else None
    if not t or not t[1]:
        return None
    return 1e3 * t[0] / t[1]
