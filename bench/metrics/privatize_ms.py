"""Host time to privatize one update: the benchmark's spans around each DP
release and each pairwise-mask call, ending on ready arrays, summed and
divided by the releases (ms)."""


def read(run):
    n = run.counters.get("dp_releases")
    if not n:
        return None
    total = sum(run.spans.get("privatize", [])) + sum(run.spans.get("mask", []))
    return 1e3 * total / n
