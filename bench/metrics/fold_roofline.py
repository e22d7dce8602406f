"""Share of the HBM roofline of the N-way fold kernel: for each fold the
parameter sets that carry weight (the base among them) read once and the
result written once, at the configuration's dtype, counted as the window
called the kernel and not as the power-of-two padding streams; over the
device time of the kernel's program (``jit_agg_tiled``) and the chip's HBM
bandwidth (%)."""

from costs import roofline_share


def read(run):
    t = run.trace.modules.get("jit_agg_tiled") if run.trace else None
    nbytes = run.counters.get("fold_bytes")
    if not t or not t[1] or not nbytes or not run.peak:
        return None
    return roofline_share(0.0, nbytes, t[0], run.peak)
