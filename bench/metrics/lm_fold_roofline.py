"""Share of the HBM roofline of the whole fold route in the silo
fine-tuning cell: the logical bytes of the window's folds (``fold_bytes``:
the parameter sets that carry weight read once and the result written
once, at the configuration's dtype) over the device time of every program
of the route and the chip's HBM bandwidth (%).  The route is each chunk's
gather into float32 (``jit__gather_chunk``), the kernel
(``jit_agg_tiled``) and each chunk's cut back into the leaves
(``jit__scatter_chunk``).  A program that folds without the chunk programs
reads nothing."""

from costs import roofline_share

#: the device programs of one chunk of a fold, in the order they run
ROUTE = ("jit__gather_chunk", "jit_agg_tiled", "jit__scatter_chunk")


def read(run):
    mods = run.trace.modules if run.trace else {}
    if not all(mods.get(name, (0, 0))[1] for name in ROUTE):
        return None
    nbytes = run.counters.get("fold_bytes")
    if not nbytes or not run.peak:
        return None
    return roofline_share(0.0, nbytes, sum(mods[n][0] for n in ROUTE),
                          run.peak)
