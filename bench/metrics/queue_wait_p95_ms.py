"""p95 over the window's updates of their wait in the store's queue: from
an update's enqueue (a secure update's submit) to the start of the drain's
fold that takes it, as the program's ``fold`` and ``secure_fold`` spans list
each update's wait (``args["waits"]``); exact, not bucketed (ms).

Left out where the folds list fewer waits than they folded updates (an
older program, or a queue path that stamps nothing), so the metric never
reads a subset of the updates."""

import numpy as np

from program_telemetry import events


def read(run):
    folds = [ev[5] or {} for ev in events(run, ("fold", "secure_fold"))]
    waits = [w for args in folds for w in args.get("waits", ())]
    if not waits or len(waits) != sum(args.get("n", 0) for args in folds):
        return None
    return float(np.percentile(waits, 95)) * 1e-6
