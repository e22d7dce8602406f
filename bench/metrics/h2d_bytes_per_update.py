"""Bytes the program uploads to the device per client update: its
``h2d_bytes`` counter (training batches, pairwise masks, dropout
recoveries) over the updates its ``fold`` and ``secure_fold`` spans folded
in the window (B)."""

from program_telemetry import counter, events


def read(run):
    nbytes = counter(run, "h2d_bytes")
    updates = sum((ev[5] or {}).get("n", 0)
                  for ev in events(run, ("fold", "secure_fold")))
    if not nbytes or not updates:
        return None
    return nbytes / updates
