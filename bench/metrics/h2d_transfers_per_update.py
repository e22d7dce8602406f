"""Host-to-device transfers the program makes per client update: its
``h2d_transfers`` counter (one per upload: an epoch's staged training
arrays, a pairwise mask, a dropout recovery) over the updates its ``fold``
and ``secure_fold`` spans folded in the window."""

from program_telemetry import counter, events


def read(run):
    transfers = counter(run, "h2d_transfers")
    updates = sum((ev[5] or {}).get("n", 0)
                  for ev in events(run, ("fold", "secure_fold")))
    if not transfers or not updates:
        return None
    return transfers / updates
