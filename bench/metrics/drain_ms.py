"""Mean duration of the store's drains as the program records them: its
``repro.obs`` ``fold`` and ``secure_fold`` events (ms).  These time host
preparation and dispatch of the fold, not the fold on the device: the
program stamps them without waiting for the result."""


def read(run):
    durs = [ev[1] for site in run.telemetry.get("sites", [])
            for ev in site.get("events", [])
            if ev[2] in ("fold", "secure_fold")]
    if not durs:
        return None
    return sum(durs) / len(durs) * 1e-6
