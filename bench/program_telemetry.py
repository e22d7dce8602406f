"""What the program's own telemetry (``repro.obs``) recorded in a traced
run, for the per-layer metrics that read it.

``Run.telemetry`` is the store's telemetry dump, taken once the window has
closed: ``{"sites": [{"events": [...], "metrics": {...}}, ...]}``, each
event ``[t0_ns, dur_ns, name, trace, thread, args]`` on the host's
``CLOCK_MONOTONIC``.  ``Run`` carries no window bounds, so the whole dump
counts.  That is the window alone only because no driver's set-up records
an instrument that its cells' metrics read: the training cells' set-up
trains and folds with telemetry off, and ``forecast-burst``'s serves
nothing (``bench/tests/test_program_metrics.py`` checks each set-up).
"""

from __future__ import annotations


def events(run, names) -> list:
    """The program's events of the given names, oldest first."""
    out = [ev for site in (run.telemetry or {}).get("sites", [])
           for ev in site.get("events", []) if ev[2] in names]
    return sorted(out, key=lambda ev: ev[0])


def _metrics(run) -> dict:
    from repro.obs.export import merged_metrics

    return merged_metrics({"sites": (run.telemetry or {}).get("sites", [])})


def counter(run, name: str):
    """A counter's value, or None where never counted."""
    return _metrics(run).get("counters", {}).get(name)


def mean_ns(run, name: str):
    """A histogram's exact mean (its sum over its count), or None where
    nothing was observed."""
    h = _metrics(run).get("histograms", {}).get(name)
    if not h or not h["count"]:
        return None
    return h["sum"] / h["count"]
