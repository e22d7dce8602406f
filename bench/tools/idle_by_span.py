#!/usr/bin/env python3
"""Where a traced window's idle device time went, named by host span, and
the long calls on the program's thread.

    python3 bench/tools/idle_by_span.py --workload <cell> --seed <n> [--seed <m> ...]
        [--seconds 51] [--stop-on-stall] [--out runs.jsonl]
    python3 bench/tools/idle_by_span.py --xplane <trace.xplane.pb>

The first form makes traced runs of a cell through the harness, as
``bench/run.py --trace 1`` does, keeps each run's profiler trace and
prints one JSON line per run; the second reads a trace already taken.

The trace's host plane holds two kinds of span on the device's clock: the
benchmark's ``bench.<name>`` annotations, named here without the prefix
as ``devtrace`` names them, and the program's own ``fedccl.<name>`` spans
(``repro.obs``, telemetry on), which keep their prefix.  Idle time is the
part of the ``bench.window`` span in which the first device ran no
operation.  It is named two ways:

* ``idle_by_span``: each gap whole, by the innermost span over its
  middle, as ``devtrace.idle_gaps`` names the longest gaps, but summed
  over all gaps;
* ``idle_by_time``: each instant of a gap by the innermost span open
  then.  With phases of a millisecond, the middle rule hands whole gaps
  to whichever phase the middle falls in; the time split does not.

Innermost is the open span that opened last.  ``round_level_share`` is
the share of idle time, by the time split, that no span or only a
round-level one names (``window``, ``fedccl.client.start``,
``fedccl.client.update``, ``fedccl.secure.round``).

A long call is a span of ``--stall-s`` (0.4 s) or more that holds no
other span that long, among the spans of one call (all but the
round-level ones and ``fedccl.secure.model``, which gather many calls):
where the program's thread stalled.  Each comes
with the device's busy share meanwhile and the profiler's own host
events of 50 ms or more that overlap it (the runtime's, such as
``DevicePutWithSharding``).  With ``--workload``, each line also gives the
program's telemetry dump: events kept, events ``dropped`` by the rings,
and the count of each event name, and the window's end-to-end numbers
under tracing (``end_to_end``).
"""

from __future__ import annotations

import argparse
import bisect
import glob
import heapq
import json
import os
import pathlib
import sys
import tempfile

BENCH = pathlib.Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from devtrace import DEVICE_PLANE, WINDOW, merged  # noqa: E402

PROGRAM_PREFIX = "fedccl."
UNNAMED = "(no span)"
ROUND_LEVEL = (WINDOW, "fedccl.client.start", "fedccl.client.update",
               "fedccl.secure.round")
#: spans that gather many calls, so that a long one is no stall
GATHERING = ROUND_LEVEL + ("fedccl.secure.model",)
#: host events of the profiler's own kept for the long calls
HOST_EVENT_MIN_NS = 50_000_000


def read(path: str):
    """``(busy, spans, host_events)`` of one ``.xplane.pb``: the first
    device's busy intervals (its ops, else its programs), the ``bench.``
    and ``fedccl.`` host spans as ``(name, start, end)``, and the other
    host events of 50 ms or more as ``(line, name, start, end)``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    busy, spans, host = None, [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name) and busy is None:
            lines = {ln.name: [(int(e.start_ns),
                                int(e.start_ns + e.duration_ns))
                               for e in ln.events] for ln in plane.lines}
            busy = lines.get("XLA Ops") or lines.get("XLA Modules") or None
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                for e in ln.events:
                    s, d = int(e.start_ns), int(e.duration_ns)
                    if e.name.startswith("bench."):
                        spans.append((e.name[len("bench."):], s, s + d))
                    elif e.name.startswith(PROGRAM_PREFIX):
                        spans.append((e.name, s, s + d))
                    elif d >= HOST_EVENT_MIN_NS:
                        host.append((ln.name, e.name, s, s + d))
    return busy or [], spans, host


def segments(spans, lo: int, hi: int) -> list:
    """``[lo, hi]`` cut into ``(start, end, label)`` pieces, each labelled
    by the innermost span open over it (the one opened last; of two opened
    at once, the one that ends first), or ``UNNAMED``."""
    order = sorted(spans, key=lambda sp: (sp[1], sp[2]))
    points = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                                if lo < t < hi})
    heap, out, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(order) and order[i][1] <= a:
            name, s, e = order[i]
            heapq.heappush(heap, (-s, e, name))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        out.append((a, b, heap[0][2] if heap else UNNAMED))
    return out


def idle_gaps(busy, lo: int, hi: int) -> list:
    """The idle intervals of ``[lo, hi]``: where no busy interval runs."""
    runs = merged([(max(s, lo), min(e, hi)) for s, e in busy
                   if e > lo and s < hi])
    edges = [lo] + [t for r in runs for t in r] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def by_middle(gaps, segs) -> dict:
    """Seconds of idle time per label, each gap by its middle's label."""
    starts = [s for s, _, _ in segs]
    out: dict = {}
    for s, e in gaps:
        j = bisect.bisect_right(starts, (s + e) / 2) - 1
        label = segs[j][2] if j >= 0 else UNNAMED
        out[label] = out.get(label, 0.0) + (e - s) * 1e-9
    return _ranked(out)


def by_time(gaps, segs) -> dict:
    """Seconds of idle time per label, each instant by its own label."""
    out: dict = {}
    j = 0
    for gs, ge in gaps:
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < ge:
            a, b, label = segs[k]
            ov = min(ge, b) - max(gs, a)
            if ov > 0:
                out[label] = out.get(label, 0.0) + ov * 1e-9
            k += 1
    return _ranked(out)


def long_calls(spans, busy, host, lo: int, min_ns: int) -> list:
    """The spans of one call of ``min_ns`` or more that hold no other that
    long."""
    long = sorted((sp for sp in spans if sp[2] - sp[1] >= min_ns
                   and sp[0] not in GATHERING),
                  key=lambda sp: (sp[1], -sp[2]))
    runs = merged(busy)
    out = []
    for sp in long:
        name, s, e = sp
        if any(o is not sp and s <= o[1] and o[2] <= e
               and (o[1], o[2]) != (s, e) for o in long):
            continue
        on = sum(max(0, min(e, re) - max(s, rs)) for rs, re in runs)
        over = sorted(((min(e, he) - max(s, hs)) * 1e-9, line, hname)
                      for line, hname, hs, he in host if he > s and hs < e)
        out.append({"span": name, "s": (e - s) * 1e-9,
                    "at_s": (s - lo) * 1e-9,
                    "device_busy_share": on / (e - s),
                    "host_events": [[n, line, t]
                                    for t, line, n in over[::-1][:8]]})
    return out


def analyse(path: str, stall_s: float = 0.4) -> dict:
    """The readings of one trace, cut to its ``bench.window`` span (to
    the extent of its spans and device work where it has none)."""
    busy, spans, host = read(path)
    win = [sp for sp in spans if sp[0] == WINDOW]
    if win:
        _, lo, hi = win[0]
    else:
        lo = min([s for _, s, _ in spans] + [s for s, _ in busy])
        hi = max([e for _, _, e in spans] + [e for _, e in busy])
    spans = [sp for sp in spans if sp[0] != WINDOW and sp[2] > lo
             and sp[1] < hi]
    gaps = idle_gaps(busy, lo, hi)
    segs = segments(spans, lo, hi)
    timed = by_time(gaps, segs)
    idle = sum(timed.values())
    coarse = sum(v for k, v in timed.items()
                 if k == UNNAMED or k in ROUND_LEVEL)
    counts: dict = {}
    for name, _, _ in spans:
        counts[name] = counts.get(name, 0) + 1
    return {"window_s": (hi - lo) * 1e-9, "idle_s": idle,
            "busy_s": (hi - lo) * 1e-9 - idle,
            "round_level_share": coarse / idle if idle else 0.0,
            "idle_by_span": by_middle(gaps, segs), "idle_by_time": timed,
            "long_calls": long_calls(spans, busy, host, lo,
                                     int(stall_s * 1e9)),
            "span_counts": dict(sorted(counts.items()))}


def _ranked(d: dict) -> dict:
    return dict(sorted(d.items(), key=lambda kv: -kv[1]))


def _telemetry_summary(dump: dict) -> dict:
    sites = (dump or {}).get("sites", [])
    names: dict = {}
    for site in sites:
        for ev in site.get("events", []):
            names[ev[2]] = names.get(ev[2], 0) + 1
    return {"events": sum(names.values()),
            "dropped": sum(site.get("dropped", 0) for site in sites),
            "by_name": dict(sorted(names.items()))}


def run_traced(cell_name: str, seed: int, seconds: float,
               stall_s: float) -> dict:
    """One traced run of a cell through the harness, its trace kept long
    enough to analyse, and the program's telemetry dump summarised."""
    import harness

    stash = {}
    load = harness.load_module

    def load_keeping(path):
        mod = load(path)
        if path.parent.name == "drivers":
            make = mod.make

            def make_keeping(ctx):
                driver = make(ctx)
                window, telemetry = driver.window, driver.telemetry

                def window_kept(seconds):
                    stash["window"] = window(seconds)
                    return stash["window"]

                def telemetry_kept():
                    stash["telemetry"] = telemetry()
                    return stash["telemetry"]
                driver.window = window_kept
                driver.telemetry = telemetry_kept
                return driver
            mod.make = make_keeping
        return mod

    harness.load_module = load_keeping
    try:
        with tempfile.TemporaryDirectory(prefix="idle-by-span-") as tmp:
            out = harness.run_cell(
                harness.Cell.find(cell_name), seed, seconds, True,
                out_dir=pathlib.Path(tmp),
                log=lambda m: print(m, file=sys.stderr, flush=True))
            files = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                              recursive=True)
            analysis = analyse(max(files, key=os.path.getmtime), stall_s)
    finally:
        harness.load_module = load
    return {"cell": cell_name, "seed": seed, "correct": out["correct"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "end_to_end": stash.get("window", {}).get("metrics"),
            "device": out["device"], "breakdown": out.get("breakdown"),
            "telemetry": _telemetry_summary(stash.get("telemetry")),
            **analysis}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--xplane")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, action="append", default=[])
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--stall-s", type=float, default=0.4)
    ap.add_argument("--stop-on-stall", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    if args.xplane:
        print(json.dumps(analyse(args.xplane, args.stall_s)))
        return 0
    if not args.workload or not args.seed:
        ap.error("give --xplane, or --workload and at least one --seed")
    for seed in args.seed:
        rec = run_traced(args.workload, seed, args.seconds, args.stall_s)
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        if args.stop_on_stall and rec["long_calls"]:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
