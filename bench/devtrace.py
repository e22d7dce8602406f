"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb`` under ``<dir>/plugins/profile/``.
Its device planes (``/device:TPU:<n>``) hold one line of XLA modules (one
event per program run, named after the jitted function) and one line of
XLA ops; the host plane holds the benchmark's ``bench.<name>``
annotations on the same clock.  From these:

* busy time: the union of the op intervals on each device, averaged over
  the devices that ran anything;
* module time: the device time of each jitted program by name, with the
  number of runs;
* the device operations that took most time;
* the longest idle gaps, each named by the innermost ``bench.`` host span
  that covers the gap's middle (what the host was doing meanwhile).

All of it is cut to the ``bench.window`` span, so that the profiler's own
start and stop fall outside.

The pure functions below work on plain ``(name, start_ns, dur_ns)``
tuples, so the tests can check them without a trace.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PREFIX = "bench."
#: the host span that marks the measured window
WINDOW = "window"


def union_ns(intervals) -> int:
    """Total length of the union of ``(start, end)`` intervals."""
    total = 0
    end_max = None
    for s, e in sorted(intervals):
        if end_max is None or s > end_max:
            total += e - s
            end_max = e
        elif e > end_max:
            total += e - end_max
            end_max = e
    return total


def merged(intervals) -> list:
    """The union as disjoint sorted intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def module_name(name: str) -> str:
    """``jit_sgd_step(1234)`` -> ``jit_sgd_step``."""
    return name.split("(", 1)[0].strip()


def time_by_name(events, key=lambda n: n) -> dict:
    """``{key(name): [seconds, count]}`` over ``(name, start, dur)``."""
    out: dict = {}
    for name, _, dur in events:
        k = key(name)
        acc = out.setdefault(k, [0.0, 0])
        acc[0] += dur * 1e-9
        acc[1] += 1
    return out


def idle_gaps(busy, spans, k: int = 10, min_ns: int = 0) -> list:
    """The ``k`` longest gaps between busy intervals, as ``[label,
    seconds]``: the innermost host span ``(name, start, end)`` that covers
    the gap's middle, or ``"(no bench span)"``."""
    gaps = []
    runs = merged(busy)
    for (_, e0), (s1, _) in zip(runs, runs[1:]):
        if s1 - e0 > min_ns:
            gaps.append((e0, s1))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        mid = (s + e) / 2
        inner = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        label = (min(inner, key=lambda sp: sp[2] - sp[1])[0] if inner
                 else "(no bench span)")
        out.append([label, (e - s) * 1e-9])
    return out


@dataclass
class Summary:
    window_s: float
    busy_s: float
    modules: dict = field(default_factory=dict)   # name -> [seconds, runs]
    top_ops: list = field(default_factory=list)
    idle_gaps: list = field(default_factory=list)
    devices: int = 0


def read_xplane(path: str):
    """``(devices, host_spans)``: per device plane its module and op events
    as ``(name, start_ns, dur_ns)``; host spans as ``(name, start, end)``
    for the benchmark's own annotations."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            mods, ops = [], []
            for line in plane.lines:
                target = {"XLA Modules": mods, "XLA Ops": ops}.get(line.name)
                if target is None:
                    continue
                for ev in line.events:
                    target.append((ev.name, int(ev.start_ns),
                                   int(ev.duration_ns)))
            if mods or ops:
                devices.append((mods, ops))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((ev.name[len(HOST_PREFIX):], s,
                                      s + int(ev.duration_ns)))
    return devices, spans


def clip(events, lo: int, hi: int) -> list:
    """``(name, start, dur)`` events cut to the interval ``[lo, hi]``."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def reduce(devices, spans, window_s: float) -> Summary:
    """Busy time, module times, top operations and idle gaps from parsed
    events.  Where the host spans hold the benchmark's ``window`` span,
    everything is cut to it and the window's length is read from it.
    Device operations are the op events where the trace has them, else
    the programs."""
    win = [sp for sp in spans if sp[0] == WINDOW]
    if win:
        lo, hi = win[0][1], win[0][2]
        window_s = (hi - lo) * 1e-9
        devices = [(clip(m, lo, hi), clip(o, lo, hi)) for m, o in devices]
        spans = [sp for sp in spans if sp[0] != WINDOW]
    devices = [(m, o) for m, o in devices if m or o]
    if not devices:
        return Summary(window_s=window_s, busy_s=0.0)
    busy = [union_ns((s, s + d) for _, s, d in (ops or mods)) * 1e-9
            for mods, ops in devices]
    modules = time_by_name([e for mods, _ in devices for e in mods],
                           module_name)
    for v in modules.values():
        v[0] /= len(devices)
    events = [e for _, ops in devices for e in ops] or [
        (module_name(n), s, d) for mods, _ in devices for n, s, d in mods]
    top = sorted(time_by_name(events).items(), key=lambda kv: -kv[1][0])[:10]
    mods0, ops0 = devices[0]
    gaps = idle_gaps([(s, s + d) for _, s, d in (ops0 or mods0)], spans)
    return Summary(window_s=window_s, busy_s=sum(busy) / len(busy),
                   modules=modules,
                   top_ops=[[n, v[0] / len(devices)] for n, v in top],
                   idle_gaps=gaps, devices=len(devices))


def summarize(profile_dir, window_s: float) -> Summary:
    files = glob.glob(os.path.join(str(profile_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    devices, spans = read_xplane(max(files, key=os.path.getmtime))
    return reduce(devices, spans, window_s)
