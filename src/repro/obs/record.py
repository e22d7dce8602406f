"""Ring-buffer span recorders, the ``Telemetry`` facade, and the
thread-local trace context.

Events are flat tuples ``(t0_ns, dur_ns, name, trace, tid, args)`` —
``t0_ns`` on this process's monotonic axis (re-anchored at export time via
the dump's ``anchor``, see ``repro.obs.clock``), ``trace`` a nonzero trace
id when the event belongs to a sampled submit's span chain (0 = untraced),
``args`` a small msgpack-able dict or None.

Each *thread* appends to its own fixed-size ring (one uncontended lock per
ring, taken only so snapshots from other threads see a consistent view);
``dump()`` merges every ring in timestamp order.  Rings overwrite their
oldest events when full and count the overwrites (``dropped``), so a storm
degrades the trace, never the workload.

A ``Telemetry`` may carry an annotation hook (``annotate``): a callable
from a name to a context manager, such as ``jax.profiler.TraceAnnotation``.
Every span then also enters ``fedccl.<name>``, so a profiler records it on
its host timeline, on the same clock as the device's programs.  The hook
is passed in by the caller: this package imports no JAX.

The trace context is a module-level thread-local: the store's submit path
sets it for the duration of one submit (``trace_scope``), and anything
downstream on the same thread — the TCP transport framing a message, the
in-process worker emulation folding inline — reads ``current_trace()``
without any plumbing through intermediate signatures.  Across real
process/TCP boundaries the context rides the wire frame's ``trace_ctx``
header field (``docs/WIRE_PROTOCOL.md``) and the receiving server restores
it around dispatch.
"""

from __future__ import annotations

import contextlib
import threading

from repro.obs import clock
from repro.obs.metrics import MetricsRegistry

_TLS = threading.local()

#: events each recording thread keeps before overwriting its oldest: the
#: busiest traced benchmark window (51 s of asynchronous fleet rounds on
#: one thread, ``fleet-async``) recorded at most 5,434 (PERF.md)
RING_CAP = 16384

#: prefix of every span's profiler annotation
ANNOTATION_PREFIX = "fedccl."


def current_trace() -> int:
    """The active trace id on this thread (0 = untraced)."""
    return getattr(_TLS, "trace", 0)


class trace_scope:
    """``with trace_scope(tid):`` — set the thread's trace context,
    restoring the previous one on exit.  A plain class (not a generator
    contextmanager) so the submit hot path pays two attribute writes."""

    __slots__ = ("trace", "prev")

    def __init__(self, trace: int):
        self.trace = trace

    def __enter__(self):
        self.prev = current_trace()
        _TLS.trace = self.trace
        return self

    def __exit__(self, *exc):
        _TLS.trace = self.prev
        return False


def current_telemetry():
    """The ``Telemetry`` a runtime put in scope on this thread (None = off).
    Code below the runtime — training, privacy — reads it here instead of
    taking it as an argument, so its signatures stay as they are."""
    return getattr(_TLS, "tel", None)


class telemetry_scope:
    """``with telemetry_scope(tel):`` — make ``tel`` this thread's
    ``current_telemetry()``, restoring the previous one on exit."""

    __slots__ = ("tel", "prev")

    def __init__(self, tel):
        self.tel = tel

    def __enter__(self):
        self.prev = current_telemetry()
        _TLS.tel = self.tel
        return self

    def __exit__(self, *exc):
        _TLS.tel = self.prev
        return False


#: what ``maybe_span`` returns with telemetry off: a reusable no-op context
_OFF = contextlib.nullcontext()


def maybe_span(tel, name: str, trace: int = 0, args: dict | None = None, *,
               hist: str | None = None, ring: bool = True):
    """``tel.span(...)``, or with ``tel`` None (telemetry off) a shared
    no-op context that reads no clock and enters no hook; ``with ... as
    sp`` then binds None."""
    if tel is None:
        return _OFF
    return tel.span(name, trace, args, hist=hist, ring=ring)


class _Ring:
    """One thread's fixed-capacity event ring."""

    __slots__ = ("lock", "cap", "buf", "head", "n", "dropped", "tid")

    def __init__(self, cap: int, tid: int):
        self.lock = threading.Lock()
        self.cap = cap
        self.buf: list = [None] * cap
        self.head = 0          # next write slot
        self.n = 0             # live events (<= cap)
        self.dropped = 0
        self.tid = tid

    def append(self, ev) -> None:
        with self.lock:
            self.buf[self.head] = ev
            self.head = (self.head + 1) % self.cap
            if self.n < self.cap:
                self.n += 1
            else:
                self.dropped += 1

    def snapshot(self) -> list:
        with self.lock:
            if self.n < self.cap:
                return self.buf[:self.n]
            return self.buf[self.head:] + self.buf[:self.head]


class Telemetry:
    """One process's (or one shard server's) telemetry sink: a metrics
    registry plus per-thread event rings, stamped with a wall-clock anchor
    so dumps from different processes merge onto one timeline.

    Constructed only when telemetry is *enabled* — disabled stores hold
    ``None`` and their hot paths pay a single attribute check (the
    compiled-out fast path).  ``sample_n`` thins the *trace* dimension
    (every Nth submit gets a nonzero trace id and a cross-boundary span
    chain); metrics and events are always recorded.  ``annotate`` is the
    profiler hook (module docstring); None records for the rings alone.
    """

    def __init__(self, sample_n: int = 1, ring_cap: int = RING_CAP,
                 site: str = "parent", annotate=None):
        self.sample_n = max(int(sample_n), 1)
        self.ring_cap = int(ring_cap)
        self.site = site
        self.annotate = annotate
        self.metrics = MetricsRegistry()
        self.anchor = clock.wall_anchor()
        self._rings: list[_Ring] = []
        self._rings_lock = threading.Lock()
        self._tls = threading.local()

    # ----------------------------------------------------------------- spans
    def sampled(self, n: int) -> bool:
        """Whether the ``n``-th submit (0-based) is trace-sampled."""
        return n % self.sample_n == 0

    def _ring(self) -> _Ring:
        ring = getattr(self._tls, "ring", None)
        if ring is None:
            ring = _Ring(self.ring_cap, threading.get_ident())
            self._tls.ring = ring
            with self._rings_lock:
                self._rings.append(ring)
        return ring

    def event(self, name: str, t0_ns: int, dur_ns: int, trace: int = 0,
              args: dict | None = None) -> None:
        self._ring().append((int(t0_ns), int(dur_ns), name, int(trace),
                             threading.get_ident(), args))

    class _Span:
        __slots__ = ("tel", "name", "trace", "args", "hist", "ring", "ann",
                     "t0")

        def __init__(self, tel, name, trace, args, hist, ring):
            self.tel, self.name, self.trace, self.args = \
                tel, name, trace, args
            self.hist, self.ring = hist, ring

        def __enter__(self):
            hook = self.tel.annotate
            self.ann = None
            if hook is not None:
                self.ann = hook(ANNOTATION_PREFIX + self.name)
                self.ann.__enter__()
            self.t0 = clock.monotonic_ns()
            return self

        def __exit__(self, *exc):
            t0 = self.t0
            dur = clock.monotonic_ns() - t0
            if self.ann is not None:
                self.ann.__exit__(*exc)
            if self.hist is not None:
                self.tel.metrics.histogram(self.hist).observe(dur)
            if self.ring:
                self.tel.event(self.name, t0, dur, self.trace, self.args)
            return False

    def span(self, name: str, trace: int = 0, args: dict | None = None, *,
             hist: str | None = None, ring: bool = True):
        """``with tel.span("fold", trace=t) as sp:`` — time a block and
        record it as one event; ``sp.t0`` is its start and ``sp.args`` may
        be filled in before the block ends.  ``hist`` also observes the
        duration in that histogram; ``ring=False`` keeps a span too
        frequent for the rings out of them (profiler and histogram only)."""
        return Telemetry._Span(self, name, trace, args, hist, ring)

    # ------------------------------------------------------------------ dump
    def events(self) -> list:
        """Every ring merged, oldest first."""
        with self._rings_lock:
            rings = list(self._rings)
        merged: list = []
        for ring in rings:
            merged.extend(ring.snapshot())
        merged.sort(key=lambda ev: ev[0])
        return merged

    def dropped(self) -> int:
        with self._rings_lock:
            rings = list(self._rings)
        return sum(r.dropped for r in rings)

    def dump(self) -> dict:
        """One site's telemetry as a flat msgpack-able dict (the payload
        of the ``obsdump`` wire reply)."""
        return {
            "site": self.site,
            "anchor": [self.anchor[0], self.anchor[1]],
            "sample_n": self.sample_n,
            "dropped": self.dropped(),
            "events": [[t0, dur, name, trace, tid, args]
                       for t0, dur, name, trace, tid, args in self.events()],
            "metrics": self.metrics.dump(),
        }
