"""Federation observability: spans, metrics, traces (`docs/OBSERVABILITY.md`).

The subsystem is deliberately tiny and stdlib-only so that every process in
a federation — the parent store, spawned shard workers, standalone TCP
shard servers — can carry its own ``Telemetry`` instance and ship the
resulting dump over the existing msgpack wire (the ``obsdump`` command).

  * ``repro.obs.clock``   — the ONE sanctioned clock site (fedlint FED503/
    FED602 ban raw clock reads everywhere else in the core);
  * ``repro.obs.metrics`` — counters, gauges, log-bucketed histograms;
  * ``repro.obs.record``  — per-thread ring-buffer event recorders, the
    ``Telemetry`` facade, and the thread-local trace context that rides
    wire frames across process/TCP boundaries;
  * ``repro.obs.export``  — Prometheus text, JSON percentiles, and
    Chrome/Perfetto trace-event writers.

Everything here is additive: a store constructed without a ``Telemetry``
keeps a ``None`` sink and the hot submit path pays one attribute check.
"""

from repro.obs import clock, export, metrics, record
from repro.obs.export import (
    metrics_json,
    perfetto_trace,
    prometheus_text,
    write_perfetto,
)
from repro.obs.metrics import MetricsRegistry
from repro.obs.record import (
    Telemetry,
    current_telemetry,
    current_trace,
    maybe_span,
    telemetry_scope,
    trace_scope,
)

__all__ = [
    "MetricsRegistry",
    "Telemetry",
    "clock",
    "current_telemetry",
    "current_trace",
    "export",
    "maybe_span",
    "metrics",
    "metrics_json",
    "perfetto_trace",
    "prometheus_text",
    "record",
    "telemetry_scope",
    "trace_scope",
    "write_perfetto",
]
