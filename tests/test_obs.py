"""Telemetry layer unit tests: log-bucketed histograms (observe, merge,
percentiles), event rings (overwrite-oldest, dropped accounting), the
thread-local trace and telemetry contexts, the profiler annotation hook,
the exporters (JSON, Prometheus text, Perfetto trace events with
cross-site flow chains), the store-side hooks a single-process
``ModelStore`` exercises end to end, and the spans of the runtimes,
training, privacy, clustering and serving.  Cross-topology parity
lives in ``test_store_equivalence.py``; wire propagation in
``test_tcp_transport.py`` / ``test_wire_protocol.py``.
"""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import AggregationConfig, ModelMeta, UpdateDelta
from repro.core.fedccl import ClusterSpaceConfig, FedCCL, FedCCLConfig
from repro.core.protocol import ClientSpec
from repro.core.store import ModelStore, ShardedModelStore
from repro.obs import clock
from repro.obs.export import (
    merged_metrics,
    metrics_json,
    perfetto_trace,
    prometheus_text,
    write_perfetto,
)
from repro.obs.metrics import (
    LogHistogram,
    MetricsRegistry,
    bucket_le,
    merge_hist_dumps,
    merge_metric_dumps,
    percentile_from_buckets,
)
from repro.obs.record import (
    RING_CAP,
    Telemetry,
    current_telemetry,
    current_trace,
    maybe_span,
    telemetry_scope,
    trace_scope,
)
from repro.privacy.secure_agg import PairwiseMasker
from repro.training.fed_solar import make_train_fn

# =========================================================================
# metrics: log-bucketed histograms
# =========================================================================


def test_log_histogram_bucketing_by_bit_length():
    h = LogHistogram()
    for v in (0, 1, 2, 3, 1000, -5):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 6 and s["max"] == 1000 and s["sum"] == 1006
    assert s["buckets"][0] == 2          # 0 and clamped -5
    assert s["buckets"][1] == 1          # 1
    assert s["buckets"][2] == 2          # 2, 3
    assert s["buckets"][1000 .bit_length()] == 1
    assert bucket_le(0) == 0 and bucket_le(3) == 7


def test_log_histogram_merge_equals_single_recorder():
    rng = np.random.default_rng(7)
    vals = [int(v) for v in rng.integers(0, 1 << 20, size=200)]
    one, a, b = LogHistogram(), LogHistogram(), LogHistogram()
    for i, v in enumerate(vals):
        one.observe(v)
        (a if i % 2 else b).observe(v)
    assert merge_hist_dumps(a.snapshot(), b.snapshot()) == one.snapshot()


def test_percentiles_within_one_octave():
    h = LogHistogram()
    for _ in range(100):
        h.observe(1000)                  # bucket 10: [512, 1024)
    s = h.snapshot()
    p50 = percentile_from_buckets(s, 0.50)
    assert 512 <= p50 < 1024             # geometric midpoint of the octave
    assert percentile_from_buckets(s, 0.99) == p50
    assert percentile_from_buckets({"buckets": [0] * 64, "count": 0,
                                    "sum": 0, "max": 0}, 0.5) == 0.0


def test_registry_dump_merge_gauges_sum_counters_add():
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    r1.counter("folds").inc(3)
    r2.counter("folds").inc(4)
    r1.gauge("wire_tx_bytes").set(100)
    r2.gauge("wire_tx_bytes").set(50)
    r1.histogram("lat").observe(8)
    r2.histogram("lat").observe(9)
    m = merge_metric_dumps(r1.dump(), r2.dump())
    assert m["counters"]["folds"] == 7
    assert m["gauges"]["wire_tx_bytes"] == 150.0   # per-site totals sum
    assert m["histograms"]["lat"]["count"] == 2


# =========================================================================
# event rings + trace context
# =========================================================================


def test_ring_overwrites_oldest_and_counts_dropped():
    tel = Telemetry(ring_cap=4)
    for i in range(7):
        tel.event(f"e{i}", t0_ns=i, dur_ns=0)
    dump = tel.dump()
    assert dump["dropped"] == 3
    assert [ev[2] for ev in dump["events"]] == ["e3", "e4", "e5", "e6"]


def test_dump_merges_threads_in_timestamp_order():
    tel = Telemetry()
    tel.event("main", t0_ns=5, dur_ns=0)

    def other():
        tel.event("worker", t0_ns=1, dur_ns=0)

    t = threading.Thread(target=other)
    t.start()
    t.join()
    names = [ev[2] for ev in tel.dump()["events"]]
    assert names == ["worker", "main"]


def test_trace_scope_nests_and_restores():
    assert current_trace() == 0
    with trace_scope(7):
        assert current_trace() == 7
        with trace_scope(9):
            assert current_trace() == 9
        assert current_trace() == 7
    assert current_trace() == 0


def test_trace_context_is_thread_local():
    seen = {}

    def other():
        seen["other"] = current_trace()

    with trace_scope(5):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert seen["other"] == 0


def test_sampling_thins_traces_only():
    tel = Telemetry(sample_n=3)
    assert [tel.sampled(n) for n in range(7)] == \
        [True, False, False, True, False, False, True]
    assert Telemetry().sample_n == 1     # default: trace everything


def test_span_records_one_event_with_duration():
    tel = Telemetry()
    with tel.span("mirror_sync", trace=3, args={"shard": 1}):
        pass
    ((t0, dur, name, trace, tid, args),) = tel.dump()["events"]
    assert name == "mirror_sync" and trace == 3 and args == {"shard": 1}
    assert dur >= 0 and tid == threading.get_ident()


# =========================================================================
# exporters
# =========================================================================


def _site(name, events=(), metrics=None):
    reg = MetricsRegistry()
    for mname, vals in (metrics or {}).items():
        for v in vals:
            reg.histogram(mname).observe(v)
    return {"site": name, "anchor": [1_000_000, 0], "sample_n": 1,
            "dropped": 0, "events": [list(e) for e in events],
            "metrics": reg.dump()}


def test_metrics_json_shape_and_percentile_fields():
    dump = {"sites": [_site("parent", metrics={"lat": [10, 20, 3000]}),
                      _site("shard-0", metrics={"lat": [15]})]}
    rep = metrics_json(dump)
    assert rep["sites"] == ["parent", "shard-0"]
    h = rep["histograms"]["lat"]
    assert h["count"] == 4 and h["max"] == 3000
    assert set(h) == {"count", "sum", "mean", "max", "p50", "p95", "p99"}
    assert h["p50"] <= h["p95"] <= h["p99"] <= 4096   # octave bound


def test_prometheus_text_format():
    dump = {"sites": [_site("parent", metrics={"lat_ns": [1, 1, 900]})]}
    text = prometheus_text(dump)
    lines = text.splitlines()
    assert "# TYPE fedccl_lat_ns histogram" in lines
    assert 'fedccl_lat_ns_bucket{le="1"} 2' in lines
    assert 'fedccl_lat_ns_bucket{le="+Inf"} 3' in lines
    assert "fedccl_lat_ns_sum 902" in lines
    assert "fedccl_lat_ns_count 3" in lines
    # cumulative buckets are monotone
    cum = [int(ln.rsplit(" ", 1)[1]) for ln in lines if "_bucket{" in ln]
    assert cum == sorted(cum)


def test_perfetto_chains_flow_across_sites_via_trace_and_seq():
    """The cross-boundary join: submit/enqueue share a trace id on the
    parent; the worker fold shares the wire seq with the enqueue — the
    exporter must emit one flow chain crossing both process tracks."""
    # event tuples: (t0, dur, name, trace, tid, args)
    parent = _site("parent", events=[
        (100, 50, "submit", 5, 1, None),
        (110, 10, "enqueue", 5, 1, {"key": "c0", "seq": 9}),
    ])
    worker = _site("shard-0", events=[
        (400, 30, "worker.fold", 0, 2, {"key": "c0", "seqs": [9]}),
    ])
    trace = perfetto_trace({"sites": [parent, worker]})
    evs = trace["traceEvents"]
    flows = [e for e in evs if e["ph"] in ("s", "t", "f")]
    # chain 5 (trace) links submit->enqueue; chain 10 (seq 9 + 1) links
    # enqueue->worker.fold — so flows appear on BOTH pids
    assert {f["pid"] for f in flows} == {0, 1}
    assert {f["id"] for f in flows} == {5, 10}
    x = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in x} == {"submit", "enqueue", "worker.fold"}
    # re-anchoring: ts = (wall + (t - mono)) / 1000 us
    assert min(e["ts"] for e in x) == (1_000_000 + 100) / 1000.0


def test_perfetto_trace_equals_seq_plus_one_joins_chain_once():
    """Regression: stores mint trace ids from the submit seq counter, so a
    traced enqueue carries ``trace == seq + 1`` — it must appear in that
    flow chain once, not once per linking scheme."""
    parent = _site("parent", events=[
        (100, 50, "submit", 10, 1, None),
        (110, 10, "enqueue", 10, 1, {"key": "c0", "seq": 9}),
    ])
    worker = _site("shard-0", events=[
        (400, 30, "worker.fold", 0, 2, {"key": "c0", "seqs": [9]}),
    ])
    evs = perfetto_trace({"sites": [parent, worker]})["traceEvents"]
    flows = [e for e in evs if e["ph"] in ("s", "t", "f")]
    assert {f["id"] for f in flows} == {10}      # one merged chain
    assert [f["ph"] for f in sorted(flows, key=lambda f: f["ts"])] == \
        ["s", "t", "f"]                          # each hop exactly once
    assert {f["pid"] for f in flows} == {0, 1}


def test_perfetto_singleton_chains_emit_no_flow():
    dump = {"sites": [_site("parent",
                            events=[(1, 1, "submit", 42, 1, None)])]}
    evs = perfetto_trace(dump)["traceEvents"]
    assert [e["ph"] for e in evs if e["ph"] not in ("M",)] == ["X"]


def test_write_perfetto_is_loadable_json(tmp_path):
    path = tmp_path / "trace.json"
    write_perfetto({"sites": [_site("parent",
                                    events=[(1, 2, "fold", 0, 1, None)])]},
                   path)
    loaded = json.loads(path.read_text())
    assert loaded["displayTimeUnit"] == "ms"
    assert any(e.get("name") == "fold" for e in loaded["traceEvents"])


# =========================================================================
# store hooks (single-process end to end) + regressions
# =========================================================================


def _tree(rng):
    return {"w": rng.normal(size=8).astype(np.float32)}


def test_max_queue_depth_empty_store_is_zero_not_valueerror():
    """Regression: the bare ``max(...)`` raised ValueError when a store
    reported no submit sinks (e.g. inspected before its shards exist)."""
    store = ModelStore(_tree(np.random.default_rng(0)), ["c0"])

    class _NoSinks(ModelStore):
        def _all_submit_stats(self):
            return []

    empty = _NoSinks(_tree(np.random.default_rng(0)), ["c0"])
    assert empty.max_queue_depth == 0
    assert store.max_queue_depth == 0        # fresh store: nothing queued


def test_model_store_records_metrics_events_and_trace_chain():
    rng = np.random.default_rng(1)
    tel = Telemetry()
    store = ModelStore(_tree(rng), ["c0"],
                       agg_cfg=AggregationConfig(sequential_fast_path=False),
                       batch_aggregation=True, max_coalesce=4, telemetry=tel)
    for _ in range(3):
        store.handle_model_update("cluster", "c0", _tree(rng),
                                  ModelMeta(5, 1, 1), UpdateDelta(5, 1, 1))
    store.drain_all()
    dump = store.telemetry_dump()
    assert [s["site"] for s in dump["sites"]] == ["parent"]

    m = merged_metrics(dump)
    assert m["histograms"]["submit_latency_ns"]["count"] == 3
    assert m["histograms"]["queue_depth"]["count"] == 3
    assert m["histograms"]["coalesce_batch"]["count"] >= 1
    assert m["histograms"]["staleness_at_fold"]["count"] == 3
    assert m["histograms"]["drain_fold_ns_host"]["count"] >= 1

    events = dump["sites"][0]["events"]
    by_name = {}
    for t0, dur, name, trace, tid, args in events:
        by_name.setdefault(name, []).append(trace)
    # every submit minted a distinct trace id; its enqueue adopted it
    assert sorted(by_name["submit"]) == sorted(by_name["enqueue"])
    assert len(set(by_name["submit"])) == 3 and 0 not in by_name["submit"]


def test_telemetry_off_store_records_nothing():
    rng = np.random.default_rng(2)
    store = ModelStore(_tree(rng), ["c0"], batch_aggregation=True)
    store.handle_model_update("cluster", "c0", _tree(rng),
                              ModelMeta(5, 1, 1), UpdateDelta(5, 1, 1))
    store.drain_all()
    assert store.telemetry is None
    assert store.telemetry_dump() == {"sites": []}
    assert current_trace() == 0              # no leaked trace context


# =========================================================================
# profiler annotations, the telemetry scope, the off path
# =========================================================================


class _Hook:
    """An annotation hook that logs what it enters and leaves."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        hook = self

        class _Ann:
            def __enter__(self):
                hook.log.append(("enter", name))

            def __exit__(self, *exc):
                hook.log.append(("exit", name))
        return _Ann()


def test_span_enters_annotation_hook_under_prefixed_name():
    hook = _Hook()
    tel = Telemetry(annotate=hook)
    with tel.span("fold", args={"key": "c0"}):
        with tel.span("train.step", ring=False, hist="train_step_host_ns"):
            pass
    assert hook.log == [("enter", "fedccl.fold"), ("enter", "fedccl.train.step"),
                        ("exit", "fedccl.train.step"), ("exit", "fedccl.fold")]
    # a span kept out of the rings still feeds the profiler and its histogram
    assert [ev[2] for ev in tel.dump()["events"]] == ["fold"]
    assert tel.metrics.histogram("train_step_host_ns").snapshot()["count"] == 1


def test_maybe_span_is_a_span_or_a_shared_no_op(monkeypatch):
    tel = Telemetry()
    with maybe_span(tel, "fold", args={"key": "c0"}, hist="h") as sp:
        assert sp.args == {"key": "c0"}
    assert [ev[2] for ev in tel.dump()["events"]] == ["fold"]
    assert tel.metrics.histogram("h").snapshot()["count"] == 1

    def touched():
        raise AssertionError("telemetry off, yet the clock was read")

    monkeypatch.setattr(clock, "monotonic_ns", touched)
    off = maybe_span(None, "fold", args={"key": "c0"}, hist="h")
    assert off is maybe_span(None, "enqueue")
    with off as sp:
        assert sp is None


def test_span_args_filled_in_before_exit_reach_the_event():
    tel = Telemetry()
    with tel.span("fold", args={"key": "c0"}, hist="drain_fold_ns_host") as sp:
        sp.args["waits"] = [sp.t0 - sp.t0]
    ((_, dur, name, _, _, args),) = tel.dump()["events"]
    assert (name, args) == ("fold", {"key": "c0", "waits": [0]})
    assert tel.metrics.histogram("drain_fold_ns_host").snapshot()["sum"] == dur


def test_telemetry_scope_nests_restores_and_is_thread_local():
    a, b = Telemetry(), Telemetry()
    seen = {}
    assert current_telemetry() is None
    with telemetry_scope(a):
        with telemetry_scope(b):
            assert current_telemetry() is b
        assert current_telemetry() is a
        t = threading.Thread(target=lambda: seen.setdefault(
            "other", current_telemetry()))
        t.start()
        t.join()
    assert current_telemetry() is None and seen["other"] is None


#: events the busiest traced benchmark window recorded on one thread
#: (``fleet-async``, 51 s on a TPU v5e; PERF.md)
WINDOW_EVENTS = 5_434


def test_ring_default_holds_a_traced_window():
    tel = Telemetry()
    assert tel.ring_cap == RING_CAP >= 2 * WINDOW_EVENTS
    for i in range(WINDOW_EVENTS):
        tel.event("submit", i, 1)
    dump = tel.dump()
    assert dump["dropped"] == 0 and len(dump["events"]) == WINDOW_EVENTS


def _fake_sgd(params, batch, anchor, lam):
    """A stand-in training step: moves the weights by the batch's mean."""
    return {"w": params["w"] + 0.01 * jnp.mean(batch["target"])}, \
        jnp.float32(0.0)


def _windows(rng, n, sign):
    """Tiny solar-shaped windows; ``minute`` stays on the host."""
    return {"history": rng.normal(size=(n, 6, 2)).astype(np.float32),
            "forecast": rng.normal(size=(n, 3, 2)).astype(np.float32),
            "target": np.full((n, 3), sign, np.float32),
            "minute": np.zeros((n, 3), np.int64)}


def _fed(seed=0, **cfg):
    """Two location groups of two sites, trained by ``make_train_fn``."""
    rng = np.random.default_rng(seed)
    fed = FedCCL(FedCCLConfig(
        spaces=(ClusterSpaceConfig("loc", eps=100.0, min_samples=2,
                                   metric="haversine"),),
        ewc_lambda=0.05, seed=seed, **cfg),
        {"w": jnp.zeros(4)}, make_train_fn(_fake_sgd, epochs=2, batch_size=4))
    specs = [ClientSpec(f"{g}{i}", {"loc": np.array([lat, lon])
                                    + rng.normal(0, .1, 2)},
                        _windows(rng, 10, sign), speed=1.0 + i)
             for g, lat, lon, sign in (("a", 48.2, 16.4, 1.0),
                                       ("b", 52.5, 13.4, -1.0))
             for i in range(2)]
    fed.setup(specs)
    return fed


@pytest.mark.parametrize("privacy", [
    {"batch_aggregation": True},
    {"dp_clip": 1.0, "secure_agg": True, "dropout_prob": 0.3}])
def test_telemetry_off_reads_no_clock_and_no_hook(monkeypatch, privacy):
    """With telemetry off, no site of the runtime, store, training,
    privacy, clustering or serving reads a clock or builds an annotation."""
    def touched(*a, **k):
        raise AssertionError("telemetry off, yet a clock or hook was touched")

    monkeypatch.setattr(clock, "monotonic_ns", touched)
    monkeypatch.setattr(clock, "monotonic", touched)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", touched)
    fed = _fed(**privacy)
    fed.run(rounds=2)
    fed.join(ClientSpec("new", {"loc": np.array([48.3, 16.5])},
                        _windows(np.random.default_rng(1), 4, 1.0)))
    assert fed.model_for("a0")[1].startswith("cluster:")
    assert fed.store.telemetry is None and current_telemetry() is None


def _names(fed):
    return [ev[2] for ev in fed.store.telemetry_dump()["sites"][0]["events"]]


def _within(events, inner, outer):
    """Every ``inner`` event lies inside some ``outer`` event."""
    spans = [(ev[0], ev[0] + ev[1]) for ev in events if ev[2] == outer]
    return all(any(s <= ev[0] and ev[0] + ev[1] <= e for s, e in spans)
               for ev in events if ev[2] == inner)


def test_sim_runtime_spans_each_handled_event_and_training():
    fed = _fed(batch_aggregation=True, telemetry=True)
    fed.run(rounds=2)
    events = fed.store.telemetry_dump()["sites"][0]["events"]
    names = _names(fed)
    # one span per handled event, none across a client's whole round
    assert names.count("client.start") == names.count("client.update") == 8
    assert "client.round" not in names
    # local + one cluster + global per round: 3 train_fn calls
    assert names.count("client.train") == 3 * 8
    trains = [e for e in events if e[2] == "client.train"]
    outer = [(e[0], e[0] + e[1]) for e in events
             if e[2] in ("client.start", "client.update")]
    assert all(any(s <= t[0] and t[0] + t[1] <= e for s, e in outer)
               for t in trains)
    assert {e[5]["client"] for e in events if e[2] == "client.start"} == \
        {"a0", "a1", "b0", "b1"}
    m = fed.store.telemetry.metrics.dump()
    # 10 windows, batch 4: 3 steps an epoch, 2 epochs, 24 calls
    assert m["histograms"]["train_step_host_ns"]["count"] == 24 * 6
    assert m["counters"]["windows_trained"] == 24 * 20
    assert "train.step" not in names          # profiler and histogram only


def test_train_fn_counts_the_bytes_it_uploads():
    rng = np.random.default_rng(3)
    windows = _windows(rng, 10, 1.0)
    per_window = sum(windows[k][0].nbytes
                     for k in ("history", "forecast", "target"))
    tel = Telemetry()
    train = make_train_fn(_fake_sgd, epochs=3, batch_size=4)
    with telemetry_scope(tel):
        _, n, epochs = train({"w": jnp.zeros(4)}, windows,
                             np.random.default_rng(0), None)
    counters = tel.metrics.dump()["counters"]
    assert counters["h2d_bytes"] == 10 * 3 * per_window   # no ``minute``
    assert counters["windows_trained"] == n == 30 and epochs == 3
    assert tel.metrics.histogram("train_step_host_ns").snapshot()["count"] \
        == 3 * 3


def test_train_fn_draws_the_same_batches_traced_or_not():
    windows = _windows(np.random.default_rng(4), 10, 1.0)
    train = make_train_fn(
        lambda p, b, a, lam: ({"w": p["w"] * 1.5 + b["target"][:, 0].sum()},
                              jnp.float32(0.0)), epochs=2, batch_size=3)
    windows["target"] = np.arange(30, dtype=np.float32).reshape(10, 3)
    plain, _, _ = train({"w": jnp.zeros(())}, windows,
                        np.random.default_rng(9), None)
    with telemetry_scope(Telemetry()):
        traced, _, _ = train({"w": jnp.zeros(())}, windows,
                             np.random.default_rng(9), None)
    assert float(plain["w"]) == float(traced["w"])


def test_secure_runtime_spans_rounds_models_and_privacy():
    fed = _fed(dp_clip=1.0, secure_agg=True, dropout_prob=0.3,
               telemetry=True)
    fed.run(rounds=3)
    events = fed.store.telemetry_dump()["sites"][0]["events"]
    names = _names(fed)
    folds = names.count("secure_fold")
    assert names.count("secure.round") == 3
    assert names.count("secure.model") == folds > 0
    assert names.count("mask") == names.count("dp.release") == \
        sum(e[5]["n"] for e in events if e[2] == "secure_fold")
    assert fed.store.n_secure_recoveries == sum(
        e[5]["missing"] for e in events if e[2] == "reconstruct") > 0
    assert _within(events, "secure_fold", "secure.model")
    assert _within(events, "mask", "secure.model")
    assert _within(events, "secure.model", "secure.round")
    # each mask and each recovery uploads one flat model of 4 floats, and
    # each of the 10-window trainings its batches
    per_window = sum(fed.clients[0].spec.dataset[k][0].nbytes
                     for k in ("history", "forecast", "target"))
    m = fed.store.telemetry.metrics.dump()["counters"]
    trained = m["windows_trained"]
    assert m["h2d_bytes"] == trained * per_window + 16 * (
        names.count("mask") + names.count("reconstruct"))


def test_join_and_serving_spans():
    fed = _fed(telemetry=True)
    fed.join(ClientSpec("new", {"loc": np.array([48.3, 16.5])},
                        _windows(np.random.default_rng(1), 4, 1.0)))
    for cid in ("a0", "b1", "new"):
        fed.model_for(cid)
    events = fed.store.telemetry_dump()["sites"][0]["events"]
    # oldest first: the join opens before its parts
    assert [e[2] for e in events] == ["join", "join.cluster", "join.model"]
    assert _within(events, "join.cluster", "join")
    assert _within(events, "join.model", "join")
    assert events[0][5] == {"client": "new"}
    # serve.read feeds the profiler and its histogram, not the rings
    assert fed.store.telemetry.metrics.histogram(
        "serve_read_ns").snapshot()["count"] == 3


def test_profiler_records_fedccl_spans_inside_outer_annotation(tmp_path):
    """Telemetry on, every span is a ``jax.profiler`` annotation: a trace
    taken around a federation round holds the program's ``fedccl.`` spans
    on its host timeline, inside an enclosing annotation of the caller."""
    fed = _fed(batch_aggregation=True, telemetry=True)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            fed.run(rounds=1)
    (path,) = tmp_path.glob("**/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in pd.planes if plane.name.startswith("/host")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(("fedccl.", "bench."))]
    names = [n for n, _, _ in spans]
    for name in ("fedccl.client.start", "fedccl.client.update",
                 "fedccl.client.train", "fedccl.train.step", "fedccl.submit",
                 "fedccl.enqueue", "fedccl.fold"):
        assert name in names, name
    ((_, lo, hi),) = [sp for sp in spans if sp[0] == "bench.window"]
    assert all(lo <= s and e <= hi for n, s, e in spans
               if n.startswith("fedccl."))
    # as many profiler spans as ring events of each kind
    ring = _names(fed)
    assert names.count("fedccl.client.train") == ring.count("client.train")
    assert names.count("fedccl.fold") == ring.count("fold")


# =========================================================================
# queue waits: each update's time in its queue, exact under a fixed clock
# =========================================================================


class _Clock:
    """A monotonic clock the test sets by hand."""

    def __init__(self):
        self.now = 0

    def __call__(self):
        return self.now


def test_flat_drain_records_each_updates_queue_wait(monkeypatch):
    clk = _Clock()
    monkeypatch.setattr(clock, "monotonic_ns", clk)
    rng = np.random.default_rng(5)
    tel = Telemetry()
    store = ModelStore(_tree(rng), ["c0"],
                       agg_cfg=AggregationConfig(sequential_fast_path=False),
                       batch_aggregation=True, max_coalesce=2, telemetry=tel)
    for t in (100, 250, 400):
        clk.now = t
        store.handle_model_update("cluster", "c0", _tree(rng),
                                  ModelMeta(5, 1, 1), UpdateDelta(5, 1, 1))
    clk.now = 1_000
    assert store.drain("cluster", "c0") == 3
    folds = [ev for ev in tel.dump()["events"] if ev[2] == "fold"]
    # max_coalesce 2: two folds, both starting at 1,000
    assert [ev[5]["waits"] for ev in folds] == [[900, 750], [600]]
    assert [(ev[0], ev[5]["n"]) for ev in folds] == [(1_000, 2), (1_000, 1)]
    hist = tel.metrics.histogram("queue_wait_ns").snapshot()
    assert (hist["count"], hist["sum"], hist["max"]) == (3, 2_250, 900)
    enq = [ev for ev in tel.dump()["events"] if ev[2] == "enqueue"]
    assert [ev[0] for ev in enq] == [100, 250, 400]


def test_secure_drain_records_each_updates_wait_from_submit(monkeypatch):
    clk = _Clock()
    monkeypatch.setattr(clock, "monotonic_ns", clk)
    rng = np.random.default_rng(6)
    tel = Telemetry()
    base = _tree(rng)
    store = ModelStore(base, ["c0"], masker=PairwiseMasker(mask_scale=0.0),
                       telemetry=tel)
    members = ["a", "b", "c"]
    for t, cid in ((100, "a"), (300, "b")):     # "c" drops out
        clk.now = t
        store.submit_secure("cluster", "c0", cid, 0, _tree(rng),
                            UpdateDelta(5, 1, 1))
    clk.now = 1_000
    assert store.drain_secure("cluster", "c0", 0, members) == 2
    ((t0, _, name, _, _, args),) = tel.dump()["events"]
    assert (t0, name) == (1_000, "secure_fold")
    assert args == {"key": "c0", "n": 2, "waits": [900, 700]}
    assert tel.metrics.histogram("queue_wait_ns").snapshot()["sum"] == 1_600
    assert tel.metrics.histogram("secure_round_ns").snapshot()["count"] == 1


@pytest.mark.parametrize("store_cls,level,batched", [
    (ModelStore, "cluster", True),
    (ShardedModelStore, "global", False),
    (ShardedModelStore, "global", True),
    (ShardedModelStore, "cluster", True)])
def test_every_in_process_queue_stamps_its_updates(monkeypatch, store_cls,
                                                   level, batched):
    """One update queued singly, then two by ``submit_many`` (or singly):
    every update a fold takes has its wait, whatever the entry point, the
    store flavor or the tier (the sharded global tier folds two-level)."""
    clk = _Clock()
    monkeypatch.setattr(clock, "monotonic_ns", clk)
    rng = np.random.default_rng(7)
    tel = Telemetry()
    store = store_cls(_tree(rng), ["c0"],
                      agg_cfg=AggregationConfig(sequential_fast_path=False),
                      batch_aggregation=True, telemetry=tel)
    key = "c0" if level == "cluster" else None
    ups = [(_tree(rng), ModelMeta(5, 1, 1), UpdateDelta(5, 1, 1))
           for _ in range(3)]
    clk.now = 100
    store.handle_model_update(level, key, *ups[0])
    clk.now = 300
    if batched:
        store.submit_many(level, key, ups[1:])
    else:
        for u in ups[1:]:
            store.handle_model_update(level, key, *u)
    clk.now = 1_000
    assert store.drain(level, key) == 3
    ((t0, _, _, _, _, args),) = [ev for ev in tel.dump()["events"]
                                 if ev[2] == "fold"]
    assert (t0, args["n"], sorted(args["waits"])) == (1_000, 3,
                                                      [700, 700, 900])
    assert tel.metrics.histogram("queue_wait_ns").snapshot()["count"] == 3


def test_deferred_counters_are_read_once_per_dump():
    """``MetricsRegistry.defer``: counts kept elsewhere (on a device) join
    the dump's counters, added to what a counter of the same name holds,
    read at each dump and never in between."""
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reads = []
    held = {"x.0": 3, "x.1": 4}

    def read():
        reads.append(1)
        return dict(held)

    reg.defer("x", read)
    reg.counter("x.0").inc(10)
    assert reads == []
    assert reg.dump()["counters"] == {"x.0": 13, "x.1": 4}
    held["x.1"] = 9
    assert reg.dump()["counters"]["x.1"] == 9 and len(reads) == 2
    reg.defer("x", lambda: {"x.1": 1})          # replaces the first
    assert reg.dump()["counters"] == {"x.0": 10, "x.1": 1}
