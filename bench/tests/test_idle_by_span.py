"""The idle-time analysis of ``bench/tools/idle_by_span.py``: innermost
spans, the middle rule against the time split, long calls; then on the
recorded v5e trace and on a CPU trace of a tiny federation, whose
``fedccl.`` spans it names inside the benchmark's own."""

import pathlib
import sys

import pytest

import harness

DATA = pathlib.Path(__file__).resolve().parent / "data"
idle = harness.load_module(harness.BENCH / "tools" / "idle_by_span.py")


def test_segments_take_the_span_opened_last():
    spans = [("train", 0, 100), ("fedccl.train.step", 10, 40),
             ("sgd_step", 30, 40), ("fedccl.fold", 60, 90)]
    segs = idle.segments(spans, 0, 120)
    assert [(a, b, n) for a, b, n in segs] == [
        (0, 10, "train"), (10, 30, "fedccl.train.step"), (30, 40, "sgd_step"),
        (40, 60, "train"), (60, 90, "fedccl.fold"), (90, 100, "train"),
        (100, 120, idle.UNNAMED)]


def test_middle_rule_and_time_split():
    spans = [("train", 0, 100), ("fedccl.train.step", 10, 40)]
    segs = idle.segments(spans, 0, 100)
    gaps = idle.idle_gaps([(0, 5), (50, 60)], 0, 100)
    assert gaps == [(5, 50), (60, 100)]
    # the gap 5..50 has its middle in the step; the time split gives the
    # step only its 30 of the 45
    assert idle.by_middle(gaps, segs) == pytest.approx(
        {"fedccl.train.step": 45e-9, "train": 40e-9})
    assert idle.by_time(gaps, segs) == pytest.approx(
        {"train": 55e-9, "fedccl.train.step": 30e-9})


def test_long_calls_are_the_innermost_long_spans():
    spans = [("fedccl.client.start", 0, 2_000), ("fedccl.train.step", 100,
                                                  1_100),
             ("fedccl.submit", 1_200, 1_300),
             # a model's whole secure round: long, but many calls
             ("fedccl.secure.model", 2_000, 3_000)]
    host = [("tid 7", "DevicePutWithSharding", 150, 1_050),
            ("tid 7", "short", 1_200, 1_210)]
    (call,) = idle.long_calls(spans, [(0, 100), (1_000, 1_500)], host, 0,
                              min_ns=500)
    assert call["span"] == "fedccl.train.step"
    assert call["device_busy_share"] == pytest.approx(0.1)
    assert call["host_events"] == [["DevicePutWithSharding", "tid 7",
                                    pytest.approx(9e-7)]]


def test_recorded_trace():
    out = idle.analyse(str(DATA / "v5e_small.xplane.pb"))
    assert set(out["idle_by_span"]) == {"drain", "predict", "privatize"}
    assert sum(out["idle_by_time"].values()) == pytest.approx(out["idle_s"])
    assert sum(out["idle_by_span"].values()) == pytest.approx(out["idle_s"])
    assert 0 < out["busy_s"] < out["window_s"]


def test_program_spans_named_inside_the_benchmarks(tmp_path):
    """A CPU trace (no device plane, so the whole window reads idle) of a
    tiny federation round with telemetry on, inside ``bench.window`` and
    a ``bench.train`` span: the time split names the program's spans."""
    harness.prepare_jax(False, 1)
    if str(harness.CHECKOUT / "src") not in sys.path:
        sys.path.insert(0, str(harness.CHECKOUT / "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.fedccl import ClusterSpaceConfig, FedCCL, FedCCLConfig
    from repro.core.protocol import ClientSpec
    from repro.training.fed_solar import make_train_fn

    def sgd(params, batch, anchor, lam):
        return {"w": params["w"] + jnp.mean(batch["target"])}, 0.0

    rng = np.random.default_rng(0)
    fed = FedCCL(FedCCLConfig(
        spaces=(ClusterSpaceConfig("loc", eps=100.0, min_samples=2,
                                   metric="haversine"),),
        batch_aggregation=True, telemetry=True),
        {"w": jnp.zeros(3)}, make_train_fn(sgd, epochs=1, batch_size=4))
    windows = {"history": np.zeros((8, 5, 2), np.float32),
               "forecast": np.zeros((8, 2, 2), np.float32),
               "target": np.ones((8, 2), np.float32)}
    fed.setup([ClientSpec(f"s{i}", {"loc": np.array([48.0, 16.0])
                                    + rng.normal(0, .1, 2)}, windows)
               for i in range(3)])
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.train"):
                fed.run(rounds=1)
    (path,) = tmp_path.glob("**/*.xplane.pb")
    out = idle.analyse(str(path))
    named = set(out["idle_by_time"])
    assert {"fedccl.client.start", "fedccl.client.update",
            "fedccl.train.step", "fedccl.fold"} <= named
    assert out["span_counts"]["train"] == 1
    assert out["span_counts"]["fedccl.client.start"] == 3
    assert sum(out["idle_by_time"].values()) == pytest.approx(
        out["window_s"])
    assert out["round_level_share"] < 1
