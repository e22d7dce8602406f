"""The per-layer metrics that read the program's own telemetry: on a
synthetic ``Run``, on a dump without the program's new instruments (an
older program), on each driver's set-up (which must record none of what
its cells read), and on a tiny federation's real dump."""

import sys

import numpy as np
import pytest

import harness
from test_drivers import SMALL

# the readers merge the program's dump with ``repro.obs``, as in a run
if str(harness.CHECKOUT / "src") not in sys.path:
    sys.path.insert(0, str(harness.CHECKOUT / "src"))

NEW = ("queue_wait_p95_ms", "train_host_ms", "h2d_bytes_per_update",
       "serve_read_ms")


def _read(name, run):
    return harness.load_module(
        harness.BENCH / "metrics" / f"{name}.py").read(run)


def _hist(values):
    return {"count": len(values), "sum": sum(values), "buckets": [],
            "max": max(values, default=0)}


def _fold(t0, waits, name="fold", n=None):
    return [t0, 10, name, 0, 1, {"key": "k",
                                 "n": len(waits) if n is None else n,
                                 "waits": waits}]


def _run(events, counters=None, hists=None):
    dump = {"sites": [{"site": "parent", "events": events, "dropped": 0,
                       "metrics": {"counters": counters or {}, "gauges": {},
                                   "histograms": hists or {}}}]}
    return harness.Run(model={}, peak={}, window_s=1.0, spans={},
                       counters={}, telemetry=dump)


@pytest.mark.parametrize("name,expected", [
    ("queue_wait_p95_ms", np.percentile([1e6, 2e6, 3e6], 95) * 1e-6),
    ("train_host_ms", 2.0),
    ("h2d_bytes_per_update", 600 / 3),
    ("serve_read_ms", 0.5)])
def test_reader_on_a_synthetic_dump(name, expected):
    run = _run([_fold(1_000, [1_000_000, 2_000_000]),
                _fold(1_500, [3_000_000], "secure_fold")],
               {"h2d_bytes": 600},
               {"train_step_host_ns": _hist([1_000_000, 3_000_000]),
                "serve_read_ns": _hist([500_000])})
    assert _read(name, run) == pytest.approx(expected)


def test_queue_wait_is_left_out_where_a_fold_lists_fewer_waits():
    """A fold of three updates of which one carries no stamp: the metric
    would read a subset of the updates, so it reads nothing."""
    full = _run([_fold(1_000, [1_000_000, 2_000_000])])
    assert _read("queue_wait_p95_ms", full) is not None
    short = _run([_fold(1_000, [1_000_000, 2_000_000]),
                  _fold(2_000, [1_000_000], n=2)])
    assert _read("queue_wait_p95_ms", short) is None


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_set_up_records_nothing_the_cells_metrics_read(cell):
    """The readers take the whole dump: each driver's set-up, telemetry on
    as in a traced run, must leave every new metric of its cell empty."""
    harness.prepare_jax(False, 1)
    c = harness.Cell.find(cell)
    ctx = harness.Context(cell=c, seed=2**33 + 5, trace=True,
                          small=SMALL[cell])
    driver = harness.load_module(
        c.bench / "drivers" / f"{c.traffic['driver']}.py").make(ctx)
    driver.setup()
    try:
        run = _run([])
        run.telemetry = driver.telemetry()
        assert run.telemetry["sites"], "telemetry is on in a traced run"
        names = [m["name"] for m in c.per_layer if m["name"] in NEW]
        assert names
        for name in names:
            assert _read(name, run) is None, name
    finally:
        driver.release()


@pytest.mark.parametrize("name", ["queue_wait_p95_ms", "train_host_ms",
                                  "h2d_bytes_per_update", "serve_read_ms"])
def test_reader_reads_nothing_from_a_program_without_the_instruments(name):
    """A program that records ``fold`` events without waits and none of the
    new counters or histograms: the metric is left out, nothing raises."""
    old = [[1_200, 10, "fold", 0, 1, {"key": "k", "n": 2}]]
    assert _read(name, _run(old, hists={"queue_depth": _hist([1])})) is None
    assert _read(name, _run([])) is None
    assert _read(name, harness.Run(model={}, peak={}, window_s=1.0,
                                   spans={}, counters={})) is None


def test_readers_on_a_tiny_federations_dump():
    """A real dump: updates' bytes are the windows trained per update times
    a window's bytes, and every mean and p95 is positive."""
    harness.prepare_jax(False, 1)
    import jax.numpy as jnp

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
    fed.run(rounds=2)
    for i in range(3):
        fed.model_for(f"s{i}")
    run = _run([])
    run.telemetry = fed.store.telemetry_dump()
    per_window = (5 * 2 + 2 * 2 + 2) * 4
    # a round trains the local, the cluster and the global model: 3 calls
    # of 8 windows for 2 updates
    assert _read("h2d_bytes_per_update", run) == 3 * 8 * per_window / 2
    for name in ("queue_wait_p95_ms", "train_host_ms", "serve_read_ms"):
        assert _read(name, run) > 0, name
