"""``h2d_transfers_per_update``: the program's ``h2d_transfers`` counter
over the updates its folds took, on a synthetic dump, on a dump without
the counter or without a fold (it reads nothing), on each training
driver's set-up (which must record none), and on a tiny federation."""

import sys

import numpy as np
import pytest

import harness
from test_drivers import SMALL

# the reader merges the program's dump with ``repro.obs``, as in a run
if str(harness.CHECKOUT / "src") not in sys.path:
    sys.path.insert(0, str(harness.CHECKOUT / "src"))

NAME = "h2d_transfers_per_update"


def _read(run):
    return harness.load_module(
        harness.BENCH / "metrics" / f"{NAME}.py").read(run)


def _run(events, counters=None):
    dump = {"sites": [{"site": "parent", "events": events, "dropped": 0,
                       "metrics": {"counters": counters or {}, "gauges": {},
                                   "histograms": {}}}]}
    return harness.Run(model={}, peak={}, window_s=1.0, spans={},
                       counters={}, telemetry=dump)


def _fold(t0, n, name="fold"):
    return [t0, 10, name, 0, 1, {"key": "k", "n": n}]


def test_reads_transfers_over_folded_updates():
    run = _run([_fold(1_000, 2), _fold(1_500, 1, "secure_fold")],
               {"h2d_transfers": 12, "h2d_bytes": 600})
    assert _read(run) == pytest.approx(12 / 3)


@pytest.mark.parametrize("events,counters", [
    ([], {"h2d_transfers": 12}),                     # no fold ran
    ([_fold(1_000, 2)], {"h2d_bytes": 600}),         # an older program
    ([], {})])
def test_reads_nothing_without_a_fold_or_the_counter(events, counters):
    assert _read(_run(events, counters)) is None
    assert _read(harness.Run(model={}, peak={}, window_s=1.0, spans={},
                             counters={})) is None


@pytest.mark.parametrize("cell", ["fleet-async", "secure-dp-rounds"])
def test_set_up_records_no_transfer(cell):
    """The reader takes the whole dump: a training driver's set-up,
    telemetry on as in a traced run, must leave it empty."""
    harness.prepare_jax(False, 1)
    c = harness.Cell.find(cell)
    assert NAME in [m["name"] for m in c.per_layer]
    ctx = harness.Context(cell=c, seed=2**33 + 7, trace=True,
                          small=SMALL[cell])
    driver = harness.load_module(
        c.bench / "drivers" / f"{c.traffic['driver']}.py").make(ctx)
    driver.setup()
    try:
        run = _run([])
        run.telemetry = driver.telemetry()
        assert run.telemetry["sites"], "telemetry is on in a traced run"
        assert _read(run) is None
    finally:
        driver.release()


def test_reads_a_tiny_federations_uploads():
    """A real dump: three uploads an epoch, one epoch a training, three
    trainings (local, cluster, global) for two updates a round."""
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
    windows = {"history": np.zeros((10, 5, 2), np.float32),
               "forecast": np.zeros((10, 2, 2), np.float32),
               "target": np.ones((10, 2), np.float32)}
    fed.setup([ClientSpec(f"s{i}", {"loc": np.array([48.0, 16.0])
                                    + rng.normal(0, .1, 2)}, windows)
               for i in range(3)])
    fed.run(rounds=2)
    run = _run([])
    run.telemetry = fed.store.telemetry_dump()
    assert _read(run) == 3 * 3 / 2
