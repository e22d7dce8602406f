"""Client training stages each epoch once: ``make_train_fn`` gathers the
uploaded arrays in the epoch's order, uploads each whole and cuts the
batches on the device.  Every step still sees exactly the windows the
per-batch slices held, in the order ``batch_order`` draws, so training is
bit-equal to uploading batch by batch; the telemetry counts three uploads
and one staging span per epoch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.solar_lstm import SolarLSTMConfig
from repro.core.continual import make_anchor
from repro.data.windows import batch_order
from repro.models.lstm import SolarForecaster
from repro.obs.record import Telemetry, telemetry_scope
from repro.training.fed_solar import (
    UPLOADED,
    cut_batches,
    make_solar_fns,
    make_train_fn,
)


def _windows(n, seed=0, steps=(6, 3), channels=(3, 2)):
    """Windows of distinct values, so a misplaced row cannot go unseen;
    ``minute`` stays on the host."""
    rng = np.random.default_rng(seed)
    return {"history": rng.normal(size=(n, steps[0], channels[0]))
            .astype(np.float32),
            "forecast": rng.normal(size=(n, steps[1], channels[1]))
            .astype(np.float32),
            "target": rng.normal(size=(n, steps[1])).astype(np.float32),
            "minute": np.arange(n * steps[1]).reshape(n, steps[1])}


class _Recorder:
    """A stand-in ``sgd_step`` that keeps each batch it is handed."""

    def __init__(self):
        self.batches = []

    def __call__(self, params, batch, anchor, lam):
        self.batches.append({k: np.asarray(v) for k, v in batch.items()})
        return {"w": params["w"] + 1.0}, jnp.float32(0.0)


@pytest.mark.parametrize("epochs", [1, 3])
@pytest.mark.parametrize("n,batch_size", [(16, 4), (10, 4), (90, 8)])
def test_every_step_gets_the_windows_batch_order_draws(n, batch_size, epochs):
    windows = _windows(n)
    rec = _Recorder()
    params, trained, ran = make_train_fn(
        rec, epochs=epochs, batch_size=batch_size)(
        {"w": jnp.zeros(())}, windows, np.random.default_rng(7), None)
    rng = np.random.default_rng(7)
    sels = [sel for _ in range(epochs)
            for sel in batch_order(n, batch_size, rng)]
    assert len(rec.batches) == len(sels) == float(params["w"])
    for got, sel in zip(rec.batches, sels, strict=True):
        assert set(got) == set(UPLOADED)
        for k in UPLOADED:
            assert got[k].dtype == windows[k].dtype
            np.testing.assert_array_equal(got[k], windows[k][sel])
    assert (trained, ran) == (n * epochs, epochs)


def test_cut_batches_keeps_the_order_sizes_and_window_shape():
    staged = {"target": jnp.arange(20.0)}
    out = cut_batches(staged, (4, 4, 2), (("target", (2, 1)),))
    assert [b["target"].shape for b in out] == [(4, 2, 1), (4, 2, 1),
                                                 (2, 2, 1)]
    assert [b["target"].ravel().tolist() for b in out] == \
        [list(range(0, 8)), list(range(8, 16)), [16, 17, 18, 19]]


def _per_batch_train(sgd_step, params, windows, rng, anchor, lam, epochs,
                     batch_size):
    """Training as each batch's own upload: the loop staging replaced."""
    for _ in range(epochs):
        for sel in batch_order(len(windows["target"]), batch_size, rng):
            params, _ = sgd_step(
                params, {k: jnp.asarray(windows[k][sel]) for k in UPLOADED},
                anchor, lam)
    return params


@pytest.mark.parametrize("anchored", [False, True])
def test_real_step_trains_bit_equal_to_per_batch_uploads(anchored):
    cfg = SolarLSTMConfig(hidden_size=4, history_steps=6, horizon_steps=3,
                          history_channels=3, forecast_channels=2)
    forecaster = SolarForecaster(cfg)
    p0 = forecaster.init(jax.random.key(0))
    sgd_step, _ = make_solar_fns(forecaster, lr=0.05)
    windows = _windows(11, seed=1)
    anchor = make_anchor(p0, lam=0.3) if anchored else None
    staged, _, _ = make_train_fn(sgd_step, epochs=2, batch_size=4)(
        p0, windows, np.random.default_rng(5), anchor)
    plain = _per_batch_train(
        sgd_step, p0, windows, np.random.default_rng(5),
        p0 if anchored else None, jnp.float32(0.3 if anchored else 0.0),
        epochs=2, batch_size=4)
    for a, b in zip(jax.tree.leaves(staged), jax.tree.leaves(plain),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(staged), jax.tree.leaves(p0)))


@pytest.mark.parametrize("epochs", [1, 3])
def test_each_epoch_is_one_staging_of_three_uploads(epochs):
    windows = _windows(10)
    per_window = sum(windows[k][0].nbytes for k in UPLOADED)
    tel = Telemetry()
    train = make_train_fn(_Recorder(), epochs=epochs, batch_size=4)
    with telemetry_scope(tel):
        train({"w": jnp.zeros(())}, windows, np.random.default_rng(0), None)
    m = tel.metrics.dump()
    assert m["counters"]["h2d_transfers"] == 3 * epochs
    assert m["counters"]["h2d_bytes"] == 10 * epochs * per_window
    assert m["histograms"]["train_stage_host_ns"]["count"] == epochs
    assert m["histograms"]["train_step_host_ns"]["count"] == 3 * epochs
    # profiler and histograms only, like ``train.step``: not the rings
    assert tel.dump()["events"] == []


def test_a_step_that_slices_its_batch_runs_on_staged_batches():
    """A wrapper that halves each batch value, as the benchmark's
    ``half_batch`` fault does, gets device arrays it can slice."""
    rec = _Recorder()

    def halved(params, batch, anchor, lam):
        return rec(params, {k: v[:max(1, v.shape[0] // 2)]
                            for k, v in batch.items()}, anchor, lam)

    windows = _windows(10)
    make_train_fn(halved, epochs=1, batch_size=4)(
        {"w": jnp.zeros(())}, windows, np.random.default_rng(2), None)
    sels = batch_order(10, 4, np.random.default_rng(2))
    assert [len(b["target"]) for b in rec.batches] == [2, 2, 1]
    for got, sel in zip(rec.batches, sels, strict=True):
        np.testing.assert_array_equal(
            got["history"], windows["history"][sel[:max(1, len(sel) // 2)]])
