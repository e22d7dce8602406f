"""CPU rehearsals of each driver at a tiny size, through the harness's
whole run but its look for a chip: the sound program reads correct, its
control (the reference in bfloat16 in the program's place) and every
planted fault read not correct."""

import pathlib

import pytest

import harness

SMALL = {
    "fleet-async": {"model": {"hidden_size": 8},
                    "traffic": {"sites": 6, "days_per_site": 20,
                                "check_train_rate": 0.5,
                                "check_fold_rate": 0.5}},
    "secure-dp-rounds": {"model": {"hidden_size": 8},
                         "traffic": {"sites": 6, "days_per_site": 20,
                                     "check_train_rate": 0.5,
                                     "check_secure_rate": 1.0}},
    "forecast-burst": {"model": {"hidden_size": 8},
                       "traffic": {"sites": 12, "join_pool": 300,
                                   "join_frac": 0.25,
                                   "check_forecasts": 10}},
}
FAULTS = {
    "fleet-async": ("unchanged", "half_batch", "altered"),
    "secure-dp-rounds": harness.FAULTS,
    "forecast-burst": ("altered",),
}


def _run(name, **kw):
    return harness.run_cell(harness.Cell.find(name), 2**33 + 5, 0.3, False,
                            require_tpu=False, small=SMALL[name],
                            log=lambda m: None, **kw)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_driver_rehearsal(name):
    out = _run(name, control=True)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e = {m["name"] for m in harness.Cell.find(name).end_to_end}
    assert set(out["metrics"]) == e2e
    assert out["_diagnostics"]["compiles_in_window"] == 0
    # the control fails at least one of the cell's numbers
    assert any(v is not None and v > out["checks"][k]["limit"]
               for k, v in out["control"].items() if k in out["checks"])


@pytest.mark.parametrize("name,fault", [(n, f) for n in sorted(FAULTS)
                                        for f in FAULTS[n]])
def test_planted_fault_reads_not_correct(name, fault):
    out = _run(name, fault=fault)
    assert out["correct"] is False, (fault, out["checks"])


def test_no_result_without_a_chip(tmp_path):
    """The command exits non-zero and prints nothing on standard output
    where JAX finds no TPU."""
    import os
    import subprocess
    import sys

    run = pathlib.Path(harness.BENCH) / "run.py"
    proc = subprocess.run(
        [sys.executable, str(run), "--workload", "fleet-async", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_facade_refuses_a_runtime_it_cannot_time():
    import federation

    cfg = harness.Cell.find("fleet-async").config
    threaded = {**cfg, "store": {**cfg["store"], "runtime": "threaded"}}
    with pytest.raises(ValueError, match="runtime"):
        federation.build_facade(threaded, {"w": 0.0}, None, seed=0,
                                telemetry=False)


def test_every_burst_starts_from_the_set_up_fleet():
    """Joins do not pile up from burst to burst: each burst's new sites
    join the registered fleet alone, so every burst is the same work."""
    cell = harness.Cell.find("forecast-burst")
    small = SMALL["forecast-burst"]
    ctx = harness.Context(cell=cell, seed=7, trace=False, small=small)
    harness.prepare_jax(False, 1)
    drv = harness.load_module(
        cell.bench / "drivers" / "forecast_burst.py").make(ctx)
    drv.setup()
    ctx.counters["_on"] = True
    out = drv.window(1.0)
    sites = small["traffic"]["sites"]
    n_join = round(small["traffic"]["join_frac"] * sites)
    assert out["diagnostics"]["bursts"] >= 2
    assert len(drv.pristine.clients) == sites
    assert len(drv.fed.clients) == sites + n_join
    drv.release()
    assert drv.check(("highest",))["program"]["tier_mismatches"] == 0
