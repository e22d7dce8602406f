#!/usr/bin/env python3
"""Smoke run of the FedCCL solar federation on one TPU chip.

    python3 chip_smoke.py

Drives the paper's deployment through its normal entry points at the
published width of ``configs/solar_lstm.py`` (hidden 128, 672 history
steps, 96 horizon steps), with random weights made from a seed:

  kernels     the fold and DP kernels, as the federation calls them, lower
              to compiled TPU kernels (``tpu_custom_call``) and agree with
              their jnp oracles at the forecaster's parameter count;
  federation  ``run_fedccl_solar``: 6 sites, 2 rounds, DP clip and EWC on,
              with ``use_pallas_agg=True``, then the same seeded federation
              on the jnp route.  Global and cluster models must agree within
              RTOL/ATOL and every Table-II error must be finite;
  workers     one threaded round with two shard worker processes started
              beside this process, which holds the chip: no respawns and no
              drain timeouts.

It refuses to run unless JAX's first device is a TPU.  Every line but the
last is diagnostics; the times there are smoke timings (compilation
included), not benchmarks.  The last line is the verdict, one JSON object.
"""

from __future__ import annotations

import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

SEED = 0
N_SITES = 6          # three locations x two orientations: 3 + 2 clusters
N_DAYS = 40
ROUNDS = 2
HIDDEN = 128         # configs/solar_lstm.py
DP_CLIP = 1.0
DP_NOISE = 0.01
EWC_LAMBDA = 0.05
# the kernel and jnp folds sum in different orders, and two rounds of
# training on top of the first fold carry that difference forward
RTOL, ATOL = 1e-4, 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


def phase_kernels() -> dict:
    """Run the fold and DP kernels once at the solar model's size against
    their oracles; report whether each lowered to a compiled kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.dp_clip_noise.ops import privatize_flat
    from repro.kernels.dp_clip_noise.ref import dp_clip_noise_ref
    from repro.kernels.fedavg_agg.ops import aggregate_flat
    from repro.kernels.fedavg_agg.ref import agg_ref

    n_params = 141_953
    k1, k2, k3 = jax.random.split(jax.random.key(SEED), 3)
    stacked = jax.random.normal(k1, (5, n_params), jnp.float32)
    weights = jax.nn.softmax(jax.random.normal(k2, (5,)))
    noise = jax.random.normal(k3, (n_params,), jnp.float32)
    delta = stacked[0] * 0.01
    cases = {
        "fedavg_agg": (jax.jit(aggregate_flat), agg_ref, (stacked, weights)),
        "dp_clip_noise": (
            jax.jit(lambda d, z: privatize_flat(d, z, DP_CLIP, DP_NOISE)),
            lambda d, z: dp_clip_noise_ref(d, z, DP_CLIP, DP_NOISE),
            (delta, noise)),
    }
    out = {}
    for name, (fn, ref, args) in cases.items():
        compiled = "tpu_custom_call" in fn.lower(*args).as_text()
        got, want = np.asarray(fn(*args)), np.asarray(ref(*args))
        err = float(np.max(np.abs(got - want)))
        if not np.allclose(got, want, rtol=1e-5, atol=1e-6):
            raise AssertionError(f"{name}: kernel and oracle differ by {err}")
        log(f"kernels: {name} compiled={compiled} max|kernel-oracle|={err}")
        out[name] = compiled
    return out


def _flat(params):
    import numpy as np

    from repro.utils.tree import flatten_params

    return np.asarray(flatten_params(params))


def phase_federation() -> dict:
    """The seeded solar federation on the Pallas route and on the jnp
    route; returns the largest model difference between the two."""
    import numpy as np

    from repro.training.fed_solar import run_fedccl_solar

    kw = dict(n_sites=N_SITES, n_days=N_DAYS, rounds=ROUNDS, seed=SEED,
              hidden=HIDDEN, ewc_lambda=EWC_LAMBDA, dp_clip=DP_CLIP,
              dp_noise_multiplier=DP_NOISE)
    reports = {}
    for use_pallas in (True, False):
        t0 = time.perf_counter()
        reports[use_pallas] = run_fedccl_solar(use_pallas_agg=use_pallas, **kw)
        log(f"federation: use_pallas_agg={use_pallas} "
            f"{time.perf_counter() - t0:.3f} s (smoke timing, not a "
            f"benchmark) stats={reports[use_pallas]['async_stats']}")
    kern, ref = reports[True], reports[False]
    if sorted(kern["models"]) != sorted(ref["models"]):
        raise AssertionError(f"model keys differ: {sorted(kern['models'])} "
                             f"vs {sorted(ref['models'])}")
    clusters = [k for k in kern["models"] if k != "global"]
    if not any(k.startswith("loc") for k in clusters) or \
            not any(k.startswith("ori") for k in clusters):
        raise AssertionError(f"expected loc and ori clusters, got {clusters}")
    if kern["async_stats"]["fast_path_frac"] >= 1.0:
        raise AssertionError("every fold took the sequential fast path: the "
                             "fold kernel never ran")
    if not sum(c["steps"] for c in kern["privacy"]["per_client"].values()):
        raise AssertionError("no DP release was recorded")
    worst = 0.0
    for key in sorted(kern["models"]):
        a, b = _flat(kern["models"][key]), _flat(ref["models"][key])
        diff = float(np.max(np.abs(a - b)))
        worst = max(worst, diff)
        log(f"federation: model {key} max|pallas-jnp|={diff}")
        if not np.allclose(a, b, rtol=RTOL, atol=ATOL):
            raise AssertionError(f"{key}: Pallas and jnp routes differ by "
                                 f"{diff} (rtol={RTOL}, atol={ATOL})")
    for name, rep in reports.items():
        for section in ("table2", "independent"):
            for col, row in rep[section].items():
                bad = {m: v for m, v in row.items() if not math.isfinite(v)}
                if bad:
                    raise AssertionError(f"use_pallas_agg={name} {section} "
                                         f"{col}: non-finite {bad}")
    t2 = kern["table2"]
    log("federation: table2 mean_error_power "
        + " ".join(f"{col}={row['mean_error_power']:.5f}"
                   for col, row in t2.items()))
    return {"models": len(kern["models"]), "max_route_diff": worst}


def phase_workers() -> dict:
    """One threaded round on two shard worker processes."""
    import jax
    import numpy as np

    from repro.configs.solar_lstm import SolarLSTMConfig
    from repro.core.fedccl import FedCCL, FedCCLConfig
    from repro.core.protocol import ClientSpec
    from repro.data.solar import generate_fleet
    from repro.data.windows import make_windows, split_windows
    from repro.models.lstm import SolarForecaster
    from repro.training.fed_solar import (
        SOLAR_SPACES,
        make_solar_fns,
        make_train_fn,
    )

    forecaster = SolarForecaster(SolarLSTMConfig(hidden_size=HIDDEN))
    sgd_step, _ = make_solar_fns(forecaster)
    cfg = FedCCLConfig(spaces=SOLAR_SPACES, seed=SEED, runtime="threaded",
                       server_processes=2, batch_aggregation=True,
                       ewc_lambda=EWC_LAMBDA, drain_timeout_s=120.0)
    fed = FedCCL(cfg, forecaster.init(jax.random.key(SEED)),
                 make_train_fn(sgd_step, epochs=1))
    try:
        specs = [ClientSpec(site.site_id, site.static_features,
                            split_windows(make_windows(data))[0])
                 for site, data in generate_fleet(N_SITES, N_DAYS, SEED)]
        fed.setup(specs)
        t0 = time.perf_counter()
        stats = fed.run(rounds=1)
        log(f"workers: 1 threaded round {time.perf_counter() - t0:.3f} s "
            f"(smoke timing, not a benchmark) stats={stats}")
        glob = _flat(fed.store.params("global"))
    finally:
        fed.shutdown()
    if stats["processes"] != 2 or stats["respawns"] or stats["drain_timeouts"]:
        raise AssertionError(f"worker processes misbehaved: {stats}")
    if stats["updates"] < N_SITES or not np.all(np.isfinite(glob)):
        raise AssertionError(f"round incomplete: {stats}")
    return {"updates": stats["updates"]}


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    from repro.launch.device import use_compile_cache

    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {use_compile_cache()}")
    compiled = phase_kernels()
    if not all(compiled.values()):
        raise AssertionError(f"a kernel ran interpreted on the TPU: {compiled}")
    phase_federation()
    phase_workers()
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"device: peak_bytes_in_use={peak}")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                              "kind": dev.device_kind,
                                              "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
