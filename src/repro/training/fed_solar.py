"""FedCCL on the solar case study — the paper's §III/§IV experiment.

Builds a synthetic central-European fleet, clusters it by location and
panel orientation, runs the asynchronous FedCCL protocol, trains the two
centralized baselines, and produces a Table-II-shaped report:

  columns: CentralizedAll / CentralizedContinual / FederatedGlobal /
           FederatedLocation / FederatedOrientation / FederatedLocal
  rows:    mean/max power error, mean energy error, daytime variants

plus the §IV.E population-independent evaluation on held-out sites.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.solar_lstm import SolarLSTMConfig
from repro.core.fedccl import ClusterSpaceConfig, FedCCL, FedCCLConfig
from repro.core.protocol import ClientSpec
from repro.data.solar import generate_fleet
from repro.data.windows import batch_order, make_windows, split_windows
from repro.models.lstm import SolarForecaster
from repro.obs.record import current_telemetry, maybe_span
from repro.training.losses import solar_loss
from repro.training.metrics import summarize_errors


# ---------------------------------------------------------------------------
# jitted train / predict for the forecaster
# ---------------------------------------------------------------------------


def make_solar_fns(forecaster: SolarForecaster, lr: float = 5e-3,
                   ewc_from_anchor: bool = True):
    @jax.jit
    def sgd_step(params, batch, anchor_params, lam):
        def loss_fn(p):
            loss, _ = solar_loss(forecaster, p, batch)
            if anchor_params is not None:
                reg = sum(jnp.sum(jnp.square(a.astype(jnp.float32)
                                             - b.astype(jnp.float32)))
                          for a, b in zip(jax.tree.leaves(p),
                                          jax.tree.leaves(anchor_params),
                                          strict=True))
                loss = loss + 0.5 * lam * reg
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return new, loss

    @jax.jit
    def predict(params, history, forecast):
        return forecaster.forward(params, history, forecast)

    return sgd_step, predict


#: the arrays of a batch a training step uploads
UPLOADED = ("history", "forecast", "target")


@functools.partial(jax.jit, static_argnames=("sizes", "shapes"))
def cut_batches(staged: dict, sizes: tuple, shapes: tuple) -> tuple:
    """An epoch's arrays, already on the device in the epoch's order, cut
    into its batches of the given ``sizes``: one dict per batch.  Each
    array arrives flat and gets back its windows' shape, ``dict(shapes)
    [k]`` after the window axis, here on the device."""
    n, shapes = sum(sizes), dict(shapes)
    staged = {k: v.reshape((n,) + shapes[k]) for k, v in staged.items()}
    starts = itertools.accumulate(sizes, initial=0)
    return tuple({k: v[s:s + size] for k, v in staged.items()}
                 for s, size in zip(starts, sizes))


def make_train_fn(sgd_step, *, epochs: int = 3, batch_size: int = 8):
    """Adapts the jitted sgd into the FedCCL protocol's train_fn.

    Each epoch uploads its windows once: the ``UPLOADED`` arrays are
    gathered on the host in the order ``batch_order`` draws, uploaded whole
    and flat (the device, not the host, lays the windows out in its tiles)
    and cut into the epoch's batches on the device (``cut_batches``), so a
    step dispatches ``sgd_step`` alone.  Every batch holds the windows that
    ``windows[k][sel]`` holds.

    Where a runtime put telemetry in scope (``repro.obs.record.
    current_telemetry``), each epoch's staging is a ``train.stage`` span
    and each step's dispatch a ``train.step`` span, both for the profiler
    and a histogram (``train_stage_host_ns``, ``train_step_host_ns``), with
    no wait on the device.  The ``windows_trained``, ``h2d_bytes`` and
    ``h2d_transfers`` counters count the windows, the bytes uploaded and
    the uploads."""

    def train_fn(params, dataset, rng: np.random.Generator, anchor):
        windows = dataset
        n = len(windows["target"])
        anchor_params = anchor.anchor if anchor is not None else None
        lam = jnp.float32(anchor.lam if anchor is not None else 0.0)
        tel = current_telemetry()
        shapes = tuple((k, windows[k].shape[1:]) for k in UPLOADED)
        for _ in range(epochs):
            with maybe_span(tel, "train.stage", ring=False,
                            hist="train_stage_host_ns"):
                sels = batch_order(n, batch_size, rng)
                order = np.concatenate(sels)
                host = {k: np.take(windows[k], order, axis=0).ravel()
                        for k in UPLOADED}
                if tel is not None:
                    tel.metrics.counter("h2d_bytes").inc(
                        sum(v.nbytes for v in host.values()))
                    tel.metrics.counter("h2d_transfers").inc(len(host))
                batches = cut_batches(
                    {k: jnp.asarray(v) for k, v in host.items()},
                    tuple(len(sel) for sel in sels), shapes)
            for batch in batches:
                with maybe_span(tel, "train.step", ring=False,
                                hist="train_step_host_ns"):
                    params, _ = sgd_step(params, batch, anchor_params, lam)
        if tel is not None:
            tel.metrics.counter("windows_trained").inc(n * epochs)
        return params, n * epochs, epochs

    return train_fn


# ---------------------------------------------------------------------------
# experiment driver
# ---------------------------------------------------------------------------

#: the fleet's two clustering spaces: site location and panel orientation
SOLAR_SPACES = (
    ClusterSpaceConfig("loc", eps=120.0, min_samples=2, metric="haversine"),
    ClusterSpaceConfig("ori", eps=30.0, min_samples=2, metric="cyclic"),
)


def run_fedccl_solar(n_sites: int = 9, n_days: int = 60, rounds: int = 3,
                     seed: int = 0, hidden: int = 64, epochs: int = 3,
                     n_independent: int = 2, ewc_lambda: float = 0.05,
                     lr: float = 1e-2, eval_sites: str = "all",
                     dp_clip: float = None, dp_noise_multiplier: float = 1.0,
                     secure_agg: bool = False,
                     target_delta: float = 1e-5,
                     use_pallas_agg: bool = False) -> dict:
    """One experimental run.  Returns the Table-II-shaped report dict,
    with the federation's folded ``models`` (``"global"`` and one entry per
    cluster key) beside it.

    With ``dp_clip`` / ``secure_agg`` set, client updates are privatized
    (clip + Gaussian noise) and/or aggregated under pairwise masking; the
    report then carries a ``privacy`` section with (epsilon, delta) budgets.
    ``use_pallas_agg`` routes the fold and the DP privatization through
    the Pallas kernels.
    """
    rng = np.random.default_rng(seed)
    fleet = generate_fleet(n_sites=n_sites + n_independent, n_days=n_days,
                           seed=seed)
    train_fleet, indep_fleet = fleet[:n_sites], fleet[n_sites:]

    cfg = SolarLSTMConfig(hidden_size=hidden)
    forecaster = SolarForecaster(cfg)
    init_params = forecaster.init(jax.random.key(seed))
    sgd_step, predict = make_solar_fns(forecaster, lr=lr)
    train_fn = make_train_fn(sgd_step, epochs=epochs)

    # ---- per-site windows + split
    site_splits = {}
    for site, data in fleet:
        tr, te = split_windows(make_windows(data), train_frac=0.8)
        site_splits[site.site_id] = (site, tr, te)

    # ---- FedCCL federation over the training population
    fed_cfg = FedCCLConfig(
        spaces=SOLAR_SPACES, ewc_lambda=ewc_lambda, seed=seed,
        dp_clip=dp_clip, dp_noise_multiplier=dp_noise_multiplier,
        secure_agg=secure_agg, target_delta=target_delta,
        use_pallas_agg=use_pallas_agg)
    fed = FedCCL(fed_cfg, init_params, train_fn)
    specs = [ClientSpec(site.site_id, site.static_features,
                        site_splits[site.site_id][1],
                        speed=float(rng.uniform(0.5, 2.0)))
             for site, _ in train_fleet]
    assignments = fed.setup(specs)
    stats = fed.run(rounds=rounds)

    # ---- centralized baselines -------------------------------------------
    def concat(ws):
        return {k: np.concatenate([w[k] for w in ws]) for k in ws[0]}

    all_train = concat([site_splits[s.site_id][1] for s, _ in train_fleet])
    cen_all = init_params
    crng = np.random.default_rng(seed + 1)
    for _ in range(rounds):
        cen_all, _, _ = train_fn(cen_all, all_train, crng, None)

    cen_cont = init_params
    crng2 = np.random.default_rng(seed + 2)
    for _ in range(rounds):
        for s, _ in train_fleet:                     # sites arrive progressively
            cen_cont, _, _ = train_fn(cen_cont, site_splits[s.site_id][1],
                                      crng2, None)

    # ---- evaluation --------------------------------------------------------
    def eval_model(params, sites):
        per_site = []
        for site, _ in sites:
            _, _, te = site_splits[site.site_id]
            preds = np.asarray(predict(params, jnp.asarray(te["history"]),
                                       jnp.asarray(te["forecast"])))
            per_site.append(summarize_errors(preds, te["target"], te["minute"]))
        keys = per_site[0].keys()
        return {k: float(np.mean([p[k] for p in per_site])) for k in keys}

    def cluster_model_for(client_id, namespace):
        keys = [k for k in assignments[client_id] if k.startswith(namespace)]
        return fed.store.params("cluster", keys[0]) if keys else \
            fed.store.params("global")

    def eval_fed_cluster(namespace, sites):
        per_site = []
        for site, _ in sites:
            params = cluster_model_for(site.site_id, namespace) \
                if site.site_id in assignments else fed.store.params("global")
            _, _, te = site_splits[site.site_id]
            preds = np.asarray(predict(params, jnp.asarray(te["history"]),
                                       jnp.asarray(te["forecast"])))
            per_site.append(summarize_errors(preds, te["target"], te["minute"]))
        keys = per_site[0].keys()
        return {k: float(np.mean([p[k] for p in per_site])) for k in keys}

    def eval_fed_local(sites):
        per_site = []
        for site, _ in sites:
            client = next(c for c in fed.clients
                          if c.spec.client_id == site.site_id)
            _, _, te = site_splits[site.site_id]
            preds = np.asarray(predict(client.local_params,
                                       jnp.asarray(te["history"]),
                                       jnp.asarray(te["forecast"])))
            per_site.append(summarize_errors(preds, te["target"], te["minute"]))
        keys = per_site[0].keys()
        return {k: float(np.mean([p[k] for p in per_site])) for k in keys}

    table2 = {
        "CentralizedAll": eval_model(cen_all, train_fleet),
        "CentralizedContinual": eval_model(cen_cont, train_fleet),
        "FederatedGlobal": eval_model(fed.store.params("global"), train_fleet),
        "FederatedLocation": eval_fed_cluster("loc", train_fleet),
        "FederatedOrientation": eval_fed_cluster("ori", train_fleet),
        "FederatedLocal": eval_fed_local(train_fleet),
    }

    # ---- §IV.E population-independent (Predict phase for unseen sites) ----
    indep = {}
    if indep_fleet:
        # Global model on unseen sites
        indep["FederatedGlobal"] = eval_model(fed.store.params("global"),
                                              indep_fleet)
        # Predict & Evolve: assign clusters via incremental DBSCAN
        for namespace, col in (("loc", "FederatedLocation"),
                               ("ori", "FederatedOrientation")):
            per_site = []
            for site, _ in indep_fleet:
                keys, params = fed.pe.join(
                    ClientSpec(site.site_id + f"-join-{namespace}",
                               site.static_features,
                               site_splits[site.site_id][1]))
                keys = [k for k in keys if k.startswith(namespace)]
                params = (fed.store.params("cluster", keys[0]) if keys
                          else fed.store.params("global"))
                _, _, te = site_splits[site.site_id]
                preds = np.asarray(predict(params, jnp.asarray(te["history"]),
                                           jnp.asarray(te["forecast"])))
                per_site.append(summarize_errors(preds, te["target"],
                                                 te["minute"]))
            indep[col] = {k: float(np.mean([p[k] for p in per_site]))
                          for k in per_site[0]}

    # ---- Fig. 4/5 analogs: example day predictions (centroid-nearest site,
    # paper's test-site selection rule) --------------------------------------
    def _centroid_site(sites):
        lats = np.array([s.lat for s, _ in sites])
        lons = np.array([s.lon for s, _ in sites])
        c = np.array([lats.mean(), lons.mean()])
        d = (lats - c[0]) ** 2 + (lons - c[1]) ** 2
        return sites[int(np.argmin(d))][0]

    fig4_site = _centroid_site(train_fleet)
    _, _, te4 = site_splits[fig4_site.site_id]
    loc_params = cluster_model_for(fig4_site.site_id, "loc")
    fig4 = {
        "site": fig4_site.site_id,
        "minute": te4["minute"][0].tolist(),
        "actual": te4["target"][0].tolist(),
        "predicted": np.asarray(
            predict(loc_params, jnp.asarray(te4["history"][:1]),
                    jnp.asarray(te4["forecast"][:1])))[0].tolist(),
    }

    return {
        "table2": table2,
        "independent": indep,
        "clusters": {k: v for k, v in assignments.items()},
        "async_stats": stats,
        "privacy": fed.privacy_report(),
        "fig4_example": fig4,
        "models": {"global": fed.store.params("global"),
                   **{key: fed.store.params("cluster", key)
                      for key in sorted({k for ks in assignments.values()
                                         for k in ks})}},
        "config": {"n_sites": n_sites, "n_days": n_days, "rounds": rounds,
                   "hidden": hidden, "seed": seed,
                   "ewc_lambda": ewc_lambda, "dp_clip": dp_clip,
                   "dp_noise_multiplier": dp_noise_multiplier,
                   "secure_agg": secure_agg,
                   "use_pallas_agg": use_pallas_agg},
    }
