"""Forecast bursts: every registered site asks for its day-ahead forecast
at once, and a few new installations join and ask too.

Set-up registers the fleet with the program's ``FedCCL`` facade (no
training) and gives every served model (the global one and each
cluster's) weights of its own from the seed, through the store's public
submit and drain.  A burst is due when the last one completes (closed
loop at burst level) and all its requests are due at its start (open
within it): each is timed from the burst's start until its forecast is
back on the host.  A registered site is served through
``model_for(site, "auto")`` and the program's ``predict`` at batch 1; a
new installation goes through ``FedCCL.join`` and is then forecast with
the model it was given.  A joined site is not asked again.

Every burst starts from the facade as set-up left it (the store, which
joins only read, is kept): its new sites join the registered fleet, not
the sites earlier bursts added.  So every burst is the same work, however
many bursts a window holds.
"""

from __future__ import annotations

import copy
import functools
import time

import numpy as np

import fleet
import reference
from federation import build_facade, build_forecaster
from harness import seeds_from


def make(ctx):
    return ForecastBurst(ctx)


class ForecastBurst:
    def __init__(self, ctx):
        self.ctx = ctx
        self.traffic = dict(ctx.cell.traffic)
        self.model = ctx.model
        if ctx.small:
            self.traffic.update(ctx.small.get("traffic", {}))
        (self.data_seed, self.program_seed, self.weight_seed,
         self.order_seed) = seeds_from(ctx.seed, 4)

    # ----------------------------------------------------------- set-up
    def setup(self):
        import jax

        from repro.core.aggregation import ModelMeta, UpdateDelta
        from repro.core.protocol import ClientSpec
        from repro.training.fed_solar import make_train_fn

        m, cfg = self.model, self.ctx.cell.config
        with self.ctx.timed("data"):
            specs = self._sites(ClientSpec)
        sgd_step, self.predict = build_forecaster(m, cfg["training"]["lr"])
        weights = jax.jit(functools.partial(_init_all, model=m, n=16))(
            jax.random.key(self.weight_seed))
        self.fed = build_facade(cfg, weights[0], make_train_fn(sgd_step),
                                seed=self.program_seed,
                                telemetry=self.ctx.trace)
        with self.ctx.timed("facade"):
            self.fed.setup(specs)
        keys = ["__global__"] + sorted(self.fed.store.keys())
        if len(keys) > len(weights):
            raise ValueError(f"{len(keys)} served models, weights for "
                             f"{len(weights)}")
        # the served models: each its own weights, installed through the
        # store's public submit (an update one round ahead replaces the
        # model, Algorithm 2's fast path) and drain
        self.served = {}
        for i, key in enumerate(keys):
            level, ck = ("global", None) if key == "__global__" else \
                ("cluster", key)
            self.fed.store.handle_model_update(
                level, ck, weights[i], ModelMeta(1, 1, 1), UpdateDelta(1))
            self.served[key] = weights[i]
        self.fed.store.drain_all()
        self.global_weights = self.served["__global__"]
        # the facade every burst starts from; the store and the weights
        # are shared, not copied
        self.pristine = copy.deepcopy(self.fed, self._shared())
        w0 = self.windows[self.registered[0]]
        jax.block_until_ready(self.predict(
            self.fed.store.params("global"), w0["history"], w0["forecast"]))

    def _shared(self) -> dict:
        """A ``deepcopy`` memo that keeps the store and every array on the
        device as they are."""
        import jax

        memo = {id(self.fed.store): self.fed.store}
        for leaf in jax.tree.leaves(self.served):
            memo[id(leaf)] = leaf
        return memo

    def _sites(self, ClientSpec) -> list:
        """The registered sites, each with its latest window, and the pool
        of new installations, all placed from the traffic's
        ``layout_seed``.  A new site has no history of its own yet, so its
        forecast input is a registered site's latest window."""
        t = self.traffic
        n_reg = t["sites"]
        self.windows = {}
        specs = []
        for site, data in fleet.generate_fleet(
                n_reg, t["days_per_site"], self.data_seed,
                n_regions=t["regions"], start_day=t["start_day"],
                layout_seed=t["layout_seed"]):
            latest = {k: v[-1:] for k, v in fleet.make_windows(data).items()}
            self.windows[site.site_id] = latest
            specs.append(ClientSpec(site.site_id, site.static_features, latest))
        self.registered = [s.client_id for s in specs]
        self.pool = []
        for i, site in enumerate(fleet.fleet_sites(
                t["join_pool"], t["layout_seed"] + 1, t["regions"],
                prefix="new")):
            self.windows[site.site_id] = self.windows[self.registered[i % n_reg]]
            self.pool.append(ClientSpec(site.site_id, site.static_features,
                                        self.windows[site.site_id]))
        self.features = {s.client_id: s.static_features
                         for s in specs + self.pool}
        return specs

    # ----------------------------------------------------------- window
    def window(self, seconds: float) -> dict:
        ctx, t = self.ctx, self.traffic
        rng = np.random.default_rng(self.order_seed)
        n_reg = len(self.registered)
        n_join = int(round(t["join_frac"] * n_reg))
        latencies: list[float] = []
        burst_p95: list[float] = []
        self.served_log: list[tuple] = []
        #: new site -> (burst, place among that burst's joins)
        self.join_order: dict[str, tuple[int, int]] = {}
        self.joined: list[list[str]] = []
        failed = attempted = 0
        next_join = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            if next_join + n_join > len(self.pool):
                raise RuntimeError("join pool exhausted; raise join_pool")
            joins = self.pool[next_join:next_join + n_join]
            next_join += n_join
            order = rng.permutation(n_reg + n_join)
            self.fed = copy.deepcopy(self.pristine, self._shared())
            self.joined.append([])
            burst = len(self.joined) - 1
            first = len(latencies)
            start = time.perf_counter()
            for i in order:
                attempted += 1
                try:
                    if i < n_reg:
                        site = self.registered[i]
                        with ctx.spans.span("model_for"):
                            params, tier = self.fed.model_for(site, "auto")
                        tier = tier.split(":", 1)[1] if ":" in tier else \
                            "__global__"
                    else:
                        spec = joins[i - n_reg]
                        site = spec.client_id
                        with ctx.spans.span("join"):
                            keys, params = self.fed.join(spec)
                        self.join_order[site] = (burst,
                                                 len(self.joined[burst]))
                        self.joined[burst].append(site)
                        tier = keys[0] if keys else "__global__"
                    w = self.windows[site]
                    with ctx.spans.span("predict"):
                        pred = np.asarray(self.predict(
                            params, w["history"], w["forecast"]))
                    if ctx.fault == "altered":
                        pred = pred + 1e-3
                except Exception:   # a request that raised is a failure
                    failed += 1
                    continue
                latencies.append(time.perf_counter() - start)
                ctx.count("forecasts")
                self.served_log.append((site, tier, pred))
            if len(latencies) > first:
                burst_p95.append(round(float(np.percentile(
                    latencies[first:], 95)) * 1e3, 1))
        lat = np.asarray(latencies) * 1e3
        return {
            "attempted": attempted, "failed": failed,
            "metrics": {"forecast_p95_ms": (float(np.percentile(lat, 95))
                                            if len(lat) else None)},
            "diagnostics": {"bursts": len(self.joined), "joined": next_join,
                            "forecast_p50_ms": (float(np.percentile(lat, 50))
                                                if len(lat) else None),
                            "burst_p95_ms": burst_p95},
        }

    def telemetry(self) -> dict:
        return self.fed.store.telemetry_dump()

    def release(self):
        self.fed = self.pristine = None

    # ------------------------------------------------------------ check
    def check(self, precisions) -> dict:
        """A seeded sample of the forecasts served, each against the
        reference forward pass on the weights the bench gave the tier it
        was served from; and every served tier against the reference
        clustering of the sites."""
        t = self.traffic
        log = self.served_log
        rng = np.random.default_rng(self.order_seed + 1)
        take = rng.choice(len(log), size=min(t["check_forecasts"], len(log)),
                          replace=False)
        out = {"program": {}}
        gaps = {p: [] for p in ("program", *precisions[1:])}
        for i in sorted(take):
            site, tier, pred = log[i]
            w = self.windows[site]
            params = self.served.get(tier, self.global_weights)
            ref = reference.predict(params, w["history"], w["forecast"])
            gaps["program"].append(float(np.max(np.abs(pred - ref))))
            for p in precisions[1:]:
                c = reference.predict(params, w["history"], w["forecast"], p)
                gaps[p].append(float(np.max(np.abs(c - ref))))
        for p, v in gaps.items():
            out.setdefault(p, {})["forecast_gap"] = max(v) if v else None
        joined = [i for i, (site, _, _) in enumerate(log)
                  if site in self.join_order]
        extra = rng.choice(joined, size=min(t["check_joins"], len(joined)),
                           replace=False) if joined else []
        out["program"]["tier_mismatches"] = self._tier_mismatches(
            log, sorted(set(take) | set(extra)))
        return out

    def _tier_mismatches(self, log, take) -> int:
        """Counts the forecasts served from the wrong tier.  A site is due
        the model of the first clustering space in which it is not noise,
        else the global one, as the reference DBSCAN clusters the sites
        when it arrived: a registered site among the registered sites, a
        new one among those and the new sites of its burst that joined up
        to it.  A
        registered site's cluster keys must partition the registered
        sites as the reference does (checked over every one served); a
        sampled new site's key must be one that a site of its reference
        cluster was served."""
        spaces = self.ctx.cell.config["clustering"]["spaces"]
        tier = {site: t for site, t, _ in log}

        def labels(sites):
            return {sp["name"]: dict(zip(sites, reference.dbscan(
                np.stack([self.features[s][sp["name"]] for s in sites]),
                sp["eps"], sp["min_samples"], sp["metric"]), strict=True))
                for sp in spaces}

        def due(lab, site):
            return next(((sp["name"], lab[sp["name"]][site]) for sp in spaces
                         if lab[sp["name"]][site] >= 0), None)

        reg = self.registered
        lab = labels(reg)
        bad, pairs = 0, set()
        for site in reg:
            if site not in tier:
                continue
            want, got = due(lab, site), tier[site]
            if want is None or got == "__global__":
                bad += (want is None) != (got == "__global__")
            elif got.split(":", 1)[0] != want[0]:
                bad += 1
            else:
                pairs.add((got, want))
        # a bijection between program keys and reference clusters
        bad += len(pairs) - len({p[0] for p in pairs})
        bad += len(pairs) - len({p[1] for p in pairs})
        for i in take:
            site, got, _ = log[i]
            if site not in self.join_order:
                continue
            b, k = self.join_order[site]
            sites = reg + self.joined[b][:k + 1]
            lab = labels(sites)
            want = due(lab, site)
            if want is None or got == "__global__":
                bad += (want is None) != (got == "__global__")
                continue
            if got.split(":", 1)[0] != want[0]:
                bad += 1
                continue
            known = {tier[s] for s in sites[:-1]
                     if lab[want[0]][s] == want[1] and s in tier
                     and tier[s].startswith(want[0] + ":")}
            # a cluster this site founded has no key to match yet
            bad += bool(known) and got not in known
        return bad


def _init_all(key, *, model, n):
    """``n`` sets of served weights from one key, in one program: a base
    set and, for each further model, the base moved by a seeded
    perturbation of a tenth of its scale."""
    import jax

    k0, k1 = jax.random.split(key)
    base = reference.init_params(k0, model)
    keys = jax.random.split(k1, n - 1)
    out = [base]
    for k in keys:
        step = reference.init_params(k, model)
        out.append(jax.tree.map(lambda a, b: a + 0.1 * b, base, step))
    return out
