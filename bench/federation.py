"""The training cells' federation: the fleet from the seed, the program's
``FedCCL`` facade as the configuration states it, and the taps that time
and capture what the window drives.

The taps wrap the facade's public entry points from the outside: the
``train_fn`` the facade is given, the store's submit and drain methods,
each client's DP privatizer and the pairwise masker.  They time every
update from its submit to the drain that folds it (ending on ready
arrays), count the work, and keep, for a sample drawn from the seed, the
inputs and outputs that the plain reference checks once the window has
closed.
"""

from __future__ import annotations

import copy
import functools
import queue
import threading
import time
from collections import deque

import numpy as np

import fleet
import reference
from harness import seeds_from

#: the runtimes the drivers can time: the simulated runtime runs every
#: client on the caller's thread, where the taps see each call in order
RUNTIMES = ("sim",)


def build_forecaster(model: dict, lr: float):
    """The program's solar forecaster at the configuration's sizes: its
    jitted ``(sgd_step, predict)``."""
    from repro.configs.solar_lstm import SolarLSTMConfig
    from repro.models.lstm import SolarForecaster
    from repro.training.fed_solar import make_solar_fns

    forecaster = SolarForecaster(SolarLSTMConfig(
        hidden_size=model["hidden_size"], history_steps=model["history_steps"],
        horizon_steps=model["horizon_steps"],
        history_channels=model["history_channels"],
        forecast_channels=model["forecast_channels"], dtype=model["dtype"]))
    return make_solar_fns(forecaster, lr=lr)


def build_facade(cfg: dict, init_params, train_fn, *, seed: int,
                 telemetry: bool, dropout: float | None = None):
    """The program's ``FedCCL`` facade as the configuration states it: its
    clustering spaces, continual-learning weight, runtime, store and
    privacy mode.  ``dropout`` overrides the configured ``dropout_prob``."""
    from repro.core.fedccl import ClusterSpaceConfig, FedCCL, FedCCLConfig

    store, priv = cfg["store"], cfg["privacy"]
    if store["runtime"] not in RUNTIMES:
        raise ValueError(f"store.runtime {store['runtime']!r}: the drivers "
                         f"time the runtimes {RUNTIMES}")
    fed_cfg = FedCCLConfig(
        spaces=tuple(ClusterSpaceConfig(**s)
                     for s in cfg["clustering"]["spaces"]),
        ewc_lambda=cfg["continual"]["ewc_lambda"], runtime=store["runtime"],
        seed=seed,
        dropout_prob=priv["dropout_prob"] if dropout is None else dropout,
        use_pallas_agg=store["use_pallas_agg"],
        batch_aggregation=store["batch_aggregation"],
        max_coalesce=store["max_coalesce"],
        dp_clip=priv["dp_clip"],
        dp_noise_multiplier=priv["dp_noise_multiplier"],
        secure_agg=priv["secure_agg"],
        secure_mask_scale=priv["secure_mask_scale"],
        telemetry=telemetry)
    return FedCCL(fed_cfg, init_params, train_fn)


class TrainTap:
    """Wraps the program's ``train_fn``: spans, a count of windows trained,
    and a seeded sample of calls with their inputs and outputs."""

    def __init__(self, ctx, program_train_fn, rng, rate: float, cap: int):
        self.ctx, self.fn, self.rng = ctx, program_train_fn, rng
        self.rate, self.cap = rate, cap
        self.samples: list[dict] = []
        self._losses = None
        #: whether the calls of the round under way may be sampled
        self.eligible = True

    def sgd(self, program_sgd):
        """Wrap the jitted step the program's ``train_fn`` calls, to keep
        the loss of each step of a sampled call (and to plant faults)."""
        ctx = self.ctx

        def step(params, batch, anchor, lam):
            if ctx.fault == "half_batch":
                half = {k: v[:max(1, v.shape[0] // 2)] for k, v in batch.items()}
                batch = half
            with ctx.spans.span("sgd_step"):
                new, loss = program_sgd(params, batch, anchor, lam)
            if self._losses is not None:
                self._losses.append(loss)
            return new, loss
        return step

    def __call__(self, params, dataset, rng, anchor):
        ctx = self.ctx
        keep = (ctx.counters.get("_on") and self.eligible
                and len(self.samples) < self.cap
                and self.rng.random() < self.rate)
        if keep:
            sample = {"params": params, "windows": dataset,
                      "rng_state": copy.deepcopy(rng.bit_generator.state),
                      "anchor": None if anchor is None else anchor.anchor,
                      "lam": 0.0 if anchor is None else float(anchor.lam)}
            self._losses = []
        with ctx.spans.span("train"):
            out = self.fn(params, dataset, rng, anchor)
        if ctx.fault == "unchanged":
            out = (params,) + tuple(out[1:])
        if keep:
            sample["out"] = out[0]
            sample["losses"] = self._losses
            self._losses = None
            self.samples.append(sample)
        ctx.count("train_calls")
        ctx.count("windows_trained", out[1])
        return out


def _meta(m) -> tuple:
    return (int(m.samples_learned), int(m.round))


class ReadyStamps:
    """Stamps the moment arrays are ready on the device, on a thread of its
    own, so that the program's thread never waits for the device where the
    program itself does not."""

    def __init__(self):
        import jax

        self._ready = jax.block_until_ready
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-ready")
        self._thread.start()

    def put(self, arrays, done) -> None:
        """Call ``done(t)`` with the clock once ``arrays`` are ready."""
        self._q.put((arrays, done))

    def _loop(self):
        while (item := self._q.get()) is not None:
            arrays, done = item
            try:
                self._ready(arrays)
            except RuntimeError:
                # consumed by a later program (a donated buffer): it was
                # ready before that program ran
                pass
            done(time.perf_counter())

    def close(self) -> None:
        """Wait for every stamp asked for, then stop the thread."""
        if self._thread.is_alive():
            self._q.put(None)
            self._thread.join()


class StoreTap:
    """Wraps the store's submits and drains.  Every update is stamped at
    its submit and again when the drain that folds it has returned and its
    folded parameters are ready on the device (``ReadyStamps``); a seeded
    sample of drains keeps its inputs and output for the reference."""

    def __init__(self, ctx, store, rng, rate: float, cap: int):
        self.ctx, self.store, self.rng = ctx, store, rng
        self.rate, self.cap = rate, cap
        self.queues: dict = {}          # model key -> deque of submits
        #: (round, milliseconds from submit to visible) of each update
        self.visible_ms: list[tuple[int, float]] = []
        self.stamps = ReadyStamps()
        self.submitted = 0
        self.fold_samples: list[dict] = []
        self.secure_samples: list[dict] = []
        #: (round id, model key) of secure folds kept for the reference
        self.secure_keep: set = set()
        self.privatized: dict = {}      # (round, key, client) -> (delta, out)
        #: the secure round under way (the store's round id)
        self.current_round = 0
        #: the window's fleet round under way, for the diagnostics
        self.window_round = 0
        for name in ("handle_model_update", "drain", "submit_secure",
                     "drain_secure"):
            setattr(store, name, getattr(self, "_" + name)(
                getattr(store, name)))

    def _stamp(self, key, item):
        if self.ctx.counters.get("_on"):
            self.submitted += 1
        self.queues.setdefault(key, deque()).append(item)

    def _handle_model_update(self, orig):
        def submit(level, cluster_key, params, meta, delta, **kw):
            key = self.store.model_key(level, cluster_key)
            self._stamp(key, (time.perf_counter(), params, _meta(meta),
                              int(delta.samples_learned), int(delta.rounds)))
            return orig(level, cluster_key, params, meta, delta, **kw)
        return submit

    def _visible(self, out, items):
        """Stamp ``items`` visible once ``out`` is ready on the device."""
        if not self.ctx.counters.get("_on"):
            return
        rnd, sent = self.window_round, [it[0] for it in items]

        def done(t):
            self.visible_ms.extend((rnd, (t - t0) * 1e3) for t0 in sent)
        self.stamps.put(out, done)

    def _drain(self, orig):
        def drain(level, cluster_key=None):
            key = self.store.model_key(level, cluster_key)
            base, base_meta = self.store.request_model(level, cluster_key)
            with self.ctx.spans.span("drain"):
                n = orig(level, cluster_key)
            if not n:
                return n
            out = self.store.params(level, cluster_key)
            q = self.queues[key]
            items = [q.popleft() for _ in range(n)]
            self._visible(out, items)
            if self.ctx.counters.get("_on"):
                self.ctx.count("folds")
                if (len(self.fold_samples) < self.cap
                        and self.rng.random() < self.rate):
                    self.fold_samples.append(
                        {"base": base, "base_meta": _meta(base_meta),
                         "updates": [it[1:] for it in items], "out": out})
            return n
        return drain

    def _submit_secure(self, orig):
        def submit(level, cluster_key, client_id, round_id, masked, delta):
            key = self.store.model_key(level, cluster_key)
            self._stamp((key, round_id), (time.perf_counter(), client_id,
                                          int(delta.samples_learned)))
            return orig(level, cluster_key, client_id, round_id, masked, delta)
        return submit

    def _drain_secure(self, orig):
        def drain(level, cluster_key, round_id, expected_ids):
            key = self.store.model_key(level, cluster_key)
            base, _ = self.store.request_model(level, cluster_key)
            with self.ctx.spans.span("drain"):
                n = orig(level, cluster_key, round_id, expected_ids)
            if not n:
                return n
            out = self.store.params(level, cluster_key)
            items = list(self.queues.pop((key, round_id)))
            self._visible(out, items)
            if self.ctx.counters.get("_on"):
                self.ctx.count("folds")
                if (round_id, key) in self.secure_keep:
                    self.secure_samples.append(
                        {"base": base, "out": out, "key": key,
                         "members": [(cid, w, self.privatized.pop(
                             (round_id, key, cid))) for _, cid, w in items]})
            for k in [k for k in self.privatized
                      if k[0] == round_id and k[1] == key]:
                del self.privatized[k]
            return n
        return drain


class PrivacyTap:
    """Wraps each client's DP release and the pairwise masker: spans
    ending on ready arrays, and the unmasked releases of the secure folds
    the sample keeps."""

    def __init__(self, ctx, fed, store_tap: StoreTap):
        self.ctx, self.tap = ctx, store_tap
        for client in fed.clients:
            if client.privatizer is not None:
                client.privatizer.privatize_delta = self._privatize(
                    client.spec.client_id, client.privatizer.privatize_delta)
        if fed.masker is not None:
            fed.masker.mask_delta_flat = self._span(
                "mask", fed.masker.mask_delta_flat)
            fed.masker.reconstruct = self._span(
                "reconstruct", fed.masker.reconstruct)

    def _span(self, name, orig):
        def call(*a, **kw):
            with self.ctx.spans.span(name, ready=True) as s:
                return s.done(orig(*a, **kw))
        return call

    def _privatize(self, client_id, orig):
        def call(delta_flat, model_key="__global__"):
            with self.ctx.spans.span("privatize", ready=True) as s:
                out = s.done(orig(delta_flat, model_key))
            if self.ctx.fault == "no_noise":
                out = _clipped(delta_flat, self.ctx.cell.config["privacy"])
            self.ctx.count("dp_releases")
            tap = self.tap
            if (tap.current_round, model_key) in tap.secure_keep:
                tap.privatized[(tap.current_round, model_key, client_id)] = \
                    (delta_flat, out)
            return out
        return call


class Federation:
    """Set-up, window and check shared by the training cells."""

    def __init__(self, ctx):
        self.ctx = ctx
        cfg = ctx.cell.config
        self.traffic = dict(ctx.cell.traffic)
        self.model = ctx.model
        self.training = dict(cfg["training"])
        if ctx.small:
            self.traffic.update(ctx.small.get("traffic", {}))
        elif cfg.get("round_size", self.traffic["sites"]) != self.traffic["sites"]:
            raise ValueError(f"a secure round has {cfg['round_size']} members "
                             f"but the traffic has {self.traffic['sites']} sites")
        # the seed makes the fleet's data and the weights; the sites' places
        # (``layout_seed``) and the schedule (their speeds, the runtime's
        # jitter and dropout draws, DP noise and mask seeds) are the
        # traffic's, the same for every seed
        self.data_seed, self.weight_seed, self.tap_seed = seeds_from(ctx.seed, 3)
        self.program_seed = int(self.traffic["schedule_seed"])
        self.fed = None

    # ----------------------------------------------------------- set-up
    def _specs(self, ClientSpec):
        t = self.traffic
        sites = fleet.generate_fleet(t["sites"], t["days_per_site"],
                                     self.data_seed,
                                     n_regions=t["regions"],
                                     start_day=t["start_day"],
                                     layout_seed=t["layout_seed"])
        lo, hi = t["speed"]
        # U(lo, hi) by its quantiles, in an order fixed by the schedule
        speeds = lo + (hi - lo) * (np.arange(len(sites)) + 0.5) / len(sites)
        speeds = np.random.default_rng(self.program_seed).permutation(speeds)
        specs = []
        for (site, data), speed in zip(sites, speeds, strict=True):
            train, _ = fleet.split_windows(fleet.make_windows(data),
                                           t["train_frac"])
            specs.append(ClientSpec(site.site_id, site.static_features,
                                    train, speed=float(speed)))
        return specs

    def _facade(self, specs, weights, telemetry, dropout=None):
        fed = build_facade(self.ctx.cell.config, weights, self.train_tap,
                           seed=self.program_seed, telemetry=telemetry,
                           dropout=dropout)
        fed.setup(specs)
        return fed

    def setup(self):
        import jax

        from repro.core.protocol import ClientSpec
        from repro.training.fed_solar import make_train_fn

        ctx, m, tr, t = self.ctx, self.model, self.training, self.traffic
        sgd_step, _ = build_forecaster(m, tr["lr"])
        rng = np.random.default_rng(self.tap_seed)
        self.sample_rng = rng
        tap = TrainTap(ctx, None, rng, t["check_train_rate"],
                       t["check_train_calls"])
        tap.fn = make_train_fn(tap.sgd(sgd_step), epochs=tr["epochs"],
                               batch_size=tr["batch_size"])
        self.train_tap = tap
        with ctx.timed("weights"):
            self.weights = jax.block_until_ready(
                jax.jit(functools.partial(reference.init_params, model=m))(
                    jax.random.key(self.weight_seed)))
        with ctx.timed("data"):
            specs = self._specs(ClientSpec)
        with ctx.timed("warm"):
            self._warm(specs, jax)
        with ctx.timed("facade"):
            self.fed = self._facade(specs, self.weights, telemetry=ctx.trace)
        self.store_tap = StoreTap(ctx, self.fed.store, rng,
                                  t.get("check_fold_rate", 0.0),
                                  t.get("check_folds", 0))
        self.priv_tap = PrivacyTap(ctx, self.fed, self.store_tap)
        _count_fold_bytes(ctx)

    def _warm(self, specs, jax):
        """Compile every shape the window will use: one round of a
        two-client federation of the same configuration (both batch sizes,
        anchored and not, DP, masks, the secure fold), then the fold at
        each power-of-two width up to the widest the window can reach."""
        from repro.core.aggregation import AggregationConfig, multi_aggregate

        # two sites of one region: they share a location cluster
        pair = [specs[0], specs[min(self.traffic["regions"], len(specs) - 1)]]
        warm = self._facade(pair, self.weights, telemetry=False, dropout=0.0)
        warm.run(rounds=1)
        jax.block_until_ready([warm.store.params("global")]
                              + [warm.store.params("cluster", k)
                                 for k in warm.store.keys()])
        cfg = self.ctx.cell.config
        n = 2
        while n <= _widest_fold(cfg, len(specs)):
            out = multi_aggregate([self.weights] * n, [1.0] * n,
                                  AggregationConfig(use_pallas=True))
            jax.block_until_ready(out)
            n *= 2

    # ----------------------------------------------------------- window
    def window(self, seconds: float) -> dict:
        import jax

        fed, tap, spans = self.fed, self.store_tap, self.ctx.spans
        secure = fed.masker is not None
        keys = ["__global__"] + sorted(fed.store.keys())
        t0 = time.perf_counter()
        rounds = 0
        marks = [(0.0, {})]
        last = self.traffic.get("check_train_rounds")
        while True:
            self.train_tap.eligible = last is None or rounds < last
            tap.window_round = rounds
            if secure:
                tap.current_round = fed.store.secure_round_offset
                for key in keys:
                    # the first round's global fold, the widest, always
                    if (not tap.secure_keep
                            or (len(tap.secure_keep)
                                < self.traffic["check_secure_folds"]
                                and self.sample_rng.random()
                                < self.traffic["check_secure_rate"])):
                        tap.secure_keep.add((tap.current_round, key))
            fed.run(rounds=1)
            rounds += 1
            marks.append((time.perf_counter() - t0,
                          {k: len(v) for k, v in spans.durations.items()}))
            if time.perf_counter() - t0 >= seconds:
                break
        tap.stamps.close()
        jax.block_until_ready([fed.store.params("global")]
                              + [fed.store.params("cluster", k)
                                 for k in fed.store.keys()])
        elapsed = time.perf_counter() - t0
        vis = np.asarray([ms for _, ms in tap.visible_ms])
        by_round = [[ms for r, ms in tap.visible_ms if r == i]
                    for i in range(rounds)]
        folded = len(vis)
        return {
            "attempted": tap.submitted,
            "failed": tap.submitted - folded,
            "metrics": {
                "updates_per_s": folded / elapsed,
                "fold_visible_p95_ms": (float(np.percentile(vis, 95))
                                        if folded else None),
            },
            "diagnostics": {
                "rounds": rounds, "updates": folded,
                "fold_visible_p50_ms": (float(np.percentile(vis, 50))
                                        if folded else None),
                "round_s": [round(b[0] - a[0], 3)
                            for a, b in zip(marks, marks[1:])],
                "round_p95_ms": [round(float(np.percentile(v, 95)), 1)
                                 for v in by_round if v],
                # seconds in each span kind, round by round: where a slow
                # round spent its extra time
                "round_span_s": [
                    {k: round(sum(v[a[1].get(k, 0):b[1].get(k, 0)]), 3)
                     for k, v in spans.durations.items()}
                    for a, b in zip(marks, marks[1:])],
                "span_max_ms": {k: round(max(v) * 1e3, 1)
                                for k, v in spans.durations.items() if v},
                "train_calls": self.ctx.counters.get("train_calls", 0),
                "folds": self.ctx.counters.get("folds", 0),
                "window_s": elapsed,
            },
        }

    def telemetry(self) -> dict:
        return self.fed.store.telemetry_dump()

    def release(self):
        """Free the program's state; the captured samples stay."""
        self.fed = None
        self.store_tap.stamps.close()
        self.store_tap.store = None
        self.priv_tap = None

    # ------------------------------------------------------------ check
    def check(self, precisions) -> dict:
        tr, t = self.training, self.traffic
        out = {"program": {}}
        for p in precisions[1:]:
            out[p] = {}
        samples = self.train_tap.samples
        if samples:
            gaps, loss_gaps, norm_gaps = [], [], []
            ctl = {p: ([], [], []) for p in precisions[1:]}
            for s in samples:
                ref, ref_losses = reference.train(
                    s["params"], s["windows"], s["rng_state"], s["anchor"],
                    s["lam"], tr["lr"], tr["batch_size"], tr["epochs"])
                p_in = _leaves(s["params"])
                r = _leaves(ref)
                g = _leaves(s["out"])
                gaps.append(_change_gap(p_in, g, r))
                norm_gaps.append(_norm_gap(p_in, g, r))
                loss_gaps.append(abs(float(s["losses"][0]) - ref_losses[0])
                                 / max(abs(ref_losses[0]), 1e-12))
                for p in precisions[1:]:
                    c, c_losses = reference.train(
                        s["params"], s["windows"], s["rng_state"],
                        s["anchor"], s["lam"], tr["lr"], tr["batch_size"],
                        tr["epochs"], precision=p)
                    c = _leaves(c)
                    ctl[p][0].append(_change_gap(p_in, c, r))
                    ctl[p][1].append(abs(c_losses[0] - ref_losses[0])
                                     / max(abs(ref_losses[0]), 1e-12))
                    ctl[p][2].append(_norm_gap(p_in, c, r))
            out["program"].update(train_change_gap=max(gaps),
                                  train_loss_gap=max(loss_gaps),
                                  train_norm_gap=max(norm_gaps))
            for p in precisions[1:]:
                out[p].update(train_change_gap=max(ctl[p][0]),
                              train_loss_gap=max(ctl[p][1]),
                              train_norm_gap=max(ctl[p][2]))
        folds = self.store_tap.fold_samples
        if folds:
            gaps = {p: [] for p in ("program", *precisions[1:])}
            for s in folds:
                base = reference.flat(s["base"])
                ups = [(reference.flat(u[0]), u[1], u[2], u[3]) for u in s["updates"]]
                ref = reference.fold_algorithm2(base, s["base_meta"], ups)
                gaps["program"].append(_fold_gap(reference.flat(s["out"]), ref))
                for p in precisions[1:]:
                    c = reference.fold_algorithm2(base, s["base_meta"], ups, p)
                    gaps[p].append(_fold_gap(c, ref))
            for p, v in gaps.items():
                out[p]["fold_gap"] = max(v)
        secure = self.store_tap.secure_samples
        if secure:
            priv = self.ctx.cell.config["privacy"]
            gaps = {p: [] for p in ("program", *precisions[1:])}
            dp = []
            for s in secure:
                base = reference.flat(s["base"])
                privs = [np.asarray(d[1], np.float64) for _, _, d in s["members"]]
                ws = [w for _, w, _ in s["members"]]
                ref = reference.secure_fold(base, privs, ws)
                gaps["program"].append(_fold_gap(reference.flat(s["out"]), ref))
                for p in precisions[1:]:
                    c = reference.secure_fold(base, privs, ws, p)
                    gaps[p].append(_fold_gap(c, ref))
                for _, _, (delta, release) in \
                        s["members"][:t["check_dp_per_fold"]]:
                    dp.append(reference.dp_noise_z(
                        np.asarray(delta, np.float64),
                        np.asarray(release, np.float64), priv["dp_clip"],
                        priv["dp_noise_multiplier"]))
            for p, v in gaps.items():
                out[p]["secure_fold_gap"] = max(v)
            out["program"]["dp_noise_z"] = max(dp)
        return out


def _count_fold_bytes(ctx):
    """Count the logical bytes of every call of the fold kernel: the
    parameter sets with a non-zero weight (power-of-two padding carries
    zero) read once and the result written once."""
    import repro.kernels.fedavg_agg.ops as agg_ops

    from costs import dtype_bytes, fold_bytes

    orig = getattr(agg_ops.aggregate_flat, "orig", agg_ops.aggregate_flat)
    itemsize = dtype_bytes(ctx.model)

    def counted(stacked, weights, **kw):
        n = sum(1 for w in weights if float(w) != 0.0)
        ctx.count("fold_bytes", fold_bytes(n, stacked.shape[1], itemsize))
        out = orig(stacked, weights, **kw)
        return out * (1 + 1e-3) if ctx.fault == "altered" else out

    counted.orig = orig
    agg_ops.aggregate_flat = counted


def _clipped(delta, priv):
    """A release without its noise: the delta clipped to the bound."""
    import jax.numpy as jnp

    norm = jnp.sqrt(jnp.sum(delta * delta))
    return delta * jnp.minimum(1.0, priv["dp_clip"] / jnp.maximum(norm, 1e-12))


def _widest_fold(cfg: dict, n_sites: int) -> int:
    """The widest fold (parameter sets, padded to a power of two) the
    window can run: a drain of ``max_coalesce`` updates plus the base, or
    a secure round of every site plus the base and the dropout
    correction."""
    width = cfg["store"]["max_coalesce"] + 1
    if cfg["privacy"]["secure_agg"]:
        width = max(width, n_sites + 2)
    return 1 << (width - 1).bit_length()


def _leaves(tree) -> list:
    import jax

    return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


def _change_gap(p_in, got, ref) -> float:
    """How far a trained change is from the reference's: the norm of the
    difference of the two changes over the norm of the reference's, over
    all parameters."""
    num = sum(float(np.sum((g - r) ** 2)) for g, r in zip(got, ref, strict=True))
    den = sum(float(np.sum((r - p) ** 2)) for r, p in zip(ref, p_in, strict=True))
    return float(np.sqrt(num / max(den, 1e-30)))


def _norm_gap(p_in, got, ref) -> float:
    """The worst leaf's gap between the norms of the two changes, over the
    larger of that leaf's reference norm and the median leaf's."""
    g = [float(np.linalg.norm(a - p)) for a, p in zip(got, p_in, strict=True)]
    r = [float(np.linalg.norm(a - p)) for a, p in zip(ref, p_in, strict=True)]
    med = float(np.median(r))
    return max(abs(a - b) / max(b, med, 1e-30) for a, b in zip(g, r, strict=True))


def _fold_gap(got: np.ndarray, ref: np.ndarray) -> float:
    """The norm of the folded model's error over the norm of the model."""
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))
