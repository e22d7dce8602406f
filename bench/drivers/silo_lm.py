"""Cross-silo fine-tuning of the MLA+MoE share through FedCCL.

Set-up makes each silo's corpus (``corpus.py``) and the seeded weights
(``lm_reference.init_params``, in the program's layout), builds the
program's model from the configuration's ``model`` group and its trainer
(``repro.training.fed_lm``), and the facade with
``federation.build_facade``.  The window runs whole asynchronous rounds
(``FedCCL.run(rounds=1)``) until ``--seconds`` have passed; the store folds
each shared-tier update on arrival.  ``federation.StoreTap`` (with its
``ReadyStamps``) stamps every update from its submit to the moment its
folded model is ready on the device.

The check, once the program's state is freed, compares a seeded sample of
what the window produced with the plain reference: each sampled training's
first-step loss (every step's as a reading) and its parameter change (over
a seeded set of elements of every leaf), and a sample of folds against
Algorithm 2.  Read beside them, with no limit: the logits of the first
sampled training's first sequence before it trained, and the held experts'
assignment counts on it.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import time

import numpy as np

import corpus
import lm_reference
import reference
from federation import (
    StoreTap,
    _change_gap,
    _count_fold_bytes,
    _fold_gap,
    _meta,
    build_facade,
)
from harness import seeds_from

#: two location clusters of silos (lat, lon): Seoul and Busan
CENTRES = ((37.5665, 126.9780), (35.1796, 129.0756))


def make(ctx):
    return SiloLM(ctx)


def program_config(model: dict):
    """The program's ``ModelConfig`` of the registered architecture at the
    sizes of the configuration's ``model`` group (the cut, or the CPU
    rehearsals' smaller sizes)."""
    from repro.configs import get_config

    base = get_config(model["arch"])
    share = model["expert_share"]
    return base.replace(
        n_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_attention_heads"],
        head_dim=model["v_head_dim"], d_ff=model["moe_intermediate_size"],
        vocab_size=model["vocab_size"], rope_theta=float(model["rope_theta"]),
        norm_eps=model["rms_norm_eps"], dtype=model["dtype"],
        remat=model["remat"],
        mla=dataclasses.replace(
            base.mla, q_lora_rank=model["q_lora_rank"],
            kv_lora_rank=model["kv_lora_rank"],
            qk_nope_head_dim=model["qk_nope_head_dim"],
            qk_rope_head_dim=model["qk_rope_head_dim"],
            v_head_dim=model["v_head_dim"]),
        moe=dataclasses.replace(
            base.moe, n_routed_experts=share["router_experts"],
            n_held=model["n_routed_experts"], first_held=share["first_held"],
            top_k=model["num_experts_per_tok"],
            n_shared_experts=model["n_shared_experts"],
            moe_d_ff=model["moe_intermediate_size"],
            first_k_dense=model["first_k_dense_replace"],
            dense_d_ff=model["intermediate_size"],
            routed_scaling=model["routed_scaling_factor"]))


def registry_mismatches(cfg: dict) -> list:
    """Keys where the configuration file's published numbers (the cut ones
    from its ``published`` group) differ from the program's registered
    architecture."""
    from repro.configs import get_config
    from repro.configs.base import deepseek_v3_keys

    program = deepseek_v3_keys(get_config(cfg["model"]["arch"]))
    program["n_routed_experts"] = program["expert_share"]["router_experts"]
    published = {**cfg, **cfg["published"]}
    return [k for k, v in program.items()
            if k in published and published[k] != v]


class LMTrainTap:
    """Wraps the program's ``train_fn`` and its jitted step: spans, counts,
    and a seeded sample of shared-tier trainings with what the reference
    needs (the fetched parameters, copied to the host, and the sequences
    the step was given)."""

    def __init__(self, ctx, rng, rate: float, cap: int, subset):
        self.ctx, self.rng, self.rate, self.cap = ctx, rng, rate, cap
        self.subset = subset
        self.fn = None
        self.samples: list[dict] = []
        self._sample = None

    def step(self, program_step):
        ctx = self.ctx

        def step(params, tokens, i, anchor, lam, load, **kw):
            if self._sample is not None and "tokens" not in self._sample:
                self._sample["tokens"] = tokens
            if ctx.fault == "half_batch":
                # the sequence's second half replaced by its first: each
                # step trains on half of its tokens
                half = tokens.shape[1] // 2
                tokens = tokens.at[:, half:2 * half].set(tokens[:, :half])
            with ctx.spans.span("lm_step"):
                new, loss, load = program_step(params, tokens, i, anchor,
                                               lam, load, **kw)
            if self._sample is not None:
                self._sample["losses"].append(loss)
            return new, loss, load
        return step

    def __call__(self, params, dataset, rng, anchor):
        import jax

        ctx = self.ctx
        # a shared-tier training: anchored to the parameters it was given
        keep = (ctx.counters.get("_on") and anchor is not None
                and all(a is b for a, b in zip(jax.tree.leaves(anchor.anchor),
                                               jax.tree.leaves(params)))
                and len(self.samples) < self.cap
                and self.rng.random() < self.rate)
        if keep:
            self._sample = {"params": jax.device_get(params),
                            "lam": float(anchor.lam), "losses": []}
        with ctx.spans.span("train"):
            out = self.fn(params, dataset, rng, anchor)
        if ctx.fault == "unchanged":
            out = (params,) + tuple(out[1:])
        if keep:
            self._sample["out"] = self.subset(out[0])
            self.samples.append(self._sample)
            self._sample = None
        ctx.count("train_calls")
        ctx.count("tokens_trained", out[1])
        return out


class FoldTap(StoreTap):
    """``StoreTap`` for a store that folds each update on arrival: the
    update is visible once the model its submit folded is ready; a seeded
    sample of folds keeps the seeded elements of the base, the update and
    the result."""

    def __init__(self, ctx, store, rng, rate, cap, subset):
        self.subset = subset
        self.fast = 0
        super().__init__(ctx, store, rng, rate, cap)

    def _handle_model_update(self, orig):
        def submit(level, cluster_key, params, meta, delta, **kw):
            on = self.ctx.counters.get("_on")
            base, base_meta = self.store.request_model(level, cluster_key)
            t0 = time.perf_counter()
            ok = orig(level, cluster_key, params, meta, delta, **kw)
            if not on:
                return ok
            self.submitted += 1
            out = self.store.params(level, cluster_key)
            self._visible(out, [(t0,)])
            self.ctx.count("folds")
            if meta.round == base_meta.round + 1:
                self.fast += 1
            elif (len(self.fold_samples) < self.cap
                  and self.rng.random() < self.rate):
                self.fold_samples.append({
                    "base": self.subset(base), "base_meta": _meta(base_meta),
                    "update": (self.subset(params), _meta(meta),
                               int(delta.samples_learned), int(delta.rounds)),
                    "out": self.subset(out)})
            return ok
        return submit


class Subset:
    """A seeded set of elements of every leaf: a run of up to ``per_leaf``
    consecutive elements of its flat view, at a seeded start.  Calling it
    gathers them on the device as one float32 vector (a few hundred kB,
    not the model's 0.85 GB); ``host`` does the same from host arrays."""

    def __init__(self, shapes, dtypes, rng, per_leaf: int):
        import jax

        self.cuts = []
        for shape in shapes:
            n = int(np.prod(shape))
            k = min(n, per_leaf)
            a = int(rng.integers(0, n - k + 1))
            self.cuts.append((a, a + k))
        self.dtypes = [str(np.dtype(d)) for d in dtypes]
        self._take = jax.jit(self._gather)

    def _gather(self, tree):
        import jax
        import jax.numpy as jnp

        return jnp.concatenate([jnp.ravel(x)[a:b].astype(jnp.float32)
                                for x, (a, b) in zip(jax.tree.leaves(tree),
                                                     self.cuts, strict=True)])

    def __call__(self, tree):
        return self._take(tree)

    def host(self, tree) -> np.ndarray:
        import jax

        return np.concatenate([
            np.asarray(x, np.float64).ravel()[a:b]
            for x, (a, b) in zip(jax.tree.leaves(tree), self.cuts, strict=True)])

    def round(self, flat: np.ndarray) -> np.ndarray:
        """Each element rounded to its leaf's storage dtype."""
        out, off = [], 0
        for (a, b), dt in zip(self.cuts, self.dtypes, strict=True):
            out.append(lm_reference.round_to(flat[off:off + b - a], dt))
            off += b - a
        return np.concatenate(out)


class SiloLM:
    """Set-up, window and check of the silo fine-tuning cell."""

    def __init__(self, ctx):
        self.ctx = ctx
        cfg = ctx.cell.config
        self.cfg = cfg
        self.model = ctx.model
        self.training = dict(cfg["training"])
        self.traffic = dict(ctx.cell.traffic)
        if ctx.small:
            self.traffic.update(ctx.small.get("traffic", {}))
        self.data_seed, self.weight_seed, self.tap_seed = seeds_from(ctx.seed, 3)
        self.program_seed = int(self.traffic["schedule_seed"])
        self.fed = None

    # ----------------------------------------------------------- set-up
    def setup(self):
        # the program's trainer first: a program without it fails here, at
        # once, before any weight is made
        from repro.core.protocol import ClientSpec
        from repro.models.model import build_model
        from repro.training.fed_lm import make_lm_step, make_train_fn

        import jax

        ctx, m, tr, t = self.ctx, self.model, self.training, self.traffic
        if not ctx.small:
            bad = registry_mismatches(self.cfg)
            if bad:
                raise ValueError(f"the configuration's published numbers "
                                 f"differ from the program's: {bad}")
        pcfg = program_config(m)
        model = build_model(pcfg)
        self.program_model = model
        rng = np.random.default_rng(self.tap_seed)
        with ctx.timed("weights"):
            init = jax.jit(functools.partial(lm_reference.init_params, cfg=m,
                                             dtype=m["dtype"]))
            # held by set-up alone: the facade lets the initial model go
            # once its clients are seeded, and the window frees it once
            # every tier has moved on
            weights = jax.block_until_ready(
                init(jax.random.key(self.weight_seed)))
        layout = jax.tree.map(lambda a: (a.shape, a.dtype), weights)
        if layout != jax.tree.map(lambda a: (a.shape, a.dtype),
                                  model.param_shapes()):
            raise ValueError("the reference's weights are not in the "
                             "program's layout")
        leaves = jax.tree.leaves(weights)
        self.subset = Subset([x.shape for x in leaves],
                             [x.dtype for x in leaves], rng,
                             t["check_elements_per_leaf"])
        tap = LMTrainTap(ctx, rng, t["check_train_rate"],
                         t["check_train_calls"], self.subset)
        tap.fn = make_train_fn(
            tap.step(make_lm_step(model, lr=tr["lr"])), steps=tr["steps"],
            n_load=pcfg.moe.held_count, first_expert=pcfg.moe.first_held)
        self.train_tap = tap
        with ctx.timed("data"):
            specs = self._specs(ClientSpec)
        with ctx.timed("warm"):
            self._warm(specs, weights, jax)
        with ctx.timed("facade"):
            self.fed = build_facade(self.cfg, weights, tap,
                                    seed=self.program_seed,
                                    telemetry=ctx.trace)
            self.fed.setup(specs)
        self.store_tap = FoldTap(ctx, self.fed.store, rng,
                                 t["check_fold_rate"], t["check_folds"],
                                 self.subset)
        _count_fold_bytes(ctx)

    def _specs(self, ClientSpec):
        t, m = self.traffic, self.model
        lay = np.random.default_rng(t["layout_seed"])
        lo, hi = t["speed"]
        n = t["silos"]
        # U(lo, hi) by its quantiles, in an order fixed by the schedule
        speeds = lo + (hi - lo) * (np.arange(n) + 0.5) / n
        speeds = np.random.default_rng(self.program_seed).permutation(speeds)
        seeds = seeds_from(self.data_seed, n)
        specs = []
        for i in range(n):
            lat, lon = CENTRES[i % t["clusters"]]
            loc = np.array([lat, lon]) + lay.uniform(-0.1, 0.1, 2)
            data = corpus.silo_corpus(seeds[i], m["vocab_size"], m["seq_len"],
                                      t["sequences_per_silo"], t)
            specs.append(ClientSpec(f"silo-{i}", {"loc": loc}, data,
                                    speed=float(speeds[i])))
        return specs

    def _warm(self, specs, weights, jax):
        """Compile every program the window runs: one round of the two
        silos of the first cluster (a local training without an anchor,
        anchored trainings, a fold on arrival), then free it."""
        pair = [specs[0], specs[self.traffic["clusters"]]]
        warm = build_facade(self.cfg, weights, self.train_tap,
                            seed=self.program_seed, telemetry=False)
        warm.setup(pair)
        warm.run(rounds=1)
        jax.block_until_ready([warm.store.params("global")]
                              + [warm.store.params("cluster", k)
                                 for k in warm.store.keys()])
        self.subset(weights)
        del warm
        gc.collect()

    # ----------------------------------------------------------- window
    def window(self, seconds: float) -> dict:
        import jax

        fed, tap, spans = self.fed, self.store_tap, self.ctx.spans
        t0 = time.perf_counter()
        marks = [0.0]
        rounds = 0
        while True:
            fed.run(rounds=1)
            rounds += 1
            marks.append(time.perf_counter() - t0)
            if marks[-1] >= seconds:
                break
        tap.stamps.close()
        jax.block_until_ready([fed.store.params("global")]
                              + [fed.store.params("cluster", k)
                                 for k in fed.store.keys()])
        elapsed = time.perf_counter() - t0
        folded = len(tap.visible_ms)
        return {
            "attempted": tap.submitted,
            "failed": tap.submitted - folded,
            "metrics": {"updates_per_s": folded / elapsed},
            "diagnostics": {
                "rounds": rounds, "updates": folded, "fast_path": tap.fast,
                "round_s": [round(b - a, 3) for a, b in zip(marks, marks[1:])],
                "fold_visible_p95_ms": (
                    float(np.percentile([ms for _, ms in tap.visible_ms], 95))
                    if folded else None),
                "span_max_ms": {k: round(max(v) * 1e3, 1)
                                for k, v in spans.durations.items() if v},
                "train_calls": self.ctx.counters.get("train_calls", 0),
                "tokens_trained": self.ctx.counters.get("tokens_trained", 0),
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
        gc.collect()

    # ------------------------------------------------------------ check
    def check(self, precisions) -> dict:
        out = {"program": {}, **{p: {} for p in precisions[1:]}}
        samples = self.train_tap.samples
        if samples:
            self._check_training(samples, precisions, out)
        folds = self.store_tap.fold_samples
        if folds:
            gaps = {p: [] for p in ("program", *precisions[1:])}
            for s in folds:
                base = np.asarray(s["base"], np.float64)
                upd = [(np.asarray(s["update"][0], np.float64),
                        *s["update"][1:])]
                ref = self.subset.round(reference.fold_algorithm2(
                    base, s["base_meta"], upd))
                gaps["program"].append(
                    _fold_gap(np.asarray(s["out"], np.float64), ref))
                for p in precisions[1:]:
                    c = reference.fold_algorithm2(base, s["base_meta"], upd, p)
                    gaps[p].append(_fold_gap(self.subset.round(c), ref))
            for p, v in gaps.items():
                out[p]["fold_gap"] = _worst(v)
        return out

    def _check_training(self, samples, precisions, out):
        import jax
        import jax.numpy as jnp

        m, tr = self.model, self.training
        ctl = {p: {"change": [], "loss": []} for p in precisions[1:]}
        change, loss_gaps, later = [], [], []
        for s in samples:
            seqs = np.asarray(s["tokens"])
            p_in = self.subset.host(s["params"])
            ref_p, ref_losses = lm_reference.train(
                s["params"], seqs, s["params"], s["lam"], tr["lr"], m)
            ref_sub = self.subset.host(ref_p)
            del ref_p
            change.append(_change_gap([p_in], [np.asarray(s["out"],
                                                          np.float64)],
                                      [ref_sub]))
            steps = [abs(float(a) - b) / abs(b)
                     for a, b in zip(s["losses"], ref_losses, strict=True)]
            # the first step's loss: from the same parameters on both sides,
            # so only the arithmetic differs (later steps start from
            # parameters that rounded apart)
            loss_gaps.append(steps[0])
            later.append(_worst(steps))
            for p in precisions[1:]:
                c_p, c_losses = lm_reference.train(
                    s["params"], seqs, s["params"], s["lam"], tr["lr"], m, p)
                ctl[p]["change"].append(_change_gap(
                    [p_in], [self.subset.host(c_p)], [ref_sub]))
                ctl[p]["loss"].append(abs(c_losses[0] - ref_losses[0])
                                      / abs(ref_losses[0]))
                del c_p
        out["program"].update(train_change_gap=_worst(change),
                              train_loss_gap=_worst(loss_gaps),
                              train_loss_gap_all_steps=_worst(later))
        for p in precisions[1:]:
            out[p].update(train_change_gap=_worst(ctl[p]["change"]),
                          train_loss_gap=_worst(ctl[p]["loss"]))

        # the logits of the first sampled training's first sequence, from
        # the parameters it was fetched with
        first = samples[0]
        tokens = np.asarray(first["tokens"])[0, :-1]
        params = jax.device_put(first["params"])
        forward = jax.jit(lambda p, t: self.program_model.forward(
            p, tokens=t[None]))
        got, aux = forward(params, jnp.asarray(tokens))
        got = got[0].astype(jnp.float32)
        want, routes = lm_reference.logits_and_routes(params, tokens, m)
        out["program"]["logits_gap"] = _rel(got, want)
        held = m["expert_share"]["first_held"] + np.arange(m["n_routed_experts"])
        want_load = np.array([(routes == e).sum() for e in held])
        got_load = np.asarray(aux["moe_load"])
        out["program"]["held_load_gap"] = float(
            np.abs(got_load - want_load).sum() / max(want_load.sum(), 1))
        del got, aux
        for p in precisions[1:]:
            c, _ = lm_reference.logits_and_routes(params, tokens, m, p)
            out[p]["logits_gap"] = _rel(c, want)


def _worst(values) -> float:
    """The largest reading, NaN if any is (a NaN never hides behind a
    number, as it can in ``max``)."""
    return float(np.max(np.asarray(values, np.float64)))


def _rel(got, want) -> float:
    """||got - want|| / ||want||, on the device."""
    import jax.numpy as jnp

    d = jnp.asarray(got, jnp.float32) - jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(d) / jnp.linalg.norm(jnp.asarray(want,
                                                                 jnp.float32)))

