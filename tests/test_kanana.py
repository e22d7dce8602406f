"""Kanana-2-30B-A3B's layers (MLA without q-LoRA, sigmoid routing with a
correction bias, a dropless layer over a chip's held experts) against the
plain reference ``bench/lm_reference.py`` on seeded random weights, at a
small size on the CPU.

Tolerances: program and reference both compute in float32 here (CPU
matmuls are exact float32), by different routes (de-interleaved against
in-place rotary pairs, a grouped matmul against a dense masked pass, the
online softmax against a whole-row softmax), so they agree to float32
rounding, a few parts in 1e5 relative after three layers; a mistake in any
equation is of order 1.
"""

import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_for_smoke
from repro.configs.base import deepseek_v3_keys
from repro.models import attention
from repro.models.model import build_model
from repro.models.moe import moe_forward, moe_schema
from repro.sharding.logical import init_from_schema

REF_PATH = pathlib.Path(__file__).resolve().parents[1] / "bench" / "lm_reference.py"
#: relative tolerance of float32 against float32 by another route
RTOL = 2e-4
S = 24


def _reference():
    spec = importlib.util.spec_from_file_location("lm_reference", REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()


def small_cfg(**moe):
    cfg = reduced_for_smoke(get_config("kanana2-30b-a3b"))
    cfg = cfg.replace(n_layers=3)
    if moe:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, **moe))
    return cfg


def _setup(cfg, seed=0):
    hf = deepseek_v3_keys(cfg)
    params = ref.init_params(jax.random.key(seed), hf, dtype="float32")
    model = build_model(cfg)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), model.param_shapes())
    toks = jax.random.randint(jax.random.key(seed + 1), (S + 1,), 0,
                              cfg.vocab_size)
    return hf, params, model, toks


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_registry_entry_at_published_widths():
    cfg = get_config("kanana2-30b-a3b")
    hf = deepseek_v3_keys(cfg)
    published = {"hidden_size": 2048, "num_hidden_layers": 48,
                 "first_k_dense_replace": 1, "num_attention_heads": 32,
                 "q_lora_rank": None, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "qk_head_dim": 192, "v_head_dim": 128,
                 "intermediate_size": 6144, "moe_intermediate_size": 768,
                 "n_routed_experts": 128, "num_experts_per_tok": 6,
                 "n_shared_experts": 2, "routed_scaling_factor": 2.448,
                 "vocab_size": 128256, "rope_theta": 1e6,
                 "rope_interleave": True, "rms_norm_eps": 1e-6}
    assert {k: hf[k] for k in published} == published
    small = reduced_for_smoke(cfg)
    assert small.mla.q_lora_rank is None and small.moe.n_held == 0


@pytest.mark.parametrize("share", [{}, {"n_held": 2, "first_held": 2}],
                         ids=["all-experts", "held-share"])
def test_forward_logits_match_reference(share):
    cfg = small_cfg(**share)
    hf, params, model, toks = _setup(cfg)
    got, aux = model.forward(params, tokens=toks[None, :-1])
    want, routes = ref.logits_and_routes(params, toks[:-1], hf)
    assert _rel(got[0], want) < RTOL
    # the assignments counted on the held experts are the reference's
    held = np.arange(cfg.moe.first_held,
                     cfg.moe.first_held + cfg.moe.held_count)
    want_load = [(routes == e).sum() for e in held]
    np.testing.assert_array_equal(np.asarray(aux["moe_load"]), want_load)


def test_loss_gradients_and_sgd_step_match_reference():
    from repro.training.fed_lm import make_lm_step
    from repro.training.losses import softmax_cross_entropy

    cfg = small_cfg(n_held=2, first_held=0)
    hf, params, model, toks = _setup(cfg, seed=3)
    anchor = jax.tree.map(lambda a: a + 0.01, params)
    lam = 0.05

    def prog_loss(p):
        logits, aux = model.forward(p, tokens=toks[None, :-1])
        reg = sum(jnp.sum(jnp.square(a - b)) for a, b in
                  zip(jax.tree.leaves(p), jax.tree.leaves(anchor)))
        return (softmax_cross_entropy(logits, toks[None, 1:])
                + aux["moe_loss"] + 0.5 * lam * reg)

    lp, gp = jax.value_and_grad(prog_loss)(params)
    with jax.default_matmul_precision("highest"):
        lr_, gr = jax.value_and_grad(ref.loss)(params, toks, anchor, lam, hf)
    assert abs(float(lp) - float(lr_)) < RTOL * abs(float(lr_))
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr), strict=True):
        if float(jnp.abs(b).max()) == 0.0:      # the router's bias
            assert float(jnp.abs(a).max()) == 0.0
        else:
            assert _rel(a, b) < 10 * RTOL

    step = make_lm_step(model, lr=0.1)
    load0 = jnp.zeros((cfg.moe.held_count,), jnp.int32)
    new, loss, load = step(params, toks[None], jnp.int32(0), anchor,
                           jnp.float32(lam), load0)
    want, losses = ref.train(params, np.asarray(toks)[None], anchor, lam,
                             0.1, hf)
    assert abs(float(loss) - losses[0]) < RTOL * abs(losses[0])
    p0 = jax.tree.leaves(params)
    num = sum(float(jnp.sum((a - b) ** 2)) for a, b in
              zip(jax.tree.leaves(new), jax.tree.leaves(want)))
    den = sum(float(jnp.sum((b - c) ** 2)) for b, c in
              zip(jax.tree.leaves(want), p0))
    assert np.sqrt(num / den) < 10 * RTOL
    assert int(load.sum()) > 0


def test_dropless_routing_under_an_uneven_draw():
    """A correction bias that sends every token to expert 1: nothing is
    dropped, and the output is still the reference's."""
    cfg = small_cfg()
    hf, params, model, toks = _setup(cfg, seed=5)
    moe = params["segments"]["seg1"]["b0"]["moe"]
    bias = moe["router_bias"].at[:, 1].set(10.0)
    params["segments"]["seg1"]["b0"]["moe"]["router_bias"] = bias
    got, aux = model.forward(params, tokens=toks[None, :-1])
    load = np.asarray(aux["moe_load"])
    n_moe = cfg.n_layers - cfg.moe.first_k_dense
    assert load.sum() == n_moe * S * cfg.moe.top_k
    assert load[1] == n_moe * S
    assert _rel(got[0], ref.logits_and_routes(params, toks[:-1], hf)[0]) < RTOL


def test_expert_shares_sum_to_the_uncut_layer():
    """Two chips holding experts 0-1 and 2-3: their partial outputs, the
    shared expert counted once, add up to the uncut reference layer."""
    cfg = small_cfg()
    full = init_from_schema(moe_schema(cfg), jax.random.key(7))
    full["router_bias"] = 0.05 * jax.random.normal(jax.random.key(8), (4,))
    x = jax.random.normal(jax.random.key(9), (1, S, cfg.d_model))
    parts = []
    for first in (0, 2):
        share = cfg.replace(moe=dataclasses.replace(cfg.moe, n_held=2,
                                                    first_held=first))
        p = {**full, "experts": {k: v[first:first + 2]
                                 for k, v in full["experts"].items()}}
        parts.append(moe_forward(share, p, x)[0])
    shared = ref._swiglu(full["shared"], x[0], None)
    got = parts[0][0] + parts[1][0] - shared
    m = ref.dims(deepseek_v3_keys(cfg))
    with jax.default_matmul_precision("highest"):
        want = ref._moe(full, x[0], m, jax.lax.Precision.HIGHEST)[0]
    assert _rel(got, want) < RTOL


@pytest.mark.parametrize("s,block", [(20, 8), (32, 16), (24, 512)])
def test_causal_flash_attention_and_its_backward(s, block, monkeypatch):
    """The hand-written backward against autodiff of plain attention,
    padded (20 over blocks of 8) and whole (one block)."""
    k1, k2, k3, k4 = jax.random.split(jax.random.key(s), 4)
    q = jax.random.normal(k1, (1, s, 3, 12))
    k = jax.random.normal(k2, (1, s, 3, 12))
    v = jax.random.normal(k3, (1, s, 3, 8))
    w = jax.random.normal(k4, (1, s, 3, 8))
    scale = 12 ** -0.5

    def naive(q, k, v):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        mask = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    monkeypatch.setattr(attention, "FLASH_BLOCK", block)

    def flash(q, k, v):
        return attention.causal_flash_attention(q, k, v, scale)

    with jax.default_matmul_precision("highest"):
        assert _rel(flash(q, k, v), naive(q, k, v)) < RTOL
        gf = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
        gn = jax.grad(lambda *a: jnp.sum(naive(*a) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gn, strict=True):
        assert _rel(a, b) < RTOL


def test_long_sequence_flash_path_matches_reference(monkeypatch):
    """Above ``FLASH_THRESHOLD`` MLA re-expands keys and values and runs
    the blocked causal attention: forward and gradients as the reference."""
    monkeypatch.setattr(attention, "FLASH_THRESHOLD", 8)
    cfg = small_cfg(n_held=2, first_held=1)
    hf, params, model, toks = _setup(cfg, seed=11)
    got = model.forward(params, tokens=toks[None, :-1])[0][0]
    assert _rel(got, ref.logits_and_routes(params, toks[:-1], hf)[0]) < RTOL

    def prog_loss(p):
        from repro.training.losses import softmax_cross_entropy
        return softmax_cross_entropy(
            model.forward(p, tokens=toks[None, :-1])[0], toks[None, 1:])

    gp = jax.grad(prog_loss)(params)
    with jax.default_matmul_precision("highest"):
        gr = jax.grad(ref.loss)(params, toks, params, 0.0, hf)
    wq = ("segments", "seg1", "b0", "attn", "wq")
    a, b = gp, gr
    for key in wq:
        a, b = a[key], b[key]
    assert _rel(a, b) < 10 * RTOL


def test_lm_trainer_through_fedccl():
    """Four silos fine-tune the small model through the facade (simulated
    runtime, folds on arrival, the Pallas fold): every update folds, the
    shared models move, and the telemetry counts tokens, one upload a
    training and the held experts' assignments (read at the dump)."""
    from repro.core.fedccl import ClusterSpaceConfig, FedCCL, FedCCLConfig
    from repro.core.protocol import ClientSpec
    from repro.obs.export import merged_metrics
    from repro.training.fed_lm import make_lm_step, make_train_fn

    cfg = small_cfg(n_held=2, first_held=1)
    hf, params, model, _ = _setup(cfg, seed=13)
    steps, seq = 2, 16
    train_fn = make_train_fn(make_lm_step(model, lr=0.05), steps=steps,
                             n_load=2, first_expert=1)
    rng = np.random.default_rng(0)
    specs = [ClientSpec(f"silo-{i}", {"loc": np.array([37.5 - 2.4 * (i % 2),
                                                       127.0 + 0.01 * i])},
                        rng.integers(0, cfg.vocab_size, (4, seq + 1),
                                     dtype=np.int32))
             for i in range(4)]
    fed = FedCCL(FedCCLConfig(
        spaces=(ClusterSpaceConfig("loc", eps=100.0, min_samples=2,
                                   metric="haversine"),),
        ewc_lambda=0.05, use_pallas_agg=True, telemetry=True), params,
        train_fn)
    fed.setup(specs)
    stats = fed.run(rounds=2)
    assert stats["updates"] == 4 * 2 * 2          # cluster + global, 2 rounds
    assert sorted(fed.store.keys()) == ["loc:0", "loc:1"]
    moved = jax.tree.map(lambda a, b: bool(jnp.any(a != b)),
                         fed.store.params("global"), params)
    assert any(jax.tree.leaves(moved))
    counters = merged_metrics(fed.store.telemetry_dump())["counters"]
    trainings = 4 * 3 * 2                          # local, cluster, global
    assert counters["tokens_trained"] == trainings * steps * seq
    assert counters["h2d_transfers"] == trainings
    held = counters["moe_assignments_held.1"] + counters["moe_assignments_held.2"]
    assert 0 < held <= trainings * steps * seq * cfg.moe.top_k * (
        cfg.n_layers - cfg.moe.first_k_dense)
