"""Privacy subsystem: DP privatization invariants, exact pairwise-mask
cancellation on the secure coalesced drain (with dropout recovery), and RDP
accountant behavior — plus the end-to-end FedCCL wiring in both runtimes."""

import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # bare CI env: seeded-random fallback shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core.aggregation import (
    AggregationConfig,
    ModelMeta,
    UpdateDelta,
    secure_coalesced_aggregate,
)
from repro.core.store import ModelStore
from repro.privacy.accountant import RDPAccountant, rdp_to_epsilon
from repro.privacy.dp import DPConfig, DPPrivatizer
from repro.privacy import secure_agg
from repro.privacy.secure_agg import PairwiseMasker, _pair_masks
from repro.utils.tree import flatten_params, unflatten_params

from test_batched_aggregation import make_fed, tree_of


# ---------------------------------------------------------- DP privatization
def test_privatizer_clips_to_global_norm(rng):
    base = tree_of(rng)
    new = {k: v + jnp.asarray(rng.standard_normal(v.shape) * 5, jnp.float32)
           for k, v in base.items()}
    clip = 0.7
    priv = DPPrivatizer(DPConfig(clip=clip, noise_multiplier=0.0), "c0", seed=1)
    out = priv.privatize(base, new)
    norm = float(jnp.linalg.norm(flatten_params(out) - flatten_params(base)))
    assert norm <= clip + 1e-5
    # small deltas pass through unclipped (factor = 1)
    tiny = {k: v + 1e-4 for k, v in base.items()}
    out2 = priv.privatize(base, tiny)
    np.testing.assert_allclose(np.asarray(flatten_params(out2)),
                               np.asarray(flatten_params(tiny)), atol=1e-6)


def test_privatizer_noise_deterministic_per_seed(rng):
    base, new = tree_of(rng), tree_of(rng)
    cfg = DPConfig(clip=1.0, noise_multiplier=1.0)
    a = DPPrivatizer(cfg, "c0", seed=5).privatize(base, new)
    b = DPPrivatizer(cfg, "c0", seed=5).privatize(base, new)
    c = DPPrivatizer(cfg, "c0", seed=6).privatize(base, new)
    for k in base:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert not np.allclose(np.asarray(a["a"]), np.asarray(c["a"]))


def test_privatizer_pallas_matches_ref(rng):
    base, new = tree_of(rng), tree_of(rng)
    out = []
    for use_pallas in (False, True):
        cfg = DPConfig(clip=0.5, noise_multiplier=1.3, use_pallas=use_pallas)
        out.append(DPPrivatizer(cfg, "c0", seed=9).privatize(base, new))
    for k in base:
        np.testing.assert_allclose(np.asarray(out[0][k]),
                                   np.asarray(out[1][k]), atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(t=st.integers(3, 5000), clip=st.floats(0.05, 3.0))
def test_clipped_delta_norm_bounded_property(t, clip):
    """Privacy invariant: the clipped delta's global norm never exceeds
    ``dp_clip`` (noise_multiplier=0 isolates the clip)."""
    rng = np.random.default_rng(t * 7 + int(clip * 100))
    from repro.kernels.dp_clip_noise.ops import privatize_flat

    d = jnp.asarray(rng.standard_normal(t) * rng.uniform(0.01, 10), jnp.float32)
    out = privatize_flat(d, jnp.zeros_like(d), clip, 0.0)
    assert float(jnp.linalg.norm(out)) <= clip * (1 + 1e-5)


# ------------------------------------------------------- mask cancellation
def _masked_round(rng, masker, ids, round_id=0, model_key="__global__"):
    """One synthetic secure round: per-client deltas, weights, masked
    submissions.  Returns (base, updates_masked, updates_plain)."""
    base = tree_of(rng)
    masked, plain = [], []
    for cid in ids:
        new = tree_of(rng)
        s = int(rng.integers(10, 200))
        d = UpdateDelta(s, 1, 1)
        masked.append((masker.mask_update(base, new, cid, ids, round_id,
                                          model_key, weight=s), d))
        delta = flatten_params(new) - flatten_params(base)
        plain.append((unflatten_params(delta * jnp.float32(s), base), d))
    return base, masked, plain


@pytest.mark.parametrize("n", [2, 3, 7])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_masks_cancel_in_fused_sum(n, use_pallas):
    rng = np.random.default_rng(n + 10 * use_pallas)
    masker = PairwiseMasker(seed=3, mask_scale=2.0)
    ids = [f"c{i}" for i in range(n)]
    base, masked, plain = _masked_round(rng, masker, ids)
    meta = ModelMeta(100, 1, 4)
    cfg = AggregationConfig(use_pallas=use_pallas)
    res_m = secure_coalesced_aggregate(base, meta, masked, cfg)
    res_p = secure_coalesced_aggregate(base, meta, plain, cfg)
    assert res_m.meta == res_p.meta
    for k in base:
        np.testing.assert_allclose(np.asarray(res_m.params[k]),
                                   np.asarray(res_p.params[k]), atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(2, 6), seed=st.integers(0, 10_000))
def test_mask_cancellation_property(n, seed):
    """Privacy invariant: for any N >= 2 full participant set, the summed
    pairwise masks are exactly zero (up to float summation order)."""
    rng = np.random.default_rng(seed)
    masker = PairwiseMasker(seed=seed, mask_scale=1.0)
    ids = sorted(f"c{rng.integers(1_000_000)}" for _ in range(n))
    t = int(rng.integers(3, 2000))
    total = np.zeros(t, np.float32)
    for cid in ids:
        total += masker.mask_flat(cid, ids, round_id=int(seed % 17),
                                  model_key="m", t=t)
    np.testing.assert_allclose(total, 0.0, atol=1e-4)


def test_dropout_reconstruction_cancels_stray_masks(rng):
    """Survivors' stray masks w.r.t. a dropped member equal the
    reconstructed correction exactly."""
    masker = PairwiseMasker(seed=11, mask_scale=1.5)
    ids = ["a", "b", "c", "d"]
    dropped, survivors = ["d"], ["a", "b", "c"]
    t = 257
    total = np.zeros(t, np.float32)
    for cid in survivors:
        total += masker.mask_flat(cid, ids, 4, "k", t)
    template = {"w": jnp.zeros(t, jnp.float32)}
    corr = masker.reconstruct(template, dropped, survivors, 4, "k")
    np.testing.assert_allclose(total, np.asarray(corr["w"]), atol=1e-4)


# the solar forecaster's width, and an odd width that fills no whole tile
SOLAR_T = 141_953


@pytest.mark.parametrize("t", [SOLAR_T, 1_001])
@pytest.mark.parametrize("n", [2, 5, 32])
def test_full_member_set_masks_cancel(n, t):
    """Every member's device-drawn mask, summed over the full set, is zero
    up to float summation order."""
    masker = PairwiseMasker(seed=2_147_483_659, mask_scale=1.0)
    ids = [f"site-{i:03d}" for i in range(n)]
    total = sum(masker.mask_flat(cid, ids, 7, "__global__", t) for cid in ids)
    assert total.shape == (t,)
    # each member's mask has std sqrt(n - 1): a missing pair would leave ~1
    np.testing.assert_allclose(np.asarray(total), 0.0, atol=1e-4 * n)


@pytest.mark.parametrize("n_dropped", [1, 3])
def test_recovery_equals_survivors_stray_masks(n_dropped):
    """The dealer's recovery equals the sum of what the survivors' masks
    leave uncancelled against the dropped members."""
    masker = PairwiseMasker(seed=11, mask_scale=1.5)
    ids = [f"c{i}" for i in range(32)]
    dropped, survivors = ids[5:5 + n_dropped], ids[:5] + ids[5 + n_dropped:]
    stray = sum(masker.mask_flat(cid, ids, 4, "cluster/0", SOLAR_T)
                for cid in survivors)
    template = {"a": jnp.zeros((SOLAR_T - 1, 1)), "b": jnp.zeros(())}
    corr = masker.reconstruct(template, dropped, survivors, 4, "cluster/0")
    assert corr["a"].shape == (SOLAR_T - 1, 1) and corr["b"].shape == ()
    np.testing.assert_allclose(np.asarray(flatten_params(corr)),
                               np.asarray(stray), atol=1e-3)
    assert float(jnp.std(stray)) > 1.0      # the strays are really there


@pytest.mark.parametrize("change", [None, "round_id", "model_key", "seed"])
def test_pair_mask_is_a_function_of_seed_pair_round_and_model(change):
    """Two maskers give bit-identical masks for the same ``(seed, pair,
    round, model key)``; changing the round, the model or the master seed
    gives another mask."""
    args = {"round_id": 3, "model_key": "__global__", "seed": 5}
    a = PairwiseMasker(seed=args["seed"]).mask_flat(
        "x", ["x", "y"], args["round_id"], args["model_key"], 1_001)
    if change is not None:
        args[change] = {"round_id": 4, "model_key": "cluster/1",
                        "seed": 6}[change]
    b = PairwiseMasker(seed=args["seed"]).mask_flat(
        "x", ["x", "y"], args["round_id"], args["model_key"], 1_001)
    if change is None:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:
        assert not np.any(np.asarray(a) == np.asarray(b))


@pytest.mark.parametrize("mask_scale", [1.0, 2.5])
def test_pair_mask_is_a_gaussian_of_std_mask_scale(mask_scale):
    m = PairwiseMasker(seed=8, mask_scale=mask_scale).mask_flat(
        "a", ["a", "b"], 0, "__global__", SOLAR_T)
    assert m.dtype == jnp.float32
    m = np.asarray(m, np.float64)
    # the mean of 141,953 draws has std ~0.0027 mask_scale
    assert abs(m.mean()) < 0.015 * mask_scale
    assert abs(m.std() / mask_scale - 1.0) < 0.01


def test_member_and_dropout_counts_compile_no_program():
    """After one two-member mask of width T, masks of 5 and 32 members and
    a recovery of 3 dropped members reuse its compiled program."""
    masker = PairwiseMasker(seed=1, mask_scale=1.0)
    t = 4_099
    delta = jnp.ones(t, jnp.float32)
    masker.mask_delta_flat(delta, "c0", ["c0", "c1"], 0, "k", weight=2.0)
    compiled = _pair_masks._cache_size()
    ids = [f"c{i}" for i in range(32)]
    masker.mask_delta_flat(delta, "c0", ids[:5], 1, "k", weight=2.0)
    masker.mask_delta_flat(delta, "c3", ids, 1, "k", weight=2.0)
    masker.reconstruct({"w": delta}, ids[:3], ids[3:], 1, "k")
    assert _pair_masks._cache_size() == compiled


def test_zero_mask_scale_draws_nothing(rng, monkeypatch):
    """``mask_scale=0``: the weighted delta unmasked, a zero recovery, and
    the mask program never runs."""
    def drawn(*a, **k):
        raise AssertionError("mask_scale 0, yet masks were drawn")

    monkeypatch.setattr(secure_agg, "_pair_masks", drawn)
    masker = PairwiseMasker(seed=1, mask_scale=0.0)
    delta = jnp.asarray(rng.standard_normal(33), jnp.float32)
    out = masker.mask_delta_flat(delta, "a", ["a", "b", "c"], 0, "k", 3.0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(delta * 3.0))
    corr = masker.reconstruct({"w": delta}, ["c"], ["a", "b"], 0, "k")
    np.testing.assert_array_equal(np.asarray(corr["w"]), 0.0)


# ------------------------------------------------------- store secure drain
def test_store_drain_secure_with_dropout():
    rng = np.random.default_rng(5)
    masker = PairwiseMasker(seed=1, mask_scale=1.0)
    init = tree_of(rng)
    store = ModelStore(init, masker=masker)
    ids = ["a", "b", "c"]
    base, masked, plain = _masked_round(rng, masker, ids,
                                        round_id=0, model_key="__global__")
    # only a and b submit; c dropped — drain must reconstruct c's strays
    for cid, (y, d) in zip(ids, masked, strict=True):
        if cid != "c":
            store.submit_secure("global", None, cid, 0, y, d)
    assert store.drain_secure("global", None, 0, ids) == 2
    assert store.n_secure_recoveries == 1
    # reference: same fold of the two plain (unmasked) weighted deltas
    ref = secure_coalesced_aggregate(init, ModelMeta(), plain[:2],
                                     AggregationConfig())
    assert store.meta("global") == ref.meta
    for k in init:
        np.testing.assert_allclose(np.asarray(store.params("global")[k]),
                                   np.asarray(ref.params[k]), atol=1e-5)


def test_drain_secure_missing_masker_raises():
    rng = np.random.default_rng(6)
    store = ModelStore(tree_of(rng))
    store.submit_secure("global", None, "a", 0,
                        tree_of(rng), UpdateDelta(10, 1, 1))
    with pytest.raises(RuntimeError, match="seed reconstruction"):
        store.drain_secure("global", None, 0, ["a", "b"])


# --------------------------------------------------------------- accountant
def test_accountant_epsilon_finite_and_grows():
    acc = RDPAccountant(target_delta=1e-5)
    eps_prev = 0.0
    for _step in range(1, 6):
        acc.record("c0", "__global__", noise_multiplier=1.1)
        eps = acc.client_epsilon("c0")
        assert np.isfinite(eps) and eps > eps_prev
        eps_prev = eps
    rep = acc.model_report()
    assert rep["__global__"]["worst_client"] == "c0"
    assert rep["__global__"]["steps"] == 5


def test_accountant_zero_noise_is_infinite():
    acc = RDPAccountant()
    acc.record("c0", "k", noise_multiplier=0.0)
    assert acc.client_epsilon("c0") == np.inf


@settings(max_examples=10, deadline=None)
@given(sigma=st.floats(0.4, 5.0), k=st.integers(1, 40))
def test_accountant_monotone_in_rounds_property(sigma, k):
    """Privacy invariant: epsilon is strictly increasing in composed steps
    and decreasing in noise."""
    a, b = RDPAccountant(), RDPAccountant()
    for _ in range(k):
        a.record("c", "m", sigma)
        b.record("c", "m", sigma)
    b.record("c", "m", sigma)
    ea, eb = a.client_epsilon("c"), b.client_epsilon("c")
    assert np.isfinite(ea) and eb > ea


def test_rdp_to_epsilon_rejects_bad_delta():
    with pytest.raises(ValueError, match="delta"):
        rdp_to_epsilon([1.0], [2.0], 0.0)


# ------------------------------------------------------------- end to end
def test_sim_secure_masked_matches_unmasked_run():
    """Acceptance: with secure_agg and no dropouts, final global + cluster
    params match the unmasked run within atol 1e-5."""
    fm = make_fed(seed=7, secure_agg=True, secure_mask_scale=1.0)
    fm.run(rounds=3)
    fu = make_fed(seed=7, secure_agg=True, secure_mask_scale=0.0)
    fu.run(rounds=3)
    np.testing.assert_allclose(float(fm.store.params("global")["w"]),
                               float(fu.store.params("global")["w"]), atol=1e-5)
    for k in sorted(fm.store.keys()):
        np.testing.assert_allclose(float(fm.store.params("cluster", k)["w"]),
                                   float(fu.store.params("cluster", k)["w"]),
                                   atol=1e-5)
    assert fm.store.n_secure_recoveries == 0


def test_sim_secure_dropout_recovery_converges():
    """Acceptance: with simulated dropouts the recovery path still matches
    the unmasked run and the rounds complete (cluster specialization)."""
    fm = make_fed(seed=7, secure_agg=True, dropout_prob=0.4)
    stats = fm.run(rounds=4)
    assert stats["secure_recoveries"] > 0          # dropouts actually happened
    fu = make_fed(seed=7, secure_agg=True, dropout_prob=0.4,
                  secure_mask_scale=0.0)
    fu.run(rounds=4)
    np.testing.assert_allclose(float(fm.store.params("global")["w"]),
                               float(fu.store.params("global")["w"]), atol=1e-5)
    vals = [float(fm.store.params("cluster", k)["w"])
            for k in sorted(fm.store.keys())]
    assert max(vals) > 0.5 and min(vals) < -0.5    # still specializes


def test_threaded_secure_full_round_drains():
    fm = make_fed(runtime="threaded", seed=5, secure_agg=True)
    stats = fm.run(rounds=2)
    assert stats["updates"] == 6 * 2 * 2
    assert stats["secure_rounds"] == 2 * (1 + len(fm.store.keys()))
    assert fm.store.meta("global").round == 12
    fu = make_fed(runtime="threaded", seed=5, secure_agg=True,
                  secure_mask_scale=0.0)
    fu.run(rounds=2)
    np.testing.assert_allclose(float(fm.store.params("global")["w"]),
                               float(fu.store.params("global")["w"]), atol=1e-5)


def test_fedccl_privacy_report_grows_with_rounds():
    """Acceptance: privacy_report() returns finite (epsilon, delta) that
    grow with rounds."""
    eps = []
    for rounds in (1, 3):
        fed = make_fed(seed=3, dp_clip=0.5, dp_noise_multiplier=1.2,
                       secure_agg=True)
        fed.run(rounds=rounds)
        rep = fed.privacy_report()
        assert rep["dp"]["enabled"] and rep["secure_agg"]["enabled"]
        per_client = rep["per_client"]
        assert per_client, "accountant saw no releases"
        for row in per_client.values():
            assert np.isfinite(row["epsilon"]) and row["epsilon"] > 0
            assert row["delta"] == pytest.approx(1e-5)
        eps.append(max(r["epsilon"] for r in per_client.values()))
        assert np.isfinite(rep["per_model"]["__global__"]["epsilon"])
    assert eps[1] > eps[0]


def test_secure_round_ids_never_repeat_across_runs():
    """Regression: consecutive run() calls must advance the round-id base —
    pair masks are derived from (pair, round_id, model_key), so a restart
    at 0 would reuse (and leak-by-differencing) the same masks."""
    fm = make_fed(seed=9, secure_agg=True,
                  secure_mask_scale=300.0)   # payload-scale masks (~s*delta)
    fm.run(rounds=2)
    assert fm.store.secure_round_offset == 2
    fm.run(rounds=2)
    assert fm.store.secure_round_offset == 4
    fu = make_fed(seed=9, secure_agg=True, secure_mask_scale=0.0)
    fu.run(rounds=2)
    fu.run(rounds=2)
    np.testing.assert_allclose(float(fm.store.params("global")["w"]),
                               float(fu.store.params("global")["w"]), atol=1e-4)
    ft = make_fed(runtime="threaded", seed=9, secure_agg=True)
    ft.run(rounds=2)
    ft.run(rounds=1)
    assert ft.store.secure_round_offset == 3
    assert ft.store.meta("global").round == 6 * 3


def test_dp_with_plain_async_runtime_still_works():
    """DP privatization composes with the default (non-secure) async path."""
    fed = make_fed(seed=1, dp_clip=2.0, dp_noise_multiplier=0.05,
                   batch_aggregation=True, max_coalesce=4)
    stats = fed.run(rounds=3)
    assert stats["updates"] == 6 * 3 * 2
    rep = fed.privacy_report()
    assert all(np.isfinite(r["epsilon"]) for r in rep["per_client"].values())
    # noise is tiny, so the clusters still specialize
    vals = [float(fed.store.params("cluster", k)["w"])
            for k in sorted(fed.store.keys())]
    assert max(vals) > 0.5 and min(vals) < -0.5
