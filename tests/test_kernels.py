"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles

(run on the CPU backend, where ``repro.kernels.interpret_mode`` picks the
Pallas interpreter; tests/test_tpu_compile.py compiles the same kernels
for a TPU).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # bare CI env: seeded-random fallback shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.kernels.dp_clip_noise.ops import privatize_flat
from repro.kernels.dp_clip_noise.ref import dp_clip_noise_ref
from repro.kernels.fedavg_agg.ops import aggregate_flat, aggregate_pytrees
from repro.kernels.fedavg_agg.ref import agg_ref, aggregate_pytrees_ref
from repro.kernels.ewc_update.ops import ewc_penalty_grad_flat
from repro.kernels.ewc_update.ref import ewc_ref
from repro.kernels.lstm_cell.ops import lstm_cell_fused
from repro.kernels.lstm_cell.ref import lstm_cell_ref
from repro.kernels.local_attn.ops import local_flash_attention
from repro.kernels.local_attn.ref import local_attention_ref


# ------------------------------------------------------------- fedavg_agg
@pytest.mark.parametrize("n,t", [(2, 17), (2, 8192), (3, 100_000), (8, 4096)])
def test_agg_kernel_sweep(n, t, rng):
    x = jnp.asarray(rng.standard_normal((n, t)), jnp.float32)
    w = jnp.asarray(rng.dirichlet(np.ones(n)), jnp.float32)
    np.testing.assert_allclose(np.asarray(aggregate_flat(x, w)),
                               np.asarray(agg_ref(x, w)), atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_agg_pytrees_dtype(dtype, rng):
    trees = [{"a": jnp.asarray(rng.standard_normal((5, 7)), dtype),
              "b": {"c": jnp.asarray(rng.standard_normal(11), dtype)}}
             for _ in range(3)]
    w = [0.2, 0.3, 0.5]
    out = aggregate_pytrees(trees, w)
    ref = aggregate_pytrees_ref(trees, w)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref), strict=True):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-2)


def _lstm_like_tree(rng, dtype):
    """The solar forecaster's leaf shapes: 141,953 parameters."""
    shapes = {"encoder": {"wx": (10, 512), "wh": (128, 512), "b": (512,)},
              "decoder": {"wx": (9, 512), "wh": (128, 512), "b": (512,)},
              "head_w": (128, 1), "head_b": (1,)}
    return jax.tree.map(lambda s: jnp.asarray(rng.standard_normal(s), dtype),
                        shapes, is_leaf=lambda x: isinstance(x, tuple))


def _whole_route(trees, w):
    """The fold as one flattened call: every leaf raveled to float32,
    stacked, one kernel pass, cut back into the leaves."""
    from repro.utils.tree import unflatten_params

    flats = [jnp.concatenate([jnp.ravel(x).astype(jnp.float32)
                              for x in jax.tree.leaves(t)]) for t in trees]
    return unflatten_params(aggregate_flat(jnp.stack(flats), w), trees[0])


def _assert_same_bits(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x).view(np.uint8),
                                      np.asarray(y).view(np.uint8))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunk", [8192, 50_000, 1 << 24],
                         ids=["tile-chunks", "ragged-chunks", "whole"])
def test_agg_pytrees_chunked_same_bits(dtype, chunk, rng, monkeypatch):
    """Folding the LSTM tree chunk by chunk (leaves split across chunks)
    gives the same bits as the whole tree flattened into one call; at a
    chunk wider than the tree, as the default is, it is one chunk."""
    import repro.kernels.fedavg_agg.ops as ops

    monkeypatch.setattr(ops, "CHUNK", chunk)
    trees = [_lstm_like_tree(rng, dtype) for _ in range(2)]
    w = [0.37, 0.63]
    _assert_same_bits(ops.aggregate_pytrees(trees, w), _whole_route(trees, w))


def test_agg_pytrees_chunk_bounds_the_transient(monkeypatch, rng):
    """A tree of several chunks reaches the kernel one chunk at a time:
    no stacked buffer is wider than the chunk, whatever the tree."""
    import repro.kernels.fedavg_agg.ops as ops

    widths = []
    real = ops.aggregate_flat

    def spy(stacked, weights, **kw):
        widths.append(stacked.shape)
        return real(stacked, weights, **kw)

    monkeypatch.setattr(ops, "aggregate_flat", spy)
    monkeypatch.setattr(ops, "CHUNK", 8192)
    trees = [{"big": jnp.asarray(rng.standard_normal((300, 170)), jnp.bfloat16),
              "small": jnp.asarray(rng.standard_normal(9), jnp.float32),
              "empty": jnp.zeros((0, 4), jnp.float32)}
             for _ in range(3)]
    out = ops.aggregate_pytrees(trees, [0.2, 0.3, 0.5])
    assert len(widths) == -(-(300 * 170 + 9) // 8192)
    assert all(n == 3 and t <= 8192 for n, t in widths)
    assert sum(t for _, t in widths) == 300 * 170 + 9
    _assert_same_bits(out, _whole_route(trees, [0.2, 0.3, 0.5]))


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 6), t=st.integers(1, 3000))
def test_agg_kernel_property(n, t):
    rng = np.random.default_rng(n * 1000 + t)
    x = jnp.asarray(rng.standard_normal((n, t)), jnp.float32)
    w = jnp.asarray(rng.dirichlet(np.ones(n)), jnp.float32)
    np.testing.assert_allclose(np.asarray(aggregate_flat(x, w)),
                               np.asarray(agg_ref(x, w)), atol=1e-5)


# ----------------------------------------------------------- dp_clip_noise
@pytest.mark.parametrize("t", [17, 8192, 100_001])
@pytest.mark.parametrize("clip,nm", [(0.5, 0.0), (0.5, 1.5), (1e6, 1.0)])
def test_dp_clip_noise_kernel_sweep(t, clip, nm, rng):
    d = jnp.asarray(rng.standard_normal(t), jnp.float32)
    n = jnp.asarray(rng.standard_normal(t), jnp.float32)
    out = privatize_flat(d, n, clip, nm)
    ref = dp_clip_noise_ref(d, n, clip, nm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    if nm == 0.0:
        assert float(jnp.linalg.norm(out)) <= clip * (1 + 1e-5)


def test_dp_clip_noise_small_delta_passthrough(rng):
    """Deltas inside the clip ball pass through untouched (factor = 1)."""
    d = jnp.asarray(rng.standard_normal(100) * 1e-3, jnp.float32)
    out = privatize_flat(d, jnp.zeros_like(d), 10.0, 0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(d), atol=1e-7)


@settings(max_examples=15, deadline=None)
@given(t=st.integers(1, 3000), clip=st.floats(0.1, 5.0),
       nm=st.floats(0.0, 3.0))
def test_dp_clip_noise_kernel_property(t, clip, nm):
    rng = np.random.default_rng(t * 31 + int(clip * 10) + int(nm * 100))
    d = jnp.asarray(rng.standard_normal(t) * rng.uniform(0.1, 20), jnp.float32)
    n = jnp.asarray(rng.standard_normal(t), jnp.float32)
    np.testing.assert_allclose(np.asarray(privatize_flat(d, n, clip, nm)),
                               np.asarray(dp_clip_noise_ref(d, n, clip, nm)),
                               atol=1e-4)


# ------------------------------------------------------------- ewc_update
@pytest.mark.parametrize("t", [5, 8192, 65536 + 3])
@pytest.mark.parametrize("lam", [0.1, 1.0, 7.5])
def test_ewc_kernel_sweep(t, lam, rng):
    g, p, a = (jnp.asarray(rng.standard_normal(t), jnp.float32) for _ in range(3))
    f = jnp.abs(jnp.asarray(rng.standard_normal(t), jnp.float32))
    go, loss = ewc_penalty_grad_flat(lam, g, p, a, f)
    gr, lr = ewc_ref(lam, g, p, a, f)
    np.testing.assert_allclose(np.asarray(go), np.asarray(gr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(loss), float(lr), rtol=1e-4)


def test_ewc_kernel_l2sp_default(rng):
    t = 1000
    g, p, a = (jnp.asarray(rng.standard_normal(t), jnp.float32) for _ in range(3))
    go, loss = ewc_penalty_grad_flat(0.5, g, p, a, None)
    gr, lr = ewc_ref(0.5, g, p, a, jnp.ones(t))
    np.testing.assert_allclose(np.asarray(go), np.asarray(gr), rtol=1e-5)
    np.testing.assert_allclose(float(loss), float(lr), rtol=1e-4)


# ------------------------------------------------------------- lstm_cell
@pytest.mark.parametrize("B,I,H", [(1, 5, 64), (8, 10, 128), (13, 32, 256)])
def test_lstm_kernel_sweep(B, I, H, rng):
    x = jnp.asarray(rng.standard_normal((B, I)), jnp.float32)
    h = jnp.asarray(rng.standard_normal((B, H)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((B, H)), jnp.float32)
    p = {"wx": jnp.asarray(rng.standard_normal((I, 4 * H)) * .1, jnp.float32),
         "wh": jnp.asarray(rng.standard_normal((H, 4 * H)) * .1, jnp.float32),
         "b": jnp.asarray(rng.standard_normal(4 * H) * .1, jnp.float32)}
    hn, cn = lstm_cell_fused(p, x, h, c)
    hr, cr = lstm_cell_ref(x, h, c, p["wx"], p["wh"], p["b"])
    np.testing.assert_allclose(np.asarray(hn), np.asarray(hr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(cn), np.asarray(cr), atol=1e-5)


def test_lstm_kernel_matches_model_cell(rng):
    """Kernel is a drop-in for the model's lstm_cell."""
    from repro.models.lstm import lstm_cell

    B, I, H = 4, 10, 64
    x = jnp.asarray(rng.standard_normal((B, I)), jnp.float32)
    h = jnp.asarray(rng.standard_normal((B, H)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((B, H)), jnp.float32)
    p = {"wx": jnp.asarray(rng.standard_normal((I, 4 * H)) * .1, jnp.float32),
         "wh": jnp.asarray(rng.standard_normal((H, 4 * H)) * .1, jnp.float32),
         "b": jnp.asarray(rng.standard_normal(4 * H) * .1, jnp.float32)}
    hn, cn = lstm_cell_fused(p, x, h, c)
    hm, cm = lstm_cell(p, x, h, c)
    np.testing.assert_allclose(np.asarray(hn), np.asarray(hm), atol=1e-5)
    np.testing.assert_allclose(np.asarray(cn), np.asarray(cm), atol=1e-5)


# ------------------------------------------------------------- local_attn
@pytest.mark.parametrize("H,KV,S,causal,window,dtype", [
    (4, 2, 64, True, 0, jnp.float32),
    (4, 1, 96, True, 32, jnp.float32),
    (2, 2, 64, False, 0, jnp.float32),
    (8, 4, 128, True, 64, jnp.float32),
    (4, 2, 64, True, 16, jnp.bfloat16),
])
def test_local_attn_kernel_sweep(H, KV, S, causal, window, dtype, rng):
    q = jnp.asarray(rng.standard_normal((2, H, S, 32)), dtype)
    k = jnp.asarray(rng.standard_normal((2, KV, S, 32)), dtype)
    v = jnp.asarray(rng.standard_normal((2, KV, S, 32)), dtype)
    out = local_flash_attention(q, k, v, causal=causal, window=window,
                                scale=0.18, blk_q=32, blk_k=32)
    ref = local_attention_ref(q, k, v, causal=causal, window=window, scale=0.18)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_local_attn_window_actually_limits_context(rng):
    """Tokens outside the window must not influence the output."""
    S, W = 64, 8
    q = jnp.asarray(rng.standard_normal((1, 2, S, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, S, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, S, 16)), jnp.float32)
    out1 = local_flash_attention(q, k, v, causal=True, window=W, scale=0.25,
                                 blk_q=16, blk_k=16)
    # perturb k/v far outside the window of the last query
    k2 = k.at[:, :, :S - 2 * W].set(99.0)
    v2 = v.at[:, :, :S - 2 * W].set(-99.0)
    out2 = local_flash_attention(q, k2, v2, causal=True, window=W, scale=0.25,
                                 blk_q=16, blk_k=16)
    np.testing.assert_allclose(np.asarray(out1[:, :, -1]),
                               np.asarray(out2[:, :, -1]), atol=1e-5)


# ------------------------------------------------------------- ssd_chunk
@pytest.mark.parametrize("b,l,h,p,g,n,chunk", [
    (1, 16, 2, 4, 1, 8, 4),
    (2, 32, 4, 8, 2, 16, 8),
    (1, 20, 2, 16, 1, 32, 8),     # l not divisible by chunk (padding path)
])
def test_ssd_chunk_kernel_sweep(b, l, h, p, g, n, chunk, rng):
    from repro.kernels.ssd_chunk.ops import ssd_chunked_pallas
    from repro.kernels.ssd_chunk.ref import ssd_ref

    x = jnp.asarray(rng.standard_normal((b, l, h, p)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.standard_normal((b, l, h)), jnp.float32))
    A = -jnp.exp(jnp.asarray(rng.standard_normal(h) * 0.5, jnp.float32))
    B = jnp.asarray(rng.standard_normal((b, l, g, n)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((b, l, g, n)), jnp.float32)
    y, s = ssd_chunked_pallas(x, dt, A, B, C, chunk)
    yr, sr = ssd_ref(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=2e-5)


def test_ssd_backend_switch_model_parity(monkeypatch):
    """Full mamba2 model forward: pallas SSD backend == jax backend."""
    from repro.configs import get_config, reduced_for_smoke
    from repro.models import ssm as S
    from repro.models.model import build_model

    cfg = reduced_for_smoke(get_config("mamba2-370m"))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 24), 0, cfg.vocab_size)
    monkeypatch.setattr(S, "SSD_BACKEND", "jax")
    ref, _ = model.forward(params, tokens=toks)
    monkeypatch.setattr(S, "SSD_BACKEND", "pallas")
    out, _ = model.forward(params, tokens=toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)


# ------------------------------------------------------------- dispatch
@pytest.mark.parametrize("backend,explicit,want", [
    ("cpu", None, True), ("tpu", None, False), ("gpu", None, False),
    ("cpu", False, False), ("tpu", True, True)])
def test_interpret_mode_follows_backend_at_call_time(backend, explicit, want,
                                                     monkeypatch):
    """Interpreted only on the CPU backend, decided when a kernel is
    called; an explicit ``interpret=`` wins."""
    from repro import kernels

    monkeypatch.setattr(kernels.jax, "default_backend", lambda: backend)
    assert kernels.interpret_mode(explicit) is want
