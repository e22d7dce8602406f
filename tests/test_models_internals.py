"""Model-internal properties: flash==sdpa, SSD chunk invariance, RoPE,

RG-LRU scan vs sequential, MoE router invariants.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # bare CI env: seeded-random fallback shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.configs import get_config, reduced_for_smoke
from repro.models import attention as A
from repro.models.layers import apply_rope
from repro.models.ssm import ssd_chunked


# ---------------------------------------------------------------- attention
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 24), (False, 0)])
def test_flash_matches_sdpa(causal, window):
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(k1, (2, 80, 2, 3, 16))
    k = jax.random.normal(k2, (2, 80, 2, 16))
    v = jax.random.normal(k3, (2, 80, 2, 16))
    fa = A.flash_attention(q, k, v, causal=causal, window=window, scale=0.25,
                           blk_q=16, blk_k=32)
    bias = A._mask_bias(jnp.arange(80), jnp.arange(80), causal=causal,
                        window=window)
    ref = A._sdpa(q, k, v, bias, 0.25, 0.0, None)
    np.testing.assert_allclose(np.asarray(fa), np.asarray(ref), atol=2e-5)


def test_rope_preserves_norm_and_relative_angle():
    x = jax.random.normal(jax.random.key(0), (1, 8, 2, 16))
    pos = jnp.arange(8)
    rx = apply_rope(x, pos, 10_000.0)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                               np.linalg.norm(np.asarray(rx), axis=-1),
                               rtol=1e-5)
    # relative property: <R(p)q, R(p+d)k> depends only on d
    q = jax.random.normal(jax.random.key(1), (1, 1, 1, 16))
    k = jax.random.normal(jax.random.key(2), (1, 1, 1, 16))
    def dot_at(p, d):
        rq = apply_rope(q, jnp.array([p]), 10_000.0)
        rk = apply_rope(k, jnp.array([p + d]), 10_000.0)
        return float(jnp.sum(rq * rk))
    assert abs(dot_at(3, 5) - dot_at(10, 5)) < 1e-4


# ---------------------------------------------------------------- SSD
@settings(max_examples=10, deadline=None)
@given(chunk=st.sampled_from([2, 4, 8, 16]))
def test_ssd_chunk_size_invariance(chunk):
    """SSD output must not depend on the chunking — state-space duality."""
    key = jax.random.key(42)
    ks = jax.random.split(key, 4)
    b, l, h, p, g, n = 1, 16, 2, 4, 1, 8
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    Amat = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    B = jax.random.normal(ks[3], (b, l, g, n))
    C = jax.random.normal(jax.random.key(7), (b, l, g, n))
    y_ref, s_ref = ssd_chunked(x, dt, Amat, B, C, chunk=l)   # single chunk
    y, s = ssd_chunked(x, dt, Amat, B, C, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=2e-4, atol=2e-4)


def test_ssd_matches_naive_recurrence():
    key = jax.random.key(1)
    ks = jax.random.split(key, 5)
    b, l, h, p, g, n = 1, 12, 1, 3, 1, 4
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    Amat = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l, g, n))
    C = jax.random.normal(ks[4], (b, l, g, n))
    y, _ = ssd_chunked(x, dt, Amat, B, C, chunk=4)

    # naive elementwise recurrence
    state = np.zeros((b, h, p, n))
    ys = []
    for t in range(l):
        dA = np.exp(np.asarray(dt[:, t] * Amat))                 # (b,h)
        Bx = np.einsum("bh,bn,bhp->bhpn", np.asarray(dt[:, t]),
                       np.asarray(B[:, t, 0]), np.asarray(x[:, t]))
        state = state * dA[..., None, None] + Bx
        ys.append(np.einsum("bhpn,bn->bhp", state, np.asarray(C[:, t, 0])))
    y_naive = np.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y), y_naive, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- MoE
def test_moe_router_gates_normalized_and_aux_positive(rng):
    from repro.models.moe import moe_forward, moe_schema
    from repro.sharding.logical import init_from_schema

    cfg = reduced_for_smoke(get_config("deepseek-moe-16b"))
    p = init_from_schema(moe_schema(cfg), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 8, cfg.d_model))
    y, aux = moe_forward(cfg, p, x)
    assert y.shape == x.shape
    assert float(aux["loss"]) > 0.0
    assert not bool(jnp.isnan(y).any())


def test_moe_capacity_drops_are_bounded(rng):
    """Routing is dropless: every (token, k) assignment is computed, so the
    held experts' counts add up to tokens * k and every token's output
    differs from the shared-experts-only output."""
    from repro.models.mlp import mlp_forward
    from repro.models.moe import moe_forward, moe_schema
    from repro.sharding.logical import init_from_schema

    cfg = reduced_for_smoke(get_config("deepseek-v3-671b"))
    p = init_from_schema(moe_schema(cfg), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 16, cfg.d_model))
    y, aux = moe_forward(cfg, p, x)
    assert not bool(jnp.isnan(y).any())
    assert int(aux["load"].sum()) == 16 * cfg.moe.top_k
    shared = mlp_forward(p["shared"], x, cfg.mlp_activation)
    assert bool((jnp.abs(y - shared).max(-1) > 0).all())
