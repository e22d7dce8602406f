"""Attention: MHA / GQA / MQA / MLA, RoPE, causal & bidirectional &

sliding-window masks, KV caches, and a pure-JAX chunked flash attention
(online softmax over query/kv blocks) used for long sequences so compiled
peak memory stays linear in sequence length.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, softcap
from repro.sharding.logical import ParamSpec, constrain

NEG_INF = -2.0**30  # large-negative instead of -inf: keeps softmax NaN-free


# ---------------------------------------------------------------------------
# Schemas
# ---------------------------------------------------------------------------


def attention_schema(cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    sch = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        sch["bq"] = ParamSpec((h, hd), ("heads", "head_dim"), init="zeros")
        sch["bk"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"), init="zeros")
        sch["bv"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"), init="zeros")
    return sch


def mla_schema(cfg: ModelConfig) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    if m.q_lora_rank is None:
        query = {"wq": ParamSpec((d, h, m.qk_head_dim), ("embed", "heads", "head_dim"))}
    else:
        query = {
            "wq_a": ParamSpec((d, m.q_lora_rank), ("embed", "rank")),
            "q_norm": ParamSpec((m.q_lora_rank,), ("rank",), init="ones", dtype="float32"),
            "wq_b": ParamSpec((m.q_lora_rank, h, m.qk_head_dim), ("rank", "heads", "head_dim")),
        }
    return {
        **query,
        "wkv_a": ParamSpec((d, m.kv_lora_rank), ("embed", "rank")),
        "kv_norm": ParamSpec((m.kv_lora_rank,), ("rank",), init="ones", dtype="float32"),
        "wk_rope": ParamSpec((d, m.qk_rope_head_dim), ("embed", "head_dim")),
        "wk_b": ParamSpec((m.kv_lora_rank, h, m.qk_nope_head_dim), ("rank", "heads", "head_dim")),
        "wv_b": ParamSpec((m.kv_lora_rank, h, m.v_head_dim), ("rank", "heads", "head_dim")),
        "wo": ParamSpec((h, m.v_head_dim, d), ("heads", "head_dim", "embed")),
    }


# ---------------------------------------------------------------------------
# Masking helpers
# ---------------------------------------------------------------------------


def _mask_bias(q_pos, k_pos, *, causal: bool, window: int):
    """(q, k) additive bias from position vectors.

    q_pos: (s,) or (b, s); k_pos: (t,) or (b, t) -> bias (s, t) or (b, s, t).
    """
    q = q_pos[..., :, None]
    k = k_pos[..., None, :]
    ok = jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), bool)
    if causal:
        ok &= k <= q
    if window:
        ok &= k > q - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Core attention math (einsum path, small sequences / decode)
# ---------------------------------------------------------------------------


def _sdpa(q, k, v, bias, scale, cap, rules):
    """q: (b,s,kv,g,hd); k,v: (b,t,kv,hd); bias: (s,t) or (b,s,t)."""
    qf = q.astype(jnp.float32) * scale
    scores = jnp.einsum("bskgd,btkd->bkgst", qf, k.astype(jnp.float32))
    scores = softcap(scores, cap)
    if bias.ndim == 2:
        scores = scores + bias
    else:
        scores = scores + bias[:, None, None]
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v.astype(jnp.float32))
    return out.astype(v.dtype)


# ---------------------------------------------------------------------------
# Chunked flash attention (pure JAX, linear memory)
# ---------------------------------------------------------------------------


def flash_attention(q, k, v, *, causal: bool, window: int, scale: float,
                    cap: float = 0.0, blk_q: int = 512, blk_k: int = 1024):
    """Online-softmax attention over blocks.  q: (b,s,kv,g,hd), k/v: (b,t,kv,hd).

    Memory per step is O(blk_q * blk_k); never materializes (s, t).
    """
    b, s, kvh, g, hd = q.shape
    hd_v = v.shape[-1]
    t = k.shape[1]
    blk_q = min(blk_q, s)
    blk_k = min(blk_k, t)
    pad_q = (-s) % blk_q
    pad_k = (-t) % blk_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = (s + pad_q) // blk_q, (t + pad_k) // blk_k
    qb = q.reshape(b, nq, blk_q, kvh, g, hd)
    kb = k.reshape(b, nk, blk_k, kvh, hd)
    vb = v.reshape(b, nk, blk_k, kvh, hd_v)

    q_pos_all = jnp.arange(s + pad_q)
    k_pos_all = jnp.arange(t + pad_k)
    k_valid = (k_pos_all < t)

    def q_step(_, qi):
        qchunk = qb[:, qi].astype(jnp.float32) * scale     # (b,blkq,kv,g,hd)
        q_pos = jax.lax.dynamic_slice_in_dim(q_pos_all, qi * blk_q, blk_q)

        def kv_step(carry, ki):
            m, l, acc = carry
            kchunk = kb[:, ki].astype(jnp.float32)
            vchunk = vb[:, ki].astype(jnp.float32)
            k_pos = jax.lax.dynamic_slice_in_dim(k_pos_all, ki * blk_k, blk_k)
            kv_ok = jax.lax.dynamic_slice_in_dim(k_valid, ki * blk_k, blk_k)
            scores = jnp.einsum("bskgd,btkd->bkgst", qchunk, kchunk)
            scores = softcap(scores, cap)
            bias = _mask_bias(q_pos, k_pos, causal=causal, window=window)
            bias = jnp.where(kv_ok[None, :], bias, NEG_INF)
            scores = scores + bias
            m_new = jnp.maximum(m, scores.max(-1))
            p = jnp.exp(scores - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum("bkgst,btkd->bkgsd", p, vchunk)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, g, blk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, blk_q), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, blk_q, hd_v), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)        # (b,kv,g,blkq,hd)
        return _, out.transpose(0, 3, 1, 2, 4)              # (b,blkq,kv,g,hd)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))    # (nq,b,blkq,kv,g,hd_v)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, s + pad_q, kvh, g, hd_v)
    return out[:, :s].astype(v.dtype)


# ---------------------------------------------------------------------------
# Causal flash attention with its own backward pass (training at long s)
# ---------------------------------------------------------------------------


#: queries (and keys) per block of ``causal_flash_attention``
FLASH_BLOCK = 512


def causal_flash_attention(q, k, v, scale: float):
    """Causal attention over blocks, with a hand-written backward pass.

    q, k: (b, s, h, dk); v: (b, s, h, dv) -> (b, s, h, dv).  The forward is
    the online softmax over the key blocks at or before each query block;
    it keeps the output and each row's log-sum-exp, and the backward
    recomputes each block's probabilities from them (FlashAttention-2), so
    neither pass holds more than a few (block, block) tiles per head and
    blocks above the diagonal are never computed.  Products take the
    inputs' dtype with float32 accumulation; the softmax is float32.
    """
    s = q.shape[1]
    blk = min(FLASH_BLOCK, s)
    pad = (-s) % blk
    if pad:
        # padded keys lie after every real query: the causal mask hides them
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
    out = _causal_flash(q, k, v, float(scale), blk)
    return out[:, :s]


def _blocks(x, blk):
    """(b, s, h, d) -> (n, b, h, blk, d): the sequence's blocks, head-major."""
    b, s, h, d = x.shape
    return x.reshape(b, s // blk, blk, h, d).transpose(1, 0, 3, 2, 4)


def _unblocks(x):
    """(n, b, h, blk, d) -> (b, n * blk, h, d)."""
    n, b, h, blk, d = x.shape
    return x.transpose(1, 0, 3, 2, 4).reshape(b, n * blk, h, d)


def _masked_scores(qi, kj, i, j, scale, blk):
    sc = jnp.einsum("bhqd,bhkd->bhqk", qi, kj,
                    preferred_element_type=jnp.float32) * scale
    q_pos = i * blk + jnp.arange(blk)
    k_pos = j * blk + jnp.arange(blk)
    return jnp.where(k_pos[None, :] <= q_pos[:, None], sc, NEG_INF)


def _causal_flash_fwd(q, k, v, scale, blk):
    qb, kb, vb = _blocks(q, blk), _blocks(k, blk), _blocks(v, blk)
    _, b, h, _, dv = vb.shape

    def q_block(_, i):
        qi = qb[i]

        def kv_step(j, carry):
            m, l, acc = carry
            sc = _masked_scores(qi, kb[j], i, j, scale, blk)
            m_new = jnp.maximum(m, sc.max(-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vb.dtype), vb[j],
                preferred_element_type=jnp.float32)
            return m_new, l * corr + p.sum(-1), acc

        m0 = jnp.full((b, h, blk), NEG_INF, jnp.float32)
        init = (m0, jnp.zeros_like(m0), jnp.zeros((b, h, blk, dv), jnp.float32))
        m, l, acc = jax.lax.fori_loop(0, i + 1, kv_step, init)
        return None, ((acc / l[..., None]).astype(v.dtype), m + jnp.log(l))

    _, (ob, lse) = jax.lax.scan(q_block, None, jnp.arange(qb.shape[0]))
    return _unblocks(ob), (q, k, v, ob, lse)


def _causal_flash_bwd(scale, blk, res, dout):
    q, k, v, ob, lse = res
    qb, kb, vb, dob = (_blocks(a, blk) for a in (q, k, v, dout))
    # D_i = rowsum(dO_i * O_i), the softmax backward's correction
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32), -1)

    def q_block(carry, i):
        qi, doi = qb[i], dob[i]

        def kv_step(j, c):
            dq, dk, dv = c
            kj, vj = kb[j], vb[j]
            p = jnp.exp(_masked_scores(qi, kj, i, j, scale, blk)
                        - lse[i][..., None])
            dp = jnp.einsum("bhqd,bhkd->bhqk", doi, vj,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delta[i][..., None])
            dv = dv.at[j].add(jnp.einsum(
                "bhqk,bhqd->bhkd", p.astype(doi.dtype), doi,
                preferred_element_type=jnp.float32))
            dk = dk.at[j].add(scale * jnp.einsum(
                "bhqk,bhqd->bhkd", ds.astype(qi.dtype), qi,
                preferred_element_type=jnp.float32))
            dq = dq + scale * jnp.einsum("bhqk,bhkd->bhqd", ds.astype(kj.dtype),
                                         kj, preferred_element_type=jnp.float32)
            return dq, dk, dv

        dk, dv = carry
        dq0 = jnp.zeros(qi.shape, jnp.float32)
        dq, dk, dv = jax.lax.fori_loop(0, i + 1, kv_step, (dq0, dk, dv))
        return (dk, dv), dq.astype(q.dtype)

    init = (jnp.zeros(kb.shape, jnp.float32), jnp.zeros(vb.shape, jnp.float32))
    (dk, dv), dqb = jax.lax.scan(q_block, init, jnp.arange(qb.shape[0]))
    return (_unblocks(dqb), _unblocks(dk).astype(k.dtype),
            _unblocks(dv).astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _causal_flash(q, k, v, scale, blk):
    return _causal_flash_fwd(q, k, v, scale, blk)[0]


_causal_flash.defvjp(_causal_flash_fwd, _causal_flash_bwd)


# ---------------------------------------------------------------------------
# Standard (GQA) attention layer
# ---------------------------------------------------------------------------

# Use the chunked-flash path for sequences strictly longer than this.
# Overridable for perf experiments: the default keeps the unfused sdpa path
# at train_4k (paper-faithful baseline); §Perf drops it to 1024 so training
# attention never materializes (s, t) scores.
import os as _os

FLASH_THRESHOLD = int(_os.environ.get("REPRO_FLASH_THRESHOLD", "4096"))

# Attention backend for full-sequence (cache-free) attention:
#   "jax"    — einsum sdpa / pure-JAX chunked flash (default)
#   "pallas" — the repro.kernels.local_attn flash kernel (TPU target;
#              interpret-mode on CPU). Softcapped attns fall back to jax.
ATTN_BACKEND = _os.environ.get("REPRO_ATTN_BACKEND", "jax")


def _pallas_attention(qg, k, v, *, causal, window, scale):
    """qg: (b,s,kv,g,hd); k/v: (b,t,kv,hd) -> (b,s,kv,g,hd)."""
    from repro.kernels.local_attn.ops import local_flash_attention

    b, s, kvh, g, hd = qg.shape
    qh = qg.reshape(b, s, kvh * g, hd).transpose(0, 2, 1, 3)   # (b,H,s,hd)
    kh = k.transpose(0, 2, 1, 3)                                # (b,KV,t,hd)
    vh = v.transpose(0, 2, 1, 3)
    out = local_flash_attention(qh, kh, vh, causal=causal, window=window,
                                scale=scale)
    return out.transpose(0, 2, 1, 3).reshape(b, s, kvh, g, hd)


def attention_forward(cfg: ModelConfig, p: dict, x, *, positions, window: int,
                      causal: bool, rules=None, cache: dict | None = None,
                      cache_pos=None, rolling: bool = False):
    """Full-sequence forward (cache=None) or single/multi-token decode step.

    Returns (y, new_cache). Cache layout: {"k","v"}: (b, S, kv, hd).
    ``rolling=True``: the cache is window-sized; each step shifts it left and
    appends (local attention — RecurrentGemma).  ``cache_pos`` is then the
    absolute position of the first new token.
    """
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kv
    scale = hd ** -0.5

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = constrain(q, ("batch", "seq", "heads", "head_dim"), rules)
    k = constrain(k, ("batch", "seq", "kv_heads", "head_dim"), rules)
    v = constrain(v, ("batch", "seq", "kv_heads", "head_dim"), rules)

    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    qg = q.reshape(b, s, kv, g, hd)

    if cache is None:
        if ATTN_BACKEND == "pallas" and not cfg.attn_logit_softcap:
            out = _pallas_attention(qg, k, v, causal=causal, window=window,
                                    scale=scale)
        elif s > FLASH_THRESHOLD:
            out = flash_attention(qg, k, v, causal=causal, window=window,
                                  scale=scale, cap=cfg.attn_logit_softcap)
        else:
            pos = positions
            bias = _mask_bias(pos, pos, causal=causal, window=window)
            out = _sdpa(qg, k, v, bias, scale, cfg.attn_logit_softcap, rules)
        new_cache = None
    elif rolling:
        # window-sized rolling cache: shift left by s, append new k/v.
        S = cache["k"].shape[1]
        ck = jnp.concatenate([cache["k"][:, s:], k.astype(cache["k"].dtype)], axis=1)
        cv = jnp.concatenate([cache["v"][:, s:], v.astype(cache["v"].dtype)], axis=1)
        new_cache = {"k": ck, "v": cv}
        # slot i holds absolute position cache_pos + s - S + i
        # (cache_pos may be (b,) — continuous batching)
        pos_b = jnp.broadcast_to(jnp.atleast_1d(cache_pos), (b,))
        k_pos_idx = pos_b[:, None] + s - S + jnp.arange(S)      # (b, S)
        valid = k_pos_idx >= 0
        bias = _mask_bias(positions, k_pos_idx, causal=causal, window=window)
        bias = jnp.where(valid[:, None, :], bias, NEG_INF)
        out = _sdpa(qg, ck, cv, bias, scale, cfg.attn_logit_softcap, rules)
    else:
        # decode: write new k/v at cache_pos, attend over (windowed) cache.
        # cache_pos: scalar (lockstep batch) or (b,) per-sequence offsets.
        S = cache["k"].shape[1]
        pos_b = jnp.broadcast_to(jnp.atleast_1d(cache_pos), (b,))
        upd = jax.vmap(
            lambda c, x_, p: jax.lax.dynamic_update_slice_in_dim(c, x_, p, 0))
        ck = upd(cache["k"], k.astype(cache["k"].dtype), pos_b)
        cv = upd(cache["v"], v.astype(cache["v"].dtype), pos_b)
        new_cache = {"k": ck, "v": cv}
        if window and window < S:
            start = jnp.clip(pos_b + s - window, 0, S - window)  # (b,)
            slc = jax.vmap(
                lambda c, p: jax.lax.dynamic_slice_in_dim(c, p, window, 0))
            k_att = slc(ck, start)
            v_att = slc(cv, start)
            k_pos_idx = start[:, None] + jnp.arange(window)      # (b, window)
        else:
            k_att, v_att = ck, cv
            k_pos_idx = jnp.broadcast_to(jnp.arange(S), (b, S))
        valid = k_pos_idx < (pos_b[:, None] + s)             # only written slots
        bias = _mask_bias(positions, k_pos_idx, causal=causal, window=window)
        bias = jnp.where(valid[:, None, :], bias, NEG_INF)
        out = _sdpa(qg, k_att, v_att, bias, scale, cfg.attn_logit_softcap, rules)

    out = out.reshape(b, s, h, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return constrain(y, ("batch", "seq", "embed"), rules), new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2/V3) — latent-compressed attention
# ---------------------------------------------------------------------------


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) * scale).astype(x.dtype)


def mla_forward(cfg: ModelConfig, p: dict, x, *, positions, window: int,
                causal: bool, rules=None, cache: dict | None = None,
                cache_pos=None, absorb: bool = True):
    """MLA attention.  Cache holds the latent c_kv + shared rope key only
    (the paper-faithful memory saving).  ``absorb=True`` uses the matrix-
    absorption decode trick (scores computed in latent space) — the
    beyond-paper §Perf optimization; ``absorb=False`` re-expands K/V.
    """
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    scale = m.qk_head_dim ** -0.5

    eps = cfg.norm_eps
    if m.q_lora_rank is None:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    else:
        cq = _rms(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_norm"], eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"])             # (b,s,h,qk_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_interleave)

    c_kv = _rms(jnp.einsum("bsd,dr->bsr", x, p["wkv_a"]), p["kv_norm"], eps)  # (b,s,rank)
    k_rope_new = apply_rope(jnp.einsum("bsd,dk->bsk", x, p["wk_rope"]), positions,
                            cfg.rope_theta, cfg.rope_interleave)  # (b,s,rope_dim)

    if cache is not None:
        # cache_pos: scalar or (b,) per-sequence offsets (continuous batching)
        pos_b = jnp.broadcast_to(jnp.atleast_1d(cache_pos), (b,))
        upd = jax.vmap(
            lambda c, x_, pp: jax.lax.dynamic_update_slice_in_dim(c, x_, pp, 0))
        c_kv_all = upd(cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), pos_b)
        k_rope_all = upd(cache["k_rope"],
                         k_rope_new.astype(cache["k_rope"].dtype), pos_b)
        new_cache = {"c_kv": c_kv_all, "k_rope": k_rope_all}
        S = c_kv_all.shape[1]
        if window and window < S:
            start = jnp.clip(pos_b + s - window, 0, S - window)
            slc = jax.vmap(
                lambda c, pp: jax.lax.dynamic_slice_in_dim(c, pp, window, 0))
            c_att = slc(c_kv_all, start)
            r_att = slc(k_rope_all, start)
            k_pos_idx = start[:, None] + jnp.arange(window)      # (b, window)
        else:
            c_att, r_att = c_kv_all, k_rope_all
            k_pos_idx = jnp.broadcast_to(jnp.arange(S), (b, S))
        valid = k_pos_idx < (pos_b[:, None] + s)
    else:
        new_cache = None
        c_att, r_att = c_kv, k_rope_new
        k_pos_idx = positions
        valid = None

    if cache is None and s > FLASH_THRESHOLD:
        # long prefill: re-expand K/V (heads sharded over `model`) and run the
        # chunked-flash path so peak memory stays O(block^2), not O(s*t).
        k_nope = jnp.einsum("btr,rhk->bthk", c_att, p["wk_b"].astype(c_att.dtype))
        v_exp = jnp.einsum("btr,rhk->bthk", c_att, p["wv_b"].astype(c_att.dtype))
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(r_att[:, :, None, :],
                                      (*k_nope.shape[:2], h, m.qk_rope_head_dim)).astype(k_nope.dtype)],
            axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)      # (b,s,h,qk_hd)
        if causal and not window:
            out = causal_flash_attention(q_full, k_full, v_exp, scale)
        else:
            qg = q_full.reshape(b, s, h, 1, m.qk_head_dim)
            out = flash_attention(qg, k_full, v_exp, causal=causal, window=window,
                                  scale=scale).reshape(b, s, h, m.v_head_dim)
        y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"])
        return constrain(y, ("batch", "seq", "embed"), rules), None

    bias = _mask_bias(positions, k_pos_idx, causal=causal, window=window)
    if valid is not None:
        bias = jnp.where(valid[:, None, :], bias, NEG_INF)
    if bias.ndim == 3:
        bias = bias[:, None]                    # (b, 1, s, t) for bhst scores

    cf = c_att.astype(jnp.float32)
    rf = r_att.astype(jnp.float32)
    # rope-part scores: every head shares the cached rope key (MQA-like)
    s_rope = jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32), rf)

    if absorb:
        # absorb wk_b into the query: score in latent space, O(t*rank*h)
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope.astype(jnp.float32),
                           p["wk_b"].astype(jnp.float32))
        s_nope = jnp.einsum("bshr,btr->bhst", q_lat, cf)
        w = jax.nn.softmax((s_nope + s_rope) * scale + bias, axis=-1)
        o_lat = jnp.einsum("bhst,btr->bshr", w, cf)                 # (b,s,h,rank)
        out = jnp.einsum("bshr,rhk->bshk", o_lat, p["wv_b"].astype(jnp.float32))
    else:
        # paper-naive: re-expand K and V from the latent for every step
        k_nope = jnp.einsum("btr,rhk->bthk", cf, p["wk_b"].astype(jnp.float32))
        v_exp = jnp.einsum("btr,rhk->bthk", cf, p["wv_b"].astype(jnp.float32))
        s_nope = jnp.einsum("bshk,bthk->bhst", q_nope.astype(jnp.float32), k_nope)
        w = jax.nn.softmax((s_nope + s_rope) * scale + bias, axis=-1)
        out = jnp.einsum("bhst,bthk->bshk", w, v_exp)

    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"])
    return constrain(y, ("batch", "seq", "embed"), rules), new_cache


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Abstract per-layer cache shapes (stacked over layers by the caller)."""
    if cfg.mla is not None:
        return {
            "c_kv": jnp.zeros((batch, max_len, cfg.mla.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, cfg.mla.qk_rope_head_dim), dtype),
        }
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
    }
