"""Windowed flash attention Pallas kernel (online softmax, GQA-aware).

Used by RecurrentGemma's local-attention blocks and the sliding-window
variant that makes dense architectures sub-quadratic at long_500k.

TPU adaptation (vs. the CUDA flash-attention algorithm):
  * grid (B, H, nQ, nK) with the kv index innermost — the TPU grid is
    sequential, so the online-softmax carry lives in VMEM scratch across
    nK iterations (no atomics / warp shuffles needed);
  * GQA without materializing repeated K/V: the K/V BlockSpec index_map
    divides the head index (h // group) — the MQA/GQA gather happens in
    the DMA, not in HBM;
  * out-of-window (q, k) block pairs are skipped with pl.when on scalar
    grid indices: for window W the per-q-row work is O(W), giving the
    sub-quadratic long-context path;
  * block shapes default to (128, 128) — MXU-aligned lanes/sublanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30

DEFAULT_BLK_Q = 128
DEFAULT_BLK_K = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, causal, window, blk_q, blk_k, nk, t_real):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * blk_q
    k_start = ki * blk_k

    # block-level skip: entirely above the diagonal or left of the window
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + blk_q - 1)
    if window:
        run = jnp.logical_and(run, k_start + blk_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (blk_q, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (blk_k, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        ok = k_pos < t_real
        if causal:
            ok = jnp.logical_and(ok, k_pos <= q_pos)
        if window:
            ok = jnp.logical_and(ok, k_pos > q_pos - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jnp.dot(p, v, preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "blk_q", "blk_k", "t_real",
                     "interpret"))
def flash_tiled(q, k, v, *, causal: bool, window: int, scale: float,
                t_real: int, blk_q: int = DEFAULT_BLK_Q,
                blk_k: int = DEFAULT_BLK_K, interpret: bool):
    """q: (B, H, S, D); k/v: (B, KV, T, D); S % blk_q == 0, T % blk_k == 0.
    Returns (B, H, S, D)."""
    B, H, S, D = q.shape
    KV, T = k.shape[1], k.shape[2]
    group = H // KV
    nq, nk = S // blk_q, T // blk_k
    grid = (B, H, nq, nk)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        blk_q=blk_q, blk_k=blk_k, nk=nk, t_real=t_real)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, D), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
