"""Mixture-of-Experts layer: shared + routed experts, top-k routing, and a
dropless grouped matmul over the routed experts this chip holds.

The router scores every routed expert (``n_routed_experts``) and picks each
token's top-k; the layer computes the contribution of its held experts
(``MoEConfig.n_held`` from ``first_held``; all of them unless the config
states a share) for every (token, k) routed to them, with no capacity and
no token dropped: assignments are sorted by expert and each expert's rows
go through one grouped matmul (``jax.lax.ragged_dot``), so the work is
proportional to the assignments, not to a dense all-experts pass.  What the
experts held elsewhere would add is left out: with expert parallelism
another chip computes it.  The shared experts are computed once, here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import activation
from repro.models.mlp import mlp_forward, mlp_schema
from repro.sharding.logical import ParamSpec


def moe_schema(cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, held = cfg.d_model, m.held_count
    sch = {
        "router": ParamSpec((d, m.n_routed_experts), ("embed", "expert"), scale=0.02),
        "experts": {
            "w_gate": ParamSpec((held, d, m.moe_d_ff), ("expert", "embed", "expert_mlp")),
            "w_up": ParamSpec((held, d, m.moe_d_ff), ("expert", "embed", "expert_mlp")),
            "w_down": ParamSpec((held, m.moe_d_ff, d), ("expert", "expert_mlp", "embed")),
        },
    }
    if m.n_shared_experts:
        sch["shared"] = mlp_schema(d, m.moe_d_ff * m.n_shared_experts)
    if m.score_func == "sigmoid":
        sch["router_bias"] = ParamSpec((m.n_routed_experts,), ("expert",), init="zeros",
                                       dtype="float32")
    return sch


def zero_aux(cfg: ModelConfig) -> dict:
    """A block's auxiliary outputs when it routes nothing: the load-balance
    loss and, in an MoE model, the held experts' assignment counts."""
    aux = {"loss": jnp.zeros((), jnp.float32)}
    if cfg.is_moe:
        aux["load"] = jnp.zeros((cfg.moe.held_count,), jnp.int32)
    return aux


def moe_forward(cfg: ModelConfig, p: dict, x, rules=None):
    """x: (b, s, d) -> (y, aux): ``aux["loss"]`` the load-balance loss,
    ``aux["load"]`` the (token, k) assignments routed to each held expert."""
    m = cfg.moe
    b, s, d = x.shape
    T = b * s
    E, K = m.n_routed_experts, m.top_k
    held, first = m.held_count, m.first_held

    xt = x.reshape(T, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if m.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel_scores = scores + p["router_bias"]          # aux-loss-free biasing (DSv3)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        sel_scores = scores
    _, top_e = jax.lax.top_k(sel_scores, K)              # (T, K)
    gate_w = jnp.take_along_axis(scores, top_e, axis=-1)  # gate from unbiased scores
    if m.score_func == "sigmoid":
        # normalised over the k chosen (DeepSeek-V3)
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-20)
    gate_w = gate_w * m.routed_scaling

    # ---- load-balance auxiliary loss (Switch-style), over every expert ----
    flat_e = top_e.reshape(-1)                                     # (T*K,)
    counts = jnp.zeros((E,), jnp.float32).at[flat_e].add(1.0)
    aux_loss = m.router_aux_coef * E * jnp.sum(counts / (T * K) * scores.mean(0))

    # ---- dropless dispatch to the held experts ----------------------------
    # assignments sorted by held expert; those routed elsewhere sort last,
    # past every group.  The grouped matmul leaves rows past the groups
    # unspecified, so they are zeroed going in and coming out, where a
    # select also stops their cotangents in the backward pass.
    local = flat_e - first
    is_held = (local >= 0) & (local < held)
    group = jnp.where(is_held, local, held)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
    rows = (jnp.arange(T * K) < sizes.sum())[:, None]

    def grouped(a, w):
        return jnp.where(rows, jax.lax.ragged_dot(a, w, sizes), 0)

    xs = jnp.where(rows, jnp.take(xt, order // K, axis=0), 0)     # (T*K, d)
    ws = jnp.where(is_held, gate_w.reshape(-1), 0.0)[order]

    act = activation(cfg.mlp_activation)
    ew = p["experts"]
    h = act(grouped(xs, ew["w_gate"])) * grouped(xs, ew["w_up"])
    ys = grouped(h, ew["w_down"]) * ws[:, None].astype(h.dtype)
    # back to (token, k) order, then each token's k contributions summed in
    # float32
    inverse = jnp.zeros_like(order).at[order].set(jnp.arange(T * K))
    y = jnp.take(ys, inverse, axis=0).reshape(T, K, d).astype(jnp.float32).sum(1)

    if m.n_shared_experts:
        y = y + mlp_forward(p["shared"], xt[None], cfg.mlp_activation, rules)[0]

    return (y.astype(x.dtype).reshape(b, s, d),
            {"loss": aux_loss.astype(jnp.float32), "load": sizes})
