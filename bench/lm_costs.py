"""Analytic work of the silo fine-tuning cell's training step, from the
configuration's sizes alone (``configs/kanana2-30b-a3b-silo.json``,
``model``).

These are the numerators of ``lm_step_roofline`` and ``mfu.lm_train``.
They count what the algorithm needs: two FLOPs a multiply-add, the
backward pass twice the forward, recomputation not counted; causal
attention over the keys at or before each query; the routed experts'
work counted per (token, k) assignment that reached a held expert, as the
program counts them (``moe_assignments_held``).
"""

from __future__ import annotations


def _ffn(d: int, width: int) -> int:
    """A SwiGLU FFN on one token: three d x width products."""
    return 3 * 2 * d * width


def attention_proj_flops(m: dict) -> int:
    """One layer's MLA projections on one token (no query low-rank path)."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    nope, rope, v = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    rank = m["kv_lora_rank"]
    return 2 * (d * h * (nope + rope) + d * rank + d * rope
                + rank * h * nope + rank * h * v + h * v * d)


def attention_core_flops(m: dict, seq_len: int) -> int:
    """One layer's scores and weighted values over one causal sequence:
    query i meets keys 0..i, s(s+1)/2 pairs in all."""
    h = m["num_attention_heads"]
    dqk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return 2 * h * (dqk + m["v_head_dim"]) * seq_len * (seq_len + 1) // 2


def unrouted_flops_per_token(m: dict) -> int:
    """Forward FLOPs a token costs outside attention's scores and values
    and outside the routed experts: projections, the dense layers' FFN,
    the router, the shared experts and the head."""
    d, layers, dense = m["hidden_size"], m["num_hidden_layers"], \
        m["first_k_dense_replace"]
    router = 2 * d * m["expert_share"]["router_experts"]
    shared = _ffn(d, m["moe_intermediate_size"] * m["n_shared_experts"])
    return (layers * attention_proj_flops(m)
            + dense * _ffn(d, m["intermediate_size"])
            + (layers - dense) * (router + shared)
            + 2 * d * m["vocab_size"])


def routed_flops_per_assignment(m: dict) -> int:
    """Forward FLOPs of one (token, k) assignment on a held expert."""
    return _ffn(m["hidden_size"], m["moe_intermediate_size"])


def train_flops(m: dict, sequences: int, assignments: int) -> int:
    """Forward and backward FLOPs of ``sequences`` training sequences of
    ``seq_len`` tokens whose held experts took ``assignments``."""
    s = m["seq_len"]
    per_seq = (s * unrouted_flops_per_token(m)
               + m["num_hidden_layers"] * attention_core_flops(m, s))
    return 3 * (sequences * per_seq
                + assignments * routed_flops_per_assignment(m))


def expected_assignments(m: dict, sequences: int) -> float:
    """Assignments the held experts take under even routing: each token's
    k choices land here at the held share of the router's experts."""
    share = m["n_routed_experts"] / m["expert_share"]["router_experts"]
    return (sequences * m["seq_len"] * m["num_experts_per_tok"] * share
            * (m["num_hidden_layers"] - m["first_k_dense_replace"]))


def param_count(m: dict) -> int:
    """Parameters of the chip's share, in the program's layout."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    nope, rope, v = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    rank, f = m["kv_lora_rank"], m["moe_intermediate_size"]
    attn = (d * h * (nope + rope) + d * rank + rank + d * rope
            + rank * h * nope + rank * h * v + h * v * d)
    norms = 2 * d
    ffn = _ffn(d, 1) // 2                       # 3 * d per unit of width
    dense = attn + norms + ffn * m["intermediate_size"]
    moe = (attn + norms + d * m["expert_share"]["router_experts"]
           + m["expert_share"]["router_experts"]
           + m["n_routed_experts"] * ffn * f
           + ffn * f * m["n_shared_experts"])
    layers, k = m["num_hidden_layers"], m["first_k_dense_replace"]
    return (2 * m["vocab_size"] * d + d + k * dense + (layers - k) * moe)


def held_assignments(counters: dict):
    """The held experts' routed assignments, summed over the program's
    ``moe_assignments_held.<expert>`` counters; None where none was
    counted."""
    held = [v for k, v in counters.items()
            if k.startswith("moe_assignments_held.")]
    return sum(held) if held else None
