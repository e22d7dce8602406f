"""Roofline-term derivation from compiled dry-run artifacts.

    compute term    = FLOPs / (chips * peak_FLOP/s)
    memory term     = HBM_bytes / (chips * HBM_bw)
    collective term = collective_bytes / (chips * link_bw)

IMPORTANT measurement caveat (verified empirically on this jax/XLA): XLA's
``compiled.cost_analysis()`` counts while-loop *bodies once*, NOT multiplied
by trip count — a scan over L layers reports ~1 layer of flops/bytes.  The
dry-run therefore reports BOTH:
  * raw cost_analysis numbers (exact for scan-free graphs, undercounted for
    scanned stacks), and
  * an analytic per-device cost model (exact closed forms per architecture
    family, the PRIMARY source for the §Roofline table).
Collective bytes are parsed from the compiled HLO text; collectives inside
while-body computations are multiplied by the known scan trip count
(layer count) — this captures the per-layer FSDP all-gathers correctly.
All-reduce payloads are counted twice (ring send+receive).

Hardware model (TPU v5e): 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per ICI link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %x = bf16[8,128,512]{2,1,0} all-gather(...)   or tuple shapes
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\][^ ]*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


_COMP_RE = re.compile(r"^(?:%|ENTRY %)?([\w.\-]+)(?:\s+\([^)]*\))?\s*(?:->[^{]*)?\{",
                      re.MULTILINE)


def _split_computations(hlo_text: str) -> dict[str, str]:
    """Split HLO text into named computation bodies (brace-balanced)."""
    comps = {}
    for m in _COMP_RE.finditer(hlo_text):
        name = m.group(1)
        start = m.end()
        depth = 1
        i = start
        while i < len(hlo_text) and depth:
            if hlo_text[i] == "{":
                depth += 1
            elif hlo_text[i] == "}":
                depth -= 1
            i += 1
        comps[name] = hlo_text[start:i]
    return comps


def collective_bytes_from_hlo(hlo_text: str, scan_trip: int = 1) -> dict:
    """Sum of collective payload bytes (per device), by op kind.

    ``scan_trip``: collectives found inside while-body computations are
    multiplied by this factor (the layer-scan trip count) to undo XLA's
    count-body-once convention.  Collectives in the entry computation are
    counted once.
    """
    comps = _split_computations(hlo_text)
    # while-body computations referenced by while ops
    body_names = set(re.findall(r"body=%?([\w.\-]+)", hlo_text))
    cond_names = set(re.findall(r"condition=%?([\w.\-]+)", hlo_text))

    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for name, body in comps.items():
        mult = scan_trip if (name in body_names or name in cond_names) else 1
        for m in _OP_RE.finditer(body):
            shape_text, kind = m.group(1), m.group(2)
            b = _shape_bytes(shape_text)
            if kind == "all-reduce":
                b *= 2               # ring all-reduce moves ~2x the payload
            out[kind] += b * mult
            counts[kind] += 1
    out_total = sum(out.values())
    return {"total": out_total, "by_kind": out, "counts": counts,
            "scan_trip": scan_trip}


@dataclass(frozen=True)
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_dev: float
    bytes_per_dev: float
    collective_bytes_per_dev: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    def as_dict(self) -> dict:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "flops_per_dev": self.flops_per_dev,
            "bytes_per_dev": self.bytes_per_dev,
            "collective_bytes_per_dev": self.collective_bytes_per_dev,
        }


def roofline_terms(cost: dict, coll: dict) -> RooflineTerms:
    flops = float(cost.get("flops", 0.0) or 0.0)
    bts = float(cost.get("bytes accessed", 0.0) or 0.0)
    cb = float(coll["total"])
    return RooflineTerms(
        compute_s=flops / PEAK_FLOPS,
        memory_s=bts / HBM_BW,
        collective_s=cb / LINK_BW,
        flops_per_dev=flops,
        bytes_per_dev=bts,
        collective_bytes_per_dev=cb,
    )


# ---------------------------------------------------------------------------
# Analytic per-device cost model (primary §Roofline source — see module doc)
# ---------------------------------------------------------------------------


def _attn_flops_per_token(cfg, ctx: float, *, decode: bool = False,
                          mla_absorb: bool = True) -> float:
    """Per-layer attention flops for one token given avg context length."""
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if cfg.mla is not None:
        m = cfg.mla
        q_proj = (2 * d * h * m.qk_head_dim if m.q_lora_rank is None else
                  2 * d * m.q_lora_rank + 2 * m.q_lora_rank * h * m.qk_head_dim)
        kv_proj = 2 * d * (m.kv_lora_rank + m.qk_rope_head_dim)
        o_proj = 2 * h * m.v_head_dim * d
        if decode and not mla_absorb:
            # paper-naive decode: re-expand K/V from the latent cache for
            # the WHOLE context every step — O(ctx * rank * h * (nope+v))
            expand = 2 * ctx * m.kv_lora_rank * h * (m.qk_nope_head_dim
                                                     + m.v_head_dim)
            sdpa = 4 * h * m.qk_head_dim * ctx
            return q_proj + kv_proj + o_proj + expand + sdpa
        if decode:
            # absorbed: scores/av in latent space, O(ctx * h * rank)
            absorb_q = 2 * h * m.qk_nope_head_dim * m.kv_lora_rank
            scores = 2 * h * (m.kv_lora_rank + m.qk_rope_head_dim) * ctx
            av = 2 * h * m.kv_lora_rank * ctx
            v_up = 2 * h * m.kv_lora_rank * m.v_head_dim
            return q_proj + kv_proj + o_proj + absorb_q + scores + av + v_up
        # train/prefill: K/V expanded once per token (amortized)
        expand = 2 * m.kv_lora_rank * h * (m.qk_nope_head_dim + m.v_head_dim)
        sdpa = 4 * h * m.qk_head_dim * ctx
        return q_proj + kv_proj + o_proj + expand + sdpa
    proj = 2 * d * hd * (2 * h + 2 * kv)
    sdpa = 4 * h * hd * ctx
    return proj + sdpa


def _ssm_flops_per_token(cfg) -> float:
    s = cfg.ssm
    d = cfg.d_model
    di = s.expand * d
    nh = di // s.head_dim
    g, n, p = s.n_groups, s.d_state, s.head_dim
    conv_dim = di + 2 * g * n
    proj = 2 * d * (2 * di + 2 * g * n + nh) + 2 * di * d
    conv = 2 * s.conv_width * conv_dim
    ssd = 2 * s.chunk_size * (g * n + nh * p) + 4 * nh * p * n
    return proj + conv + ssd


def _ssm_decode_flops_per_token(cfg) -> float:
    s = cfg.ssm
    d = cfg.d_model
    di = s.expand * d
    nh = di // s.head_dim
    g, n, p = s.n_groups, s.d_state, s.head_dim
    proj = 2 * d * (2 * di + 2 * g * n + nh) + 2 * di * d
    return proj + 2 * s.conv_width * (di + 2 * g * n) + 6 * nh * p * n


def _rglru_flops_per_token(cfg) -> float:
    d = cfg.d_model
    w = cfg.rglru.lru_width or d
    return 4 * d * w + 4 * w * w + 2 * w * d + 10 * w


def _mlp_flops_per_token(d: int, ff: int) -> float:
    return 6 * d * ff


def forward_flops_per_token(cfg, ctx: float, *, decode: bool = False,
                            window: int = 0, mla_absorb: bool = True) -> float:
    """Global fwd flops for one token through all layers (no head)."""
    eff_ctx = min(ctx, window) if window else ctx
    total = 0.0
    if cfg.family == "ssm":
        per = (_ssm_decode_flops_per_token(cfg) if decode
               else _ssm_flops_per_token(cfg))
        return per * cfg.n_layers
    if cfg.family == "hybrid":
        pattern = list(cfg.rglru.block_pattern)
        n_rec = sum(k == "recurrent" for k in pattern)
        n_att = len(pattern) - n_rec
        groups = cfg.n_layers / len(pattern)
        att_ctx = min(ctx, cfg.rglru.attn_window)
        total += groups * n_rec * (_rglru_flops_per_token(cfg)
                                   + _mlp_flops_per_token(cfg.d_model, cfg.d_ff))
        total += groups * n_att * (_attn_flops_per_token(cfg, att_ctx)
                                   + _mlp_flops_per_token(cfg.d_model, cfg.d_ff))
        return total
    # attention stacks (dense / moe / audio / vlm)
    for layer in range(cfg.n_layers):
        total += _attn_flops_per_token(cfg, eff_ctx, decode=decode,
                                       mla_absorb=mla_absorb)
        if cfg.is_moe and layer >= cfg.moe.first_k_dense:
            m = cfg.moe
            total += 2 * cfg.d_model * m.n_routed_experts
            # dropless: each token's top-k assignments, of which the held
            # share lands here, plus the shared experts
            total += (m.top_k * m.held_count / m.n_routed_experts
                      + m.n_shared_experts) * \
                _mlp_flops_per_token(cfg.d_model, m.moe_d_ff)
        elif cfg.is_moe:
            total += _mlp_flops_per_token(cfg.d_model, m0_ff(cfg))
        else:
            total += _mlp_flops_per_token(cfg.d_model, cfg.d_ff)
    return total


def m0_ff(cfg) -> int:
    return cfg.moe.effective_dense_d_ff


def analytic_costs(cfg, shape, n_chips: int, mesh_shape: dict, *,
                   remat: str = "full", moment_bytes: int = 4,
                   window_override=None, flash: bool = True,
                   mla_absorb: bool = True) -> dict:
    """Per-device FLOPs and HBM-traffic estimates (documented closed forms).

    Memory-traffic model (per device, per step):
      weights:  N*pb/model_par read per fwd pass (FSDP gather lands in HBM
                once per layer, shared across the data-parallel extent);
                train adds grad writes (f32) + optimizer shard read/write.
      acts:     tokens_dev * d_model * L * c_act * 2B, c_act~12 (block-
                internal reads+writes, flash path); +score matrix traffic
                when the unfused sdpa path materializes (s<=flash threshold).
      decode:   weights read per token + KV-cache read/write per step.
    """

    B, S = shape.global_batch, shape.seq_len
    d = cfg.d_model
    L = cfg.n_layers
    V = cfg.vocab_size
    pb = 2                                     # bf16 params
    model_par = mesh_shape.get("model", 1)
    data_par = n_chips // max(model_par, 1)
    n_params = cfg.n_params()
    n_with_embed = n_params + V * d * (1 if cfg.tie_embeddings else 2)

    window = window_override or cfg.attn_window or 0
    if shape.mode in ("train", "prefill"):
        tokens_global = B * S
        tokens_dev = tokens_global / max(data_par, 1)
        ctx = S / 2                            # causal average
        fwd = forward_flops_per_token(cfg, ctx, window=window) + 2 * d * V
        mult = {"train": 4.0 if remat == "full" else 3.0,
                "prefill": 1.0}[shape.mode]
        if shape.mode == "prefill":
            fwd = forward_flops_per_token(cfg, ctx, window=window)  # head: last pos only
        flops_global = fwd * tokens_global * mult + (
            2 * d * V * B if shape.mode == "prefill" else 0)
        flops_dev = flops_global / n_chips

        w_read = n_with_embed * pb / max(model_par, 1)
        acts = tokens_dev * d * L * 12 * 2
        if not flash and S <= 4096:
            acts += tokens_dev * S * cfg.n_heads / max(model_par, 1) * 4
        if shape.mode == "train":
            passes = 3 if remat == "none" else 4
            opt_shard = n_with_embed / n_chips
            bytes_dev = (w_read * passes
                         + n_with_embed * 4 / n_chips * 2       # grad w+r (f32)
                         + opt_shard * (2 * moment_bytes * 2 + pb * 2)
                         + acts * (2 if remat == "none" else 1.3))
        else:
            bytes_dev = w_read + acts
    else:  # decode
        n_active = cfg.n_active_params()
        ctx = S
        eff_window = window if cfg.family in ("dense", "moe", "vlm") and \
            shape.name == "long_500k" else (window or 0)
        fwd = forward_flops_per_token(cfg, ctx, decode=True,
                                      window=eff_window,
                                      mla_absorb=mla_absorb) + 2 * d * V
        flops_global = fwd * B
        flops_dev = flops_global / n_chips

        w_read = (n_active + V * d) * pb / max(model_par, 1)
        # per-device KV traffic: each sequence's cache is read once
        if cfg.family == "ssm":
            s_ = cfg.ssm
            di = s_.expand * d
            cache_per_seq = (di // s_.head_dim) * s_.head_dim * s_.d_state * 4
        elif cfg.family == "hybrid":
            att_layers = L // 3
            cache_per_seq = (att_layers * min(S, cfg.rglru.attn_window)
                             * cfg.n_kv_heads * cfg.head_dim * 2 * 2)
            cache_per_seq += (L - att_layers) * (cfg.rglru.lru_width or d) * 4
        elif cfg.mla is not None:
            cache_per_seq = (min(S, eff_window or S)
                             * (cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim)
                             * 2 * L)
        else:
            cache_per_seq = (min(S, eff_window or S) * cfg.n_kv_heads
                             * cfg.head_dim * 2 * 2 * L)
        bytes_dev = w_read + B * cache_per_seq / n_chips

    return {
        "flops_per_dev": flops_dev,
        "bytes_per_dev": bytes_dev,
        "flops_global": flops_global,
        "tokens_global": (B * S if shape.mode != "decode" else B),
    }


def model_flops(cfg, shape, n_params: int, n_active: int) -> float:
    """6*N*D convention (D = tokens processed globally)."""
    n = n_active if cfg.is_moe else n_params
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens          # forward only
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch
