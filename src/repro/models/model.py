"""LanguageModel: config-driven decoder/encoder over the block stacks.

Covers every assigned family:
  * text decoders (dense / MoE / SSM / hybrid) — causal LM
  * audio encoder (HuBERT) — bidirectional masked prediction
  * VLM — stubbed patch embeddings prepended to the token stream

Full configs are exercised shape-only via the dry-run; reduced configs run
on CPU in the smoke tests.  All stacks scan over layers so the HLO (and
512-device compile time) stays small.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.blocks import (
    block_apply,
    block_schema,
    init_block_cache,
    stack_layout,
)
from repro.models.layers import rmsnorm, rmsnorm_schema
from repro.models.moe import zero_aux
from repro.sharding.logical import (
    ParamSpec,
    Rules,
    constrain,
    init_from_schema,
    schema_shapes,
    specs_from_schema,
    stack_schema,
)


class LanguageModel:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.layout = stack_layout(cfg)

    # ------------------------------------------------------------------ schema
    def schema(self) -> dict:
        cfg = self.cfg
        sch: dict = {
            "embed": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                               init="embed", scale=0.02),
        }
        if cfg.frontend is not None:
            sch["frontend_proj"] = ParamSpec(
                (cfg.frontend.embed_dim, cfg.d_model), ("frontend_in", "embed"))
            if cfg.family == "audio":
                sch["mask_embed"] = ParamSpec((cfg.d_model,), ("embed",), init="zeros")
        segs = {}
        for si, (mode, kinds, repeat) in enumerate(self.layout):
            if mode == "scan":
                group = {f"b{i}": block_schema(cfg, k) for i, k in enumerate(kinds)}
                segs[f"seg{si}"] = stack_schema(group, repeat)
            else:
                segs[f"seg{si}"] = {f"b{i}": block_schema(cfg, k)
                                    for i, k in enumerate(kinds)}
        sch["segments"] = segs
        sch["final_norm"] = rmsnorm_schema(cfg.d_model)
        if not cfg.tie_embeddings:
            sch["head"] = ParamSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                                    scale=0.02)
        if cfg.mtp_depth:
            sch["mtp"] = {
                "proj": ParamSpec((2 * cfg.d_model, cfg.d_model), ("mtp_in", "embed")),
                "norm_h": rmsnorm_schema(cfg.d_model),
                "norm_e": rmsnorm_schema(cfg.d_model),
                "block": block_schema(
                    cfg, "attn_dense" if cfg.is_moe else "attn_mlp"),
                "final_norm": rmsnorm_schema(cfg.d_model),
            }
        return sch

    def init(self, key, dtype=None):
        dtype = dtype or jnp.dtype(self.cfg.dtype)
        return init_from_schema(self.schema(), key, dtype)

    def param_shapes(self):
        return schema_shapes(self.schema(), jnp.dtype(self.cfg.dtype))

    def param_specs(self, rules: Rules):
        return specs_from_schema(self.schema(), rules)

    # ------------------------------------------------------------ embeddings
    def _embed_inputs(self, params, tokens=None, embeds=None, mask=None,
                      rules=None):
        cfg = self.cfg
        parts = []
        if embeds is not None:
            x = jnp.einsum("bsf,fd->bsd", embeds.astype(params["frontend_proj"].dtype),
                           params["frontend_proj"])
            if cfg.family == "audio" and mask is not None:
                x = jnp.where(mask[..., None],
                              params["mask_embed"].astype(x.dtype), x)
            parts.append(x)
        if tokens is not None:
            parts.append(jnp.take(params["embed"], tokens, axis=0))
        x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        return constrain(x, ("batch", "seq", "act_embed"), rules)

    # ------------------------------------------------------------- forward
    def forward(self, params, *, tokens=None, embeds=None, mask=None,
                rules: Rules | None = None, window_override=None,
                mla_absorb: bool = True):
        """Full-sequence forward.  Returns (logits, aux): ``aux`` holds
        ``moe_loss``, the final ``hidden`` states and, in an MoE model,
        ``moe_load``, the (token, k) assignments routed to each held expert
        summed over the layers."""
        cfg = self.cfg
        h = self._embed_inputs(params, tokens, embeds, mask, rules)
        s = h.shape[1]
        positions = jnp.arange(s)
        aux = zero_aux(cfg)

        for si, (mode, kinds, _repeat) in enumerate(self.layout):
            seg_params = params["segments"][f"seg{si}"]
            if mode == "scan":
                def body(carry, xs, kinds=kinds):
                    hh, acc = carry
                    for i, kind in enumerate(kinds):
                        hh, _, a = block_apply(
                            cfg, kind, xs[f"b{i}"], hh, positions=positions,
                            rules=rules, window_override=window_override,
                            mla_absorb=mla_absorb)
                        acc = jax.tree.map(jnp.add, acc, a)
                    return (hh, acc), None

                if cfg.remat == "full":
                    body = jax.checkpoint(body, prevent_cse=False)
                elif cfg.remat == "dots_saveable":
                    body = jax.checkpoint(
                        body, policy=jax.checkpoint_policies.dots_saveable,
                        prevent_cse=False)
                (h, aux), _ = jax.lax.scan(body, (h, aux), seg_params)
            else:
                for i, kind in enumerate(kinds):
                    h, _, a = block_apply(
                        cfg, kind, seg_params[f"b{i}"], h, positions=positions,
                        rules=rules, window_override=window_override,
                        mla_absorb=mla_absorb)
                    aux = jax.tree.map(jnp.add, aux, a)

        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
        logits = self.head(params, h, rules)
        out = {"moe_loss": aux["loss"], "hidden": h}
        if "load" in aux:
            out["moe_load"] = aux["load"]
        return logits, out

    def head(self, params, h, rules=None):
        """Logits of final hidden states (after ``final_norm``)."""
        if self.cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", h, params["embed"])
        else:
            logits = jnp.einsum("bsd,dv->bsv", h, params["head"])
        return constrain(logits, ("batch", "seq", "vocab"), rules)

    # ------------------------------------------------------------- MTP head
    def mtp_logits(self, params, hidden, next_tokens, rules=None):
        """DeepSeek-V3 multi-token prediction: one extra block over
        [norm(h_i); norm(emb(t_{i+1}))] predicting t_{i+2}."""
        cfg = self.cfg
        p = params["mtp"]
        e = jnp.take(params["embed"], next_tokens, axis=0)
        x = jnp.concatenate([rmsnorm(p["norm_h"], hidden, cfg.norm_eps),
                             rmsnorm(p["norm_e"], e, cfg.norm_eps)], axis=-1)
        h = jnp.einsum("bse,ed->bsd", x, p["proj"])
        positions = jnp.arange(h.shape[1])
        kind = "attn_dense" if cfg.is_moe else "attn_mlp"
        h, _, _ = block_apply(cfg, kind, p["block"], h, positions=positions,
                              rules=rules)
        h = rmsnorm(p["final_norm"], h, cfg.norm_eps)
        return self.head(params, h, rules)

    # ------------------------------------------------------------- decode
    def init_caches(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        caches = {}
        for si, (mode, kinds, repeat) in enumerate(self.layout):
            if mode == "scan":
                group = {f"b{i}": init_block_cache(self.cfg, k, batch, max_len, dtype)
                         for i, k in enumerate(kinds)}
                caches[f"seg{si}"] = jax.tree.map(
                    lambda x, repeat=repeat: jnp.broadcast_to(x[None], (repeat,) + x.shape),
                    group)
            else:
                caches[f"seg{si}"] = {
                    f"b{i}": init_block_cache(self.cfg, k, batch, max_len, dtype)
                    for i, k in enumerate(kinds)}
        return caches

    def decode_step(self, params, caches, tokens, pos, *, rules=None,
                    window_override=None, mla_absorb: bool = True):
        """One autoregressive step.  tokens: (b, 1); pos: scalar int32 index
        of the slot being written, or a (b,) vector for continuous batching
        (each sequence at its own offset).  Returns (logits, new_caches)."""
        cfg = self.cfg
        h = jnp.take(params["embed"], tokens, axis=0)
        h = constrain(h, ("batch", "seq", "act_embed"), rules)
        if getattr(pos, "ndim", 0) == 1:
            positions = pos[:, None] + jnp.arange(tokens.shape[1])   # (b, s)
        else:
            positions = pos + jnp.arange(tokens.shape[1])            # (s,)
        new_caches = {}

        for si, (mode, kinds, _repeat) in enumerate(self.layout):
            seg_params = params["segments"][f"seg{si}"]
            seg_cache = caches[f"seg{si}"]
            if mode == "scan":
                def body(hh, xs, kinds=kinds):
                    layer_p, layer_c = xs
                    new_c = {}
                    for i, kind in enumerate(kinds):
                        hh, nc, _ = block_apply(
                            cfg, kind, layer_p[f"b{i}"], hh, positions=positions,
                            rules=rules, cache=layer_c[f"b{i}"], cache_pos=pos,
                            window_override=window_override,
                            mla_absorb=mla_absorb)
                        new_c[f"b{i}"] = nc
                    return hh, new_c

                h, new_seg = jax.lax.scan(body, h, (seg_params, seg_cache))
            else:
                new_seg = {}
                for i, kind in enumerate(kinds):
                    h, nc, _ = block_apply(
                        cfg, kind, seg_params[f"b{i}"], h, positions=positions,
                        rules=rules, cache=seg_cache[f"b{i}"], cache_pos=pos,
                        window_override=window_override, mla_absorb=mla_absorb)
                    new_seg[f"b{i}"] = nc
            new_caches[f"seg{si}"] = new_seg

        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
        return self.head(params, h, rules), new_caches


def build_model(cfg: ModelConfig) -> LanguageModel:
    return LanguageModel(cfg)
