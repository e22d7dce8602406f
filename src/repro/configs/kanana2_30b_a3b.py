"""Kanana-2-30B-A3B — DeepSeek-V3 layout: MLA without q-LoRA, 128 routed
experts (top-6, sigmoid, bias-corrected selection) + 2 shared.

[huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601, config.json]
48L d_model=2048 32H (MLA: kv_lora_rank 512, qk_nope 128, qk_rope 64,
v_head 128, q_lora_rank null) moe_d_ff=768 vocab=128256; first layer dense
(d_ff=6144); routed_scaling_factor 2.448, norm_topk_prob, n_group 1;
rope_theta 1e6 with interleaved rotary pairs; RMSNorm eps 1e-6; untied head.
The router's correction bias selects experts and carries no gradient, and
no auxiliary loss is trained (``router_aux_coef`` 0).
"""

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="kanana2-30b-a3b",
    family="moe",
    n_layers=48,
    d_model=2048,
    n_heads=32,
    n_kv_heads=32,
    head_dim=128,           # v_head_dim; qk dims come from MLAConfig
    d_ff=768,               # routed-expert hidden dim
    vocab_size=128_256,
    mlp_activation="silu",
    rope_theta=1_000_000.0,
    rope_interleave=True,
    norm_eps=1e-6,
    moe=MoEConfig(
        n_routed_experts=128,
        top_k=6,
        n_shared_experts=2,
        moe_d_ff=768,
        first_k_dense=1,
        dense_d_ff=6144,
        router_aux_coef=0.0,
        routed_scaling=2.448,
        score_func="sigmoid",
    ),
    mla=MLAConfig(
        q_lora_rank=None,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
    citation="https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601",
)
