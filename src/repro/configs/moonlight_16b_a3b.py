"""moonlight-16b-a3b [moe] — 27L d_model=2048, MLA 16H (kv rank 512, qk
128 + 64 rope, v 128), one dense layer of 11264 then 26 MoE layers of 64
experts of 1408, 6 a token by sigmoid score with a selection bias
(noaux_tc), 2 shared experts, routed scaling 2.446; vocab=163840, untied.
[hf:moonshotai/Moonlight-16B-A3B, model_type deepseek_v3]"""
from repro.configs.base import ArchConfig, reduced_from
from repro.models.common import LayerSpec, ModelConfig

DENSE = LayerSpec(mixer="mla", ffn="mlp")
MOE = LayerSpec(mixer="mla", ffn="moe")

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    arch_type="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,                # q and k: 128 without position + 64 rope
    d_ff=11264,                  # the leading dense layer
    vocab_size=163840,
    rope_theta=50_000.0,
    rms_eps=1e-5,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=64,
    experts_per_token=6,
    expert_d_ff=1408,
    num_shared_experts=2,
    router_scoring="sigmoid",
    routed_scaling=2.446,
    router_aux_coef=1e-4,        # DeepSeek-V3's sequence-wise alpha
    pattern=(DENSE,) + (MOE,) * 26,   # first_k_dense_replace 1
    tie_embeddings=False,
    citation="hf:moonshotai/Moonlight-16B-A3B",
)

ARCH = ArchConfig(
    arch_id="moonlight-16b-a3b",
    model=CONFIG,
    # the router keeps 8 outputs and holds the first 4 of them
    reduced=reduced_from(
        CONFIG, num_layers=2, pattern=(DENSE, MOE), head_dim=24,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, expert_d_ff=64, router_experts=8),
    sharding_mode="gossip-fsdp",
    fsdp_nodes=4,
    skip_shapes=("long_500k",),
    skip_reason="pure full-attention stack; no sub-quadratic variant in the "
                "source model card (DESIGN.md section 4)",
)
