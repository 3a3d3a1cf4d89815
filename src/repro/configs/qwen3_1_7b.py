"""qwen3-1.7b [dense] — 28L d_model=2048 16H (GQA kv=8) d_ff=6144
vocab=151936; qk_norm. [hf:Qwen/Qwen3-8B family card]"""
import dataclasses

from repro.configs.base import ArchConfig, ChipShare, reduced_from
from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-1.7b",
    arch_type="dense",
    num_layers=28,
    d_model=2048,
    num_heads=16,
    num_kv_heads=8,
    head_dim=128,
    d_ff=6144,
    vocab_size=151936,
    qk_norm=True,
    rope_theta=1_000_000.0,
    tie_embeddings=True,
    citation="hf:Qwen/Qwen3-8B",
)

# One v5e chip (16 GiB) holds two DFL nodes of 8 layers each: per node
# 714 M bf16 parameters (1.43 GB), so two nodes' parameters, gradients,
# gossip mixing copy and CHOCO estimates come to ~11.4 GB before
# activations. The whole 28-layer model (3.44 GB per node) does not fit
# twice with its gradients.
CHIP_SHARE = ChipShare(
    model=dataclasses.replace(CONFIG, name="qwen3-1.7b-chip", num_layers=8),
    source="Qwen/Qwen3-1.7B config.json (Hugging Face): 28 layers, hidden "
           "2048, 16 q / 8 kv heads of 128, intermediate 6144, vocab "
           "151936, tied embeddings, rope theta 1e6",
    reduced=("num_layers",),
    deployment="each DFL node's 28 layers run as a pipeline over 4 chips "
               "(8/8/6/6 layers); this share is one 8-layer stage plus the "
               "tied embedding and head, the input and output ends of the "
               "pipeline",
)

ARCH = ArchConfig(
    arch_id="qwen3-1.7b",
    model=CONFIG,
    reduced=reduced_from(CONFIG),
    chip_share=CHIP_SHARE,
    sharding_mode="gossip-dp",
    skip_shapes=("long_500k",),
    skip_reason="pure full-attention stack; no sub-quadratic variant in the "
                "source model card (DESIGN.md section 4)",
)
