"""deepseek-v2-lite-16b — MLA (kv_lora=512, no q LoRA) with YaRN RoPE, a
dense first layer, then MoE with 64 routed experts (top-6, softmax gate,
no renormalization) and 2 shared experts [arXiv:2405.04434; HF
deepseek-ai/DeepSeek-V2-Lite config.json]: 15,706,484,224 params.

`chip_share` is one chip's part of an expert-parallel deployment in
which `chips` chips share every layer: the dense layer and the first
`moe_layers` MoE layers (the rest would be further pipeline stages),
the routed experts whose ids are this chip's (`held`; the router keeps
all 64 outputs and its 6 experts a token, and no pair bound for a held
expert is dropped), and a `1/chips` slice of the vocabulary. Every width
is as published. The uncut `CONFIG` holds all 64 experts at a fixed
capacity, which the vmapped round and the mesh-sharded steps run.
"""
import dataclasses

from repro.models.config import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    arch_type="moe",
    num_layers=27,
    first_dense_layers=1,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,
    vocab_size=102400,
    moe_pattern="all",
    tie_embeddings=False,
    moe=MoEConfig(num_experts=64, top_k=6, num_shared=2, d_ff_expert=1408,
                  norm_topk_prob=False, routed_scaling_factor=1.0,
                  aux_loss="seq", aux_loss_weight=0.001,
                  router_precision="highest"),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, rope_head_dim=64,
                  nope_head_dim=128, v_head_dim=128),
    rope_theta=10000.0,
    yarn=YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                    beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                    mscale_all_dim=0.707),
    source="arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite "
           "config.json (27L d2048 16H, MLA 512, dense layer 0, 64e top6 "
           "+ 2 shared, YaRN x40)",
)


def chip_share(chips: int = 8, moe_layers: int = 4, chip: int = 0,
               dtype: str = "float32") -> ModelConfig:
    """Chip `chip`'s share of a `chips`-way expert-parallel deployment,
    `moe_layers` MoE layers deep."""
    m = CONFIG.moe
    per = m.num_experts // chips
    return dataclasses.replace(
        CONFIG,
        name=f"{CONFIG.name}-share{chip}of{chips}",
        num_layers=CONFIG.first_dense_layers + moe_layers,
        vocab_size=CONFIG.vocab_size // chips,
        moe=dataclasses.replace(m, held=tuple(range(chip * per,
                                                    (chip + 1) * per))),
        dtype=dtype,
    )
