"""deepseek-v2-lite [moe] — multi-head latent attention over 16 heads
(latent 512, rotary 64 with YaRN x40), one dense first layer of width
10944, then 64 routed experts of width 1408, top-6, plus 2 shared
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json].
Softmax router with raw gates (`norm_topk_prob` false, greedy top-k,
routed scaling 1).  The published checkpoint stores the rotary columns
interleaved; with seeded weights that is a fixed permutation, so the
program rotates halves."""
import dataclasses
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YaRN

CONFIG = ModelConfig(
    arch_id="deepseek-v2-lite", family="moe", source="arXiv:2405.04434",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=10944,
    vocab_size=102400, block_pattern=("mla",), mlp_act="swiglu",
    norm_head=False, rope_theta=10_000.0,
    rope_scaling=YaRN(factor=40.0, original_max_position_embeddings=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, expert_d_ff=1408,
                  n_shared_experts=2, first_dense_layers=1),
)


def smoke_config() -> ModelConfig:
    """Every part of the layer at a small size: the dense first layer and
    two latent-attention MoE layers, and a YaRN range that starts at
    position 64, so a 256-token test crosses into it."""
    return dataclasses.replace(
        CONFIG, n_layers=3, d_model=256, n_heads=4, n_kv_heads=4, d_ff=512,
        vocab_size=512,
        rope_scaling=dataclasses.replace(
            CONFIG.rope_scaling, original_max_position_embeddings=64),
        mla=MLAConfig(kv_lora_rank=64, qk_nope_head_dim=32,
                      qk_rope_head_dim=16, v_head_dim=32),
        moe=MoEConfig(n_experts=4, top_k=2, expert_d_ff=128,
                      n_shared_experts=1, router_warmup_steps=4,
                      first_dense_layers=1))
