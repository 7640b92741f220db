"""deepseek-v2-lite [moe] — MLA, 64 routed experts top-6 + 2 shared, one
leading dense layer, YaRN rotary [arXiv:2405.04434;
hf:deepseek-ai/DeepSeek-V2-Lite config.json]."""

from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=1,               # one latent row per token, shared by heads
    d_ff=10944,                 # the dense first layer's FFN
    vocab=102400,
    head_dim=192,               # qk_nope_head_dim + qk_rope_head_dim
    n_experts=64,
    top_k=6,
    d_expert=1408,
    n_shared_experts=2,
    first_k_dense=1,
    norm_topk_prob=False,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10_000.0,
    rope_factor=40.0,
    rope_original_max_pos=4096,
    rope_beta_fast=32.0,
    rope_beta_slow=1.0,
    rope_mscale=0.707,
    rope_mscale_all_dim=0.707,
))

REDUCED = CONFIG.replace(
    name="deepseek-v2-lite-reduced", n_layers=3, d_model=64, n_heads=4,
    d_ff=128, vocab=256, head_dim=24, n_experts=8, top_k=2, d_expert=32,
    n_shared_experts=1, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rope_original_max_pos=64,
    moe_group=64, lop_block=32)
