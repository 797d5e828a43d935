"""DeepSeek-V2-Lite — MLA without a query LoRA, 64 routed experts top-6
(softmax, not renormalised) + 2 shared, one leading dense layer.

[hf:deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json] 27L d_model=2048
16H, kv_lora=512, qk_nope=128, qk_rope=64, v_head=128, q_lora null;
first_k_dense_replace=1 (dense SwiGLU 10944), expert width 1408,
n_shared_experts=2, routed_scaling_factor=1 (the gate weights as they
are), vocab=102400 (untied),
rms eps 1e-6, rope theta 1e4 with YaRN (factor 40, original 4096,
beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707).

Every routed expert is held here; an expert-parallel deployment gives
each chip its contiguous share through ``experts_held``.
"""
from .base import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=10944,
    vocab_size=102400, norm_eps=1e-6,
    attention="mla", q_lora_rank=0, kv_lora_rank=512,
    qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    yarn=Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
              beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    n_experts=64, experts_per_token=6, n_shared_experts=2, moe_d_ff=1408,
    n_dense_layers=1, norm_topk_prob=False,
    experts_held=(0, 64),
)
