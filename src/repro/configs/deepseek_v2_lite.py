"""deepseek-v2-lite [moe] — 27L d_model=2048 16H, multi-head latent attention
(kv_lora_rank 512, no q_lora; qk 128 nope + 64 rope, v 128) with YaRN rope
(factor 40 over 4,096), layer 0 a dense SwiGLU of 10,944, then 26 MoE
layers: 64 routed experts of 1,408, top-6 softmax without renormalization,
2 shared experts; vocab 102400, untied head
[hf:deepseek-ai/DeepSeek-V2-Lite config.json].

The registered config holds every routed expert through the expert-share
layer; a deployment that divides them over devices sets ``experts_held`` /
``first_expert`` to one device's share."""

from repro.models.config import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, vocab=102400, mlp_type="swiglu",
    kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    rope_theta=10_000.0,
    rope_yarn=Yarn(factor=40.0, original_max=4096, beta_fast=32.0,
                   beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    n_experts=64, top_k=6, norm_topk=False, n_shared_experts=2,
    experts_held=64,
    first_dense_layers=1, dense_d_ff=10944,
)
