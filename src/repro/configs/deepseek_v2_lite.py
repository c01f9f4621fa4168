"""DeepSeek-V2-Lite — multi-head latent attention (no q compression), one
leading dense layer, then MoE layers of 64 routed experts (top-6, softmax
scores, weights not renormalised) and 2 shared experts; YaRN rotary.
[hf:deepseek-ai/DeepSeek-V2-Lite, config.json]

Not in ``configs.ARCHS``: that table drives the dry-run, training and
decode paths, and this family implements only the forward pass a scorer
needs (``models/deepseek.py``).
"""
from repro.configs.base import ModelConfig, YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="deepseek",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,               # qk_nope_head_dim + qk_rope_head_dim
    d_ff=10944,                 # the leading dense layer's SwiGLU
    vocab_size=102400,
    num_experts=64,
    num_experts_per_tok=6,
    moe_d_ff=1408,
    num_shared_experts=2,
    first_dense_layers=1,
    router_renormalize=False,   # norm_topk_prob: false; routed_scaling_factor 1
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10_000.0,
    rope_scaling=YarnScaling(factor=40.0, original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    norm_eps=1e-6,
    tie_embeddings=False,
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json",
)
