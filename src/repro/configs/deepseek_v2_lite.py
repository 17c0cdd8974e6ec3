"""deepseek-v2-lite [moe] — MLA (kv_lora=512, no q-LoRA) with YaRN rope,
2 shared + 64 routed experts top-6, first layer dense
[hf:deepseek-ai/DeepSeek-V2-Lite config.json]."""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    attn_impl="mla",
    q_lora_rank=0,  # published q_lora_rank: null — wq is [d, h, 192]
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    d_ff=10944,  # the single leading dense layer
    vocab_size=102400,
    num_experts=64,
    experts_per_token=6,
    moe_d_ff=1408,
    num_shared_experts=2,
    first_k_dense=1,
    route_norm=False,  # norm_topk_prob: false; routed_scaling_factor 1
    norm_eps=1e-6,
)

SMOKE = ModelConfig(
    name="deepseek-v2-lite-smoke",
    family="moe",
    num_layers=3,
    d_model=64,
    num_heads=4,
    num_kv_heads=4,
    attn_impl="mla",
    q_lora_rank=0,
    kv_lora_rank=32,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    rope_theta=10000.0,
    yarn_factor=40.0,
    yarn_original_max_pos=64,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    d_ff=128,
    vocab_size=256,
    num_experts=8,
    experts_per_token=3,
    moe_d_ff=32,
    num_shared_experts=2,
    first_k_dense=1,
    route_norm=False,
    norm_eps=1e-6,
    remat=False,
)
