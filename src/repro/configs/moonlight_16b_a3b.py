"""moonlight-16b-a3b [moe]: DeepSeek-V3's block (without MTP). 27L
d_model=2048 16H, latent attention (MLA: kv_lora_rank 512, qk 128 + 64
rotary, v 128, q_lora_rank null), layer 0 a dense SwiGLU of 11264, layers
1-26 MoE: 64 routed experts of 1408 top-6 by sigmoid scores with a
correction bias (noaux_tc, one group, normalised, x 2.446) and 2 shared
experts; vocab 163840, untied head, rope_theta 50000.
[hf:moonshotai/Moonlight-16B-A3B; config.json]

``CONFIG`` holds all 64 experts; an expert-parallel share is
``CONFIG.replace(experts_held=16, experts_first=16 * rank)``."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=1,  # one latent, shared by every head
    d_ff=11264,  # the leading dense layer's MLP
    vocab_size=163840,
    activation="swiglu",
    rope_theta=50000.0,
    norm_eps=1e-5,
    num_experts=64,
    experts_per_token=6,
    router="sigmoid_bias",
    routed_scale=2.446,
    moe_d_ff=1408,
    num_shared_experts=2,
    first_dense_layers=1,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    fsdp=False,
)

REDUCED = ModelConfig(
    name="moonlight-reduced",
    family="moe",
    num_layers=3,
    d_model=64,
    num_heads=4,
    num_kv_heads=1,
    d_ff=96,
    vocab_size=512,
    activation="swiglu",
    num_experts=16,
    experts_per_token=6,
    router="sigmoid_bias",
    routed_scale=2.446,
    moe_d_ff=32,
    num_shared_experts=2,
    first_dense_layers=1,
    kv_lora_rank=32,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    fsdp=False,
    dtype="float32",
)
