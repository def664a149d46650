"""Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B; DeepSeek-V3 block]
— multi-head latent attention (DeepSeek-V2 MLA, no q-LoRA), one leading
dense layer, then 64 fine-grained routed experts (6 a token, sigmoid
scores with a selection-only correction bias, renormalised and scaled
by 2.446) beside 2 shared experts."""
from repro.configs.base import ModelConfig, MoEConfig, MOE

FULL = ModelConfig(
    name="moonlight-16b-a3b",
    family=MOE,
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    rope_theta=50000.0,
    norm_eps=1e-5,
    act="silu",
    first_k_dense=1,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    moe=MoEConfig(n_experts=64, top_k=6, n_routed=64, d_expert=1408,
                  n_shared=2, routed_scale=2.446, aux_loss_weight=0.0),
    moe_impl="dropless",
)

# same shape at a toy size: one share of 4 experts out of 32 routed
SMOKE = ModelConfig(
    name="moonlight-16b-a3b-smoke",
    family=MOE,
    n_layers=3,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=128,
    vocab_size=256,
    rope_theta=50000.0,
    norm_eps=1e-5,
    act="silu",
    first_k_dense=1,
    kv_lora_rank=32,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    moe=MoEConfig(n_experts=4, top_k=6, n_routed=32, d_expert=32,
                  n_shared=2, routed_scale=2.446, aux_loss_weight=0.0),
    moe_impl="dropless",
)
