"""Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B, config.json,
model_type deepseek_v3]: multi-head latent attention (q_lora_rank null,
kv_lora_rank 512, 128 + 64 rope query/key widths, v 128), layer 0 dense
(SwiGLU 11,264), layers 1-26 MoE (64 routed experts of 1,408, top-6,
sigmoid scores with a selection bias, normalised weights scaled by 2.446,
2 shared experts).

``experts_held`` is the share one chip holds when eight chips divide each
MoE layer by expert parallelism: routed experts 0-7 of 64.  Attention,
shared experts, the dense layer, embedding and head are whole on every
chip.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    source="hf:moonshotai/Moonlight-16B-A3B",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,              # the dense first layer
    vocab=163840,
    n_experts=64,
    moe_top_k=6,
    moe_router="sigmoid",
    moe_d_ff=1408,
    n_shared_experts=2,
    routed_scaling=2.446,
    experts_held=(0, 8),
    first_k_dense=1,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    rope_theta=50000.0,
    norm_eps=1e-5,
)


def smoke() -> ModelConfig:
    """The same shape, small: 1 dense + 2 MoE layers, latent 32, rope 16,
    16 routed experts of which 4 are held, top-4, 1 shared expert."""
    return ModelConfig(
        name="moonshot-v1-16b-a3b/smoke", family="moe",
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128, vocab=256,
        n_experts=16, moe_top_k=4, moe_router="sigmoid", moe_d_ff=32,
        n_shared_experts=1, routed_scaling=2.446, experts_held=(0, 4),
        first_k_dense=1, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=16,
        v_head_dim=16, rope_theta=50000.0,
    )
