"""Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B; DeepSeek-V3 block].

27L d_model=2048 16H vocab=163840. Multi-head Latent Attention with no
query compression (q_lora_rank null: a direct query projection),
kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
v_head_dim=128, rope_theta=50000, no RoPE scaling. Layer 0 has a dense
SwiGLU MLP of width 11264 (first_k_dense_replace=1); layers 1-26 have 64
routed experts of width 1408, top-6, and 2 shared experts (one SwiGLU of
width 2816). Routing: sigmoid scores, top-6 chosen on score plus a
selection bias (noaux_tc, n_group=1), weights the unbiased scores of the
six over their sum, times routed_scaling_factor 2.446.

Deployment: eight chips share each MoE layer by expert parallelism, chip
c holding routed experts 8c..8c+7; attention stays data-parallel (the
DeepSeek-V3/R1 inference design), so every head, the dense layer, the
shared experts, the router and the whole vocabulary are on every chip.
This is chip c = 0. On one chip the layer runs without its exchange: the
tokens routed to the other 56 experts add nothing here.

Departures from the published model: RoPE pairs rotate-half where the
published code de-interleaves the rope columns first (a fixed permutation
of those columns); weights, the selection bias included, are whatever the
caller loads.
"""
from repro.configs import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="mla_moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab=163840,
    head_dim=128,
    rope_theta=50000.0,
    norm_eps=1e-5,
    n_experts=64,
    top_k=6,
    d_ff_expert=1408,
    n_shared_experts=2,
    first_k_dense=1,
    routed_scaling=2.446,
    experts_held=8,
    expert_offset=0,
    q_lora_rank=0,
    kv_lora_rank=512,
    rope_head_dim=64,
    nope_head_dim=128,
    v_head_dim=128,
)
