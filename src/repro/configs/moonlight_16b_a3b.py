"""Moonlight-16B-A3B [moe] — hf:moonshotai/Moonlight-16B-A3B (deepseek_v3).

27 layers at hidden 2,048: multi-head latent attention (16 heads, kv
latent 512, q/k 128 + 64 RoPE, v 128, q one plain projection); layer 0 a
dense SwiGLU of 11,264; layers 1-26 MoE with 64 routed experts of 1,408,
6 per token chosen by sigmoid score plus a selection bias (``noaux_tc``,
one group), weights renormalised and scaled by 2.446, and 2 shared experts
(one SwiGLU of 2,816).  Built for training only (no decode cache)."""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab=163840,
    rope_theta=5e4,
    norm_eps=1e-5,
    attn_kind="mla",
    kv_lora_rank=512,
    q_lora_rank=0,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64,
    experts_per_tok=6,
    first_dense_layers=1,
    dense_d_ff=11264,
    shared_d_ff=2816,
    router_scoring="sigmoid",
    routed_scaling=2.446,
    norm_topk=True,
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B",
    param_dtype="bfloat16", compute_dtype="bfloat16",
)
