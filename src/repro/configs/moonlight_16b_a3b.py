"""Moonlight-16B-A3B — deepseek_v3 MoE with latent attention
[hf:moonshotai/Moonlight-16B-A3B config.json].

27L d_model=2048 16H, MLA (q_lora_rank none, kv_lora_rank 512, qk 128
nope + 64 rope, v 128), rope_theta 5e4, RMS norm eps 1e-5; layer 0 dense
(FFN 11264), layers 1-26 MoE: 64 routed experts of width 1408, top-6 by
sigmoid score plus a correction bias (noaux_tc, one group), weights
renormalised and scaled by 2.446, plus 2 shared experts; vocab 163840,
untied head.

``config()`` is one chip's share of 8-way expert parallelism: this chip
is expert rank 0 and holds experts 0-7 of each layer's 64 (``n_experts``
8 of ``router_experts`` 64), and every other weight; depth and
vocabulary are whole.
"""

from repro.configs.base import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        arch_id="moonlight-16b-a3b", family="moe",
        n_layers=27, d_model=2048, n_heads=16, kv_heads=16,
        d_ff=1408, vocab=163840, norm="rmsnorm", norm_eps=1e-5,
        rope_theta=50000.0, causal=True, activation="silu",
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128,
        n_experts=8, router_experts=64, expert_offset=0, top_k=6,
        n_shared_experts=2, routed_scaling_factor=2.446,
        first_dense_layers=1, dense_d_ff=11264,
        scan_layers=True,
    )


def smoke() -> ModelConfig:
    """1 dense + 2 MoE layers of width 64; 4 of 16 experts held, top-3."""
    return config().replace(
        n_layers=3, d_model=64, n_heads=4, kv_heads=4, d_ff=32,
        vocab=128, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, n_experts=4, router_experts=16, top_k=3,
        dense_d_ff=128, compute_dtype="float32", remat="none")
