"""Moonlight-16B-A3B [moe, MLA] — DeepSeek-V3 layout: 27 layers at
d_model 2048, the first dense (MLP 11264), the other 26 with 64 routed
experts of width 1408 (top-6, sigmoid scores, ``noaux_tc`` choice bias,
normalised gates x 2.446) and 2 shared experts; MLA with no query LoRA
(16 heads, kv_lora_rank 512, qk 128 + 64 rope, v 128); rope theta 50000,
vocab 163840, untied, RMSNorm eps 1e-5, context 8192.
[hf:moonshotai/Moonlight-16B-A3B config.json]

``EP8`` is one chip's share of an expert-parallel deployment: each MoE
layer's 64 experts over 8 chips (8 held here), the vocabulary sliced 8
ways, attention, shared experts and the dense layer replicated; of the
depth, the dense layer and 4 MoE layers (the rest lie on further pipeline
stages).  ``reduced()`` is the CPU tests' size.
"""
from repro.configs.base import ModelConfig

FULL = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    source="hf:moonshotai/Moonlight-16B-A3B",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=1408, vocab_size=163_840, rope_theta=50_000.0, norm_eps=1e-5,
    num_experts=64, experts_per_token=6, num_shared_experts=2,
    routed_scale=2.446, first_dense_layers=1, dense_d_ff=11_264,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, router_bias_std=0.1, act="silu")

EP8 = FULL.replace(num_layers=5, experts_held=8, vocab_size=20_480)


def reduced() -> ModelConfig:
    return FULL.replace(
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, d_ff=32,
        vocab_size=256, num_experts=8, experts_per_token=3,
        experts_held=4, dense_d_ff=96, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12)
