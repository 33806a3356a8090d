"""deepseek-v2-lite [moe]: 27L d_model=2048 16H vocab=102400
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json].

Latent attention (MLA) without query compression: the cache holds one
512-wide latent and one 64-wide rotary key per position (576 values),
heads are 128 (no rotary) + 64 (rotary) wide for queries and keys and
128 for values; rotary positions are YaRN-scaled (factor 40 over 4096
original positions). Layer 0 is dense (d_ff 10944); the other 26 have 64
routed experts of width 1408, 6 per token with softmax scores that are
not renormalised, and 2 shared experts. Full attention: the latent
cache grows with the context. DeepSeek rotates interleaved pairs; here
the rotary halves are split (with seeded weights a fixed permutation of
the rotary columns).
"""
from ..models.common import ModelConfig, YaRN

CONFIG = ModelConfig(
    name="deepseek-v2-lite", kind="moe", n_layers=27, d_model=2048,
    n_heads=16, n_kv_heads=16, d_ff=10944, vocab=102400,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    rope_yarn=YaRN(factor=40.0, original_max=4096, beta_fast=32.0,
                   beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    n_experts=64, top_k=6, n_shared_experts=2, moe_d_ff=1408,
    first_dense_layers=1, norm_topk_prob=False, norm_eps=1e-6,
)

SMOKE = ModelConfig(
    name="deepseek-v2-lite-smoke", kind="moe", n_layers=3, d_model=64,
    n_heads=4, n_kv_heads=4, d_ff=256, vocab=103,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16,
    rope_yarn=YaRN(factor=40.0, original_max=4096, beta_fast=32.0,
                   beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    n_experts=8, top_k=2, n_shared_experts=1, moe_d_ff=32,
    first_dense_layers=1, norm_topk_prob=False, norm_eps=1e-6,
)
