"""zamba2-2.7b [hybrid]: 54L d_model=2560, Mamba2 backbone + one shared
attention+MLP block applied every 6 layers [arXiv:2411.15242].

Shared block: 32H MHA (kv=32), d_ff=10240 MLP. SSD: state N=64, head dim
P=64 (=> 80 SSD heads at expand=2). SSM: O(1) state; the shared
attention keeps one KV cache per group application.
Simplification vs. HF checkpoint (DESIGN.md): a single shared block (the
checkpoint alternates two) and no embedding concat at shared-block entry.
"""
from ..models.common import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-2.7b", kind="hybrid", n_layers=54, d_model=2560,
    n_heads=32, n_kv_heads=32, d_ff=10240, vocab=32000,
    ssm_state=64, ssm_head_dim=64, ssm_expand=2, ssm_chunk=128,
    attn_every=6,
)

SMOKE = ModelConfig(
    name="zamba2-smoke", kind="hybrid", n_layers=4, d_model=64,
    n_heads=4, n_kv_heads=4, d_ff=128, vocab=103,
    ssm_state=16, ssm_head_dim=16, ssm_expand=2, ssm_chunk=16,
    attn_every=2,
)
