"""deepseek-moe-16b [moe]: 28L d_model=2048 16H (kv=16) vocab=102400.

Fine-grained MoE [arXiv:2401.06066]: 64 routed experts top-6 with
d_ff=1408 each, plus 2 shared experts (always-on), and a dense first
layer (d_ff=10944 per the HF checkpoint). Expert parallelism: experts
sharded over the `model` axis, dispatched with the paper-technique
all-to-all (PeerComm.alltoall). Full attention: the KV cache grows with
the context.
"""
from ..models.common import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-moe-16b", kind="moe", n_layers=28, d_model=2048,
    n_heads=16, n_kv_heads=16, d_ff=10944, vocab=102400,
    n_experts=64, top_k=6, n_shared_experts=2, moe_d_ff=1408,
    first_dense_layers=1, capacity_factor=1.25,
)

SMOKE = ModelConfig(
    name="deepseek-moe-smoke", kind="moe", n_layers=3, d_model=64,
    n_heads=4, n_kv_heads=4, d_ff=256, vocab=103,
    n_experts=8, top_k=2, n_shared_experts=1, moe_d_ff=32,
    first_dense_layers=1, capacity_factor=1.5,
)
