"""qwen3-4b [dense]: 36L d_model=2560 32H (kv=8) d_ff=9728 vocab=151936
[hf:Qwen/Qwen3-8B family]. Per-head QK-RMSNorm; explicit head_dim=128
(> d_model/n_heads). Full attention: the KV cache grows with the context.
"""
from ..models.common import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-4b", kind="dense", n_layers=36, d_model=2560,
    n_heads=32, n_kv_heads=8, d_ff=9728, vocab=151936,
    head_dim=128, qk_norm=True, rope_theta=1_000_000.0,
)

SMOKE = ModelConfig(
    name="qwen3-smoke", kind="dense", n_layers=2, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=128, vocab=103,
    head_dim=32, qk_norm=True,
)
