"""h2o-danube-1.8b [dense]: 24L d_model=2560 32H (kv=8) d_ff=6912
vocab=32000 [arXiv:2401.16818]. Llama+Mistral mix with sliding-window
attention (window 4096) => KV bounded at any context.
"""
from ..models.common import ModelConfig

CONFIG = ModelConfig(
    name="h2o-danube-1.8b", kind="dense", n_layers=24, d_model=2560,
    n_heads=32, n_kv_heads=8, d_ff=6912, vocab=32000,
    window=4096,
)

SMOKE = ModelConfig(
    name="h2o-danube-smoke", kind="dense", n_layers=2, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=128, vocab=103,
    window=32,
)
