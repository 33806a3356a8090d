"""stablelm-3b [dense]: 32L d_model=2560 32H (kv=32) d_ff=6912 vocab=50304.

StableLM-family dense transformer [hf:stabilityai/stablelm-2-1_6b style]:
partial rotary (rope_pct=0.25). Full attention: the KV cache grows with
the context.
"""
from ..models.common import ModelConfig

CONFIG = ModelConfig(
    name="stablelm-3b", kind="dense", n_layers=32, d_model=2560,
    n_heads=32, n_kv_heads=32, d_ff=6912, vocab=50304,
    rope_pct=0.25,
)

SMOKE = ModelConfig(
    name="stablelm-smoke", kind="dense", n_layers=2, d_model=64,
    n_heads=4, n_kv_heads=4, d_ff=128, vocab=103, rope_pct=0.25,
)
