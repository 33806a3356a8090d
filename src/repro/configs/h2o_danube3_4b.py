"""h2o-danube-3-4b [dense]: 24L d_model=3840 32H (kv=8) d_ff=10240
vocab=32000 [arXiv:2401.16818 family]. SWA window 4096; head_dim=120.
"""
from ..models.common import ModelConfig

CONFIG = ModelConfig(
    name="h2o-danube-3-4b", kind="dense", n_layers=24, d_model=3840,
    n_heads=32, n_kv_heads=8, d_ff=10240, vocab=32000,
    window=4096,
)

SMOKE = ModelConfig(
    name="h2o-danube3-smoke", kind="dense", n_layers=2, d_model=96,
    n_heads=4, n_kv_heads=2, d_ff=160, vocab=103,
    window=32,
)
