"""xlstm-125m [ssm]: 12L d_model=768 4H vocab=50304 [arXiv:2405.04517].

mLSTM (matrix-memory, chunkwise-parallel) blocks with one sLSTM
(scalar-memory, sequential) block every 8 layers; no FFN (d_ff=0) --
mixing capacity lives in the blocks' up/down projections (proj_factor 2).
Recurrent state => memory bounded at any context. TP note (DESIGN.md):
4 heads < 16-way model axis, mixers are replicated over `model` (FSDP
over `data` only).
"""
from ..models.common import ModelConfig

CONFIG = ModelConfig(
    name="xlstm-125m", kind="xlstm", n_layers=12, d_model=768,
    n_heads=4, n_kv_heads=4, d_ff=0, vocab=50304,
    slstm_every=8, proj_factor=2.0, ssm_chunk=64,
)

SMOKE = ModelConfig(
    name="xlstm-smoke", kind="xlstm", n_layers=4, d_model=64,
    n_heads=4, n_kv_heads=4, d_ff=0, vocab=103,
    slstm_every=2, proj_factor=2.0, ssm_chunk=16,
)
