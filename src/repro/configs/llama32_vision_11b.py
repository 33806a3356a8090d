"""llama-3.2-vision-11b [vlm]: 40L d_model=4096 32H (kv=8) d_ff=14336
vocab=128256 [hf:meta-llama/Llama-3.2-11B-Vision].

Backbone only per assignment: the vision tower is a stub --
the batch's ``image_emb`` holds precomputed (B, 1601, 1280) patch
embeddings, projected by a learned (1280 -> 4096) matrix. Every 5th layer
is a tanh-gated cross-attention block (8 groups of 4 self + 1 cross =
40). Full attention: the KV cache grows with the context.
"""
from ..models.common import ModelConfig

CONFIG = ModelConfig(
    name="llama-3.2-vision-11b", kind="dense", n_layers=40, d_model=4096,
    n_heads=32, n_kv_heads=8, d_ff=14336, vocab=128256,
    cross_attn_every=5, n_image_tokens=1601, vision_d=1280,
    rope_theta=500_000.0,
)

SMOKE = ModelConfig(
    name="llama-vision-smoke", kind="dense", n_layers=4, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=128, vocab=103,
    cross_attn_every=2, n_image_tokens=17, vision_d=48,
)
