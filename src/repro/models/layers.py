"""Elementary layers: norms, RoPE, vocab-parallel embedding & cross-entropy.

All functions take ``ops`` (ShardOps | GlobalOps) and obey the shape
contract of repro.parallel.ops: tensors are local shards on the mpignite
path and global arrays on the gspmd path.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..parallel import axes as A
from ..parallel.ops import Ops
from .common import YaRN


def rmsnorm(x, w, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def rope_angles(positions, dh_rot: int, theta: float,
                yarn: YaRN | None = None):
    """positions: int32 (...,); returns cos/sin of shape (..., dh_rot//2)."""
    if yarn is None:
        inv = 1.0 / (theta ** (jnp.arange(0, dh_rot, 2, dtype=jnp.float32)
                               / dh_rot))
    else:
        inv = jnp.asarray(yarn_inv_freq(dh_rot, theta, yarn))
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    return cos, sin


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V2's ``yarn_get_mscale``: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dh_rot: int, theta: float, yarn: YaRN) -> np.ndarray:
    """(dh_rot//2,) YaRN inverse frequencies, as DeepSeek-V2 computes
    them: pairs below the correction range keep theta's frequency, pairs
    above it are divided by ``factor``, and a linear ramp joins them. The
    range is where ``beta_fast`` and ``beta_slow`` full rotations fit in
    ``original_max`` positions (``yarn_find_correction_range``)."""
    def corr_dim(rotations):
        return (dh_rot * math.log(yarn.original_max
                                  / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), dh_rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dh_rot // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    extra = 1.0 / theta ** (np.arange(0, dh_rot, 2, dtype=np.float32)
                            / dh_rot)
    return (extra / yarn.factor * ramp + extra * (1.0 - ramp)
            ).astype(np.float32)


def apply_rope(x, cos, sin, rope_pct: float = 1.0):
    """x: (B, S, H, D); cos/sin: (S, d_rot/2) or (B, S, d_rot/2)."""
    d = x.shape[-1]
    d_rot = int(d * rope_pct) // 2 * 2
    if d_rot == 0:
        return x
    xr, xp = x[..., :d_rot], x[..., d_rot:]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    if cos.ndim == 2:   # (S, d_rot/2) -> broadcast over batch & heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:               # (B, S, d_rot/2)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.astype(x.dtype), xp], -1)


# ---------------------------------------------------------------------------
# Vocab-parallel embedding / logits / cross-entropy (Megatron-style).
# The embedding table is (V_pad, d) sharded P(model, data); on the mpignite
# path each shard embeds only tokens inside its vocab slice, followed by a
# model-axis psum (fused into the sequence-parallel scatter when SP is on).
# ---------------------------------------------------------------------------

def embed(ops: Ops, table, tokens, v_pad: int, combine: str = "psum"):
    """tokens: (B, S) int32 -> (B, S, d) with table FSDP dim gathered.
    combine="none" returns the *partial* (vocab-shard-masked) embedding so
    the caller can fuse the model-axis reduction into a reduce-scatter
    (sequence-parallel entry)."""
    w = ops.weight(table, P(A.MODEL_AXIS, A.DATA_AXIS))   # (V_loc, d)
    v_loc = w.shape[0]
    if v_loc == v_pad:                                     # global path / tp=1
        return jnp.take(w, tokens, axis=0)
    start = ops.tp_index() * v_loc
    local = tokens - start
    inside = (local >= 0) & (local < v_loc)
    local = jnp.clip(local, 0, v_loc - 1)
    out = jnp.take(w, local, axis=0)
    out = jnp.where(inside[..., None], out, jnp.zeros_like(out))
    return out if combine == "none" else ops.tp_psum(out)


def logits_and_xent(ops: Ops, head_w, x, labels, valid, v_pad: int, vocab: int):
    """Fused LM head + cross-entropy, numerically stable, vocab-parallel.

    x: (..., d) activations (full d); head_w: (d, V_pad) sharded col-parallel;
    labels: int32 (...,); valid: bool/float mask (...,).
    Returns (sum_nll, n_valid) -- both *local* to this shard's batch slice;
    callers finish with dp reductions.
    """
    w = ops.weight(head_w, P(A.DATA_AXIS, A.MODEL_AXIS))   # (d, V_loc)
    v_loc = w.shape[1]
    logits = (x @ w).astype(jnp.float32)                   # (..., V_loc)
    start = ops.tp_index() * v_loc
    # mask padded vocab entries (only the last shard can own them)
    col = start + jnp.arange(v_loc)
    logits = jnp.where(col < vocab, logits, -jnp.inf)

    m_loc = jnp.max(logits, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m_loc), m_loc, jnp.finfo(jnp.float32).min)
    # the stabilizer is gradient-free (standard softmax trick) -- and pmax
    # has no AD rule, so stop_gradient is also required for correctness
    m_glob = _tp_max(ops, lax.stop_gradient(m_safe))
    z = jnp.sum(jnp.exp(logits - m_glob[..., None]), axis=-1)
    z = ops.tp_psum(z)
    lse = jnp.log(z) + m_glob

    lab_local = labels - start
    inside = (lab_local >= 0) & (lab_local < v_loc)
    lab_safe = jnp.clip(lab_local, 0, v_loc - 1)
    picked = jnp.take_along_axis(logits, lab_safe[..., None], axis=-1)[..., 0]
    picked = jnp.where(inside, picked, 0.0)
    picked = ops.tp_psum(picked)

    nll = (lse - picked) * valid.astype(jnp.float32)
    return jnp.sum(nll), jnp.sum(valid.astype(jnp.float32))


def _tp_max(ops: Ops, x):
    if ops.tp <= 1:
        return x
    # PeerComm supports arbitrary reductions (the paper's allReduce(data, f));
    # native backend fast-paths to lax.pmax.
    if hasattr(ops, "comm_model"):
        return ops.comm_model.allreduce(x, "max")
    return x  # GlobalOps: logits are global already


def logits_only(ops: Ops, head_w, x, v_pad: int, vocab: int):
    """Full (gathered) logits for decode steps: (..., vocab)."""
    w = ops.weight(head_w, P(A.DATA_AXIS, A.MODEL_AXIS))
    logits = (x @ w).astype(jnp.float32)
    logits = ops.tp_all_gather(logits, dim=logits.ndim - 1)
    return logits[..., :vocab]
