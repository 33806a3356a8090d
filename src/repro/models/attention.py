"""Attention cores (XLA path) + dispatch to the Pallas kernel (TPU path).

Shapes follow the local-shard contract: q is (B, Sq, Hq, D), k/v are
(B, Sk, Hkv, D) where Hq = gq * Hkv (GQA slots after layout padding --
see models.common.gqa_layout). All cores use online-softmax accumulation
in fp32 and never materialize an (Sq, Sk) matrix larger than one block row.

Three cores:
- ``attn_kv_scan``  : scan over KV blocks, full Sq resident. causal/bidir.
- ``attn_swa``      : scan over Q blocks; each gathers its KV window slice
                      (FLOPs scale with S*window, not S^2).
- ``attn_decode``   : single-query against a (ring-buffered) cache.
- ``attn_latent_decode`` : latent attention (MLA) decode, absorbed form,
                      against the cached latents and rotary keys.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _expand_kv(k, gq: int):
    """(B, S, Hkv, D) -> (B, S, Hkv*gq, D) by repeating each kv head gq x."""
    if gq == 1:
        return k
    return jnp.repeat(k, gq, axis=2)


def attention(q, k, v, *, causal: bool, window: int = 0, q_offset=0,
              impl: str = "xla", block_q: int = 512, block_k: int = 512):
    """Unified entry. q_offset: absolute position of q[0] (chunked prefill)."""
    gq = q.shape[2] // k.shape[2]
    if impl == "pallas":
        from ..kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal, window=window,
                                    q_offset=q_offset)
    k = _expand_kv(k, gq)
    v = _expand_kv(v, gq)
    if window and q.shape[1] > 1:
        return attn_swa(q, k, v, window=window, q_offset=q_offset,
                        block_q=block_q)
    if q.shape[1] == 1:
        return attn_decode(q, k, v, kv_len=k.shape[1], causal=causal,
                           q_pos=q_offset)
    return attn_kv_scan(q, k, v, causal=causal, q_offset=q_offset,
                        block_k=block_k)


def attn_kv_scan(q, k, v, *, causal: bool, q_offset=0, block_k: int = 512,
                 scale: float | None = None):
    """Online-softmax over KV blocks. q/k: (B,Sq|Sk,H,D), v: (B,Sk,H,Dv);
    ``scale`` defaults to D**-0.5."""
    B, Sq, H, D = q.shape
    Sk, Dv = k.shape[1], v.shape[-1]
    block_k = min(block_k, Sk)
    n_blk = -(-Sk // block_k)
    pad = n_blk * block_k - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = D ** -0.5 if scale is None else scale
    qf = (q * scale).astype(q.dtype)
    q_pos = q_offset + jnp.arange(Sq)

    kb = k.reshape(B, n_blk, block_k, H, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blk, block_k, H, Dv).transpose(1, 0, 2, 3, 4)

    def step(carry, blk):
        acc, m, l = carry
        kc, vc, i = blk
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kc,
                       preferred_element_type=jnp.float32)
        k_pos = i * block_k + jnp.arange(block_k)
        mask = k_pos[None, :] < Sk
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32)
        acc = acc * corr.transpose(0, 2, 1)[..., None] + pv
        return (acc, m_new, l), None

    acc0 = jnp.zeros((B, Sq, H, Dv), jnp.float32)
    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    (acc, m, l), _ = lax.scan(step, (acc0, m0, l0),
                              (kb, vb, jnp.arange(n_blk)))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def attn_swa(q, k, v, *, window: int, q_offset=0, block_q: int = 512):
    """Sliding-window attention: scan over Q blocks; each q block attends to
    the KV slice [start, start + window + block_q) where start is clamped --
    compute is O(Sq * (window + block_q)) regardless of Sk."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    block_q = min(block_q, Sq)
    assert Sq % block_q == 0, "Sq must divide into q blocks"
    n_blk = Sq // block_q
    span = min(window + block_q, Sk)
    scale = D ** -0.5

    qb = (q * scale).reshape(B, n_blk, block_q, H, D).transpose(1, 0, 2, 3, 4)

    def step(_, blk):
        qc, i = blk
        q_start = q_offset + i * block_q
        start = jnp.clip(q_start + block_q - span, 0, Sk - span)
        kc = lax.dynamic_slice_in_dim(k, start, span, axis=1)
        vc = lax.dynamic_slice_in_dim(v, start, span, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc,
                       preferred_element_type=jnp.float32)
        q_pos = q_start + jnp.arange(block_q)
        k_pos = start + jnp.arange(span)
        mask = (k_pos[None, :] <= q_pos[:, None]) & \
               (k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bkhd->bqhd", (p / jnp.maximum(l, 1e-30)
                                           ).astype(vc.dtype), vc,
                       preferred_element_type=jnp.float32)
        return None, o.astype(q.dtype)

    _, out = lax.scan(step, None, (qb, jnp.arange(n_blk)))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, D)


def attn_decode(q, k, v, *, kv_len, causal: bool = True, q_pos=None,
                new=None):
    """q: (B,1,Hq,D) against cache k/v: (B,Smax,Hkv,D), Hq = gq*Hkv.
    GQA is served by a grouped einsum -- the KV cache is *not* repeated
    (a materialized repeat doubles decode HBM traffic, the dominant term
    of the decode roofline). ``kv_len`` may be per-batch (B,).

    ``new`` = (k_new, v_new, slot): this step's keys and values (B,Hkv,D)
    and the cache row (B,) they belong in, which the cache does not hold
    yet. Row ``slot`` of k/v is left out and the new row attended to in
    its place, as if it had been written there first."""
    B, _, Hq, D = q.shape
    Smax, Hkv = k.shape[1], k.shape[2]
    gq = Hq // Hkv
    qg = (q[:, 0] * D ** -0.5).reshape(B, Hkv, gq, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k,
                   preferred_element_type=jnp.float32)
    pos = jnp.arange(Smax)
    if jnp.ndim(kv_len) == 0:
        valid = pos[None, :] < kv_len
    else:
        valid = pos[None, :] < kv_len[:, None]
    if new is None:
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, 1, Hq, D).astype(q.dtype)
    k_new, v_new, slot = new
    valid = valid & (pos[None, :] != slot[:, None])
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    s_new = jnp.einsum("bkgd,bkd->bkg", qg, k_new,
                       preferred_element_type=jnp.float32)
    # the softmax over the cache's rows and the new one
    m = jnp.maximum(jnp.max(s, axis=-1), s_new)
    e = jnp.exp(s - m[..., None])
    e_new = jnp.exp(s_new - m)
    total = jnp.sum(e, axis=-1) + e_new
    p = (e / total[..., None]).astype(v.dtype)
    p_new = (e_new / total).astype(v.dtype)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v,
                   preferred_element_type=jnp.float32)
    o = o + jnp.einsum("bkg,bkd->bkgd", p_new, v_new,
                       preferred_element_type=jnp.float32)
    return o.reshape(B, 1, Hq, D).astype(q.dtype)


def attn_latent_decode(q_lat, q_pe, c, kr, *, kv_len, new, scale: float):
    """Absorbed latent attention of one decode step (DeepSeek-V2's MLA).
    q_lat: (B,H,R) queries already multiplied into the latent space
    (W_uk^T q_nope); q_pe: (B,H,Dr) rotary queries; c: (B,Smax,R) cached
    latents and kr: (B,Smax,Dr) cached rotary keys, shared by every head.
    ``new`` = (c_new (B,R), kr_new (B,Dr), slot (B,)) is this step's row,
    not in the cache yet: row ``slot`` is left out and the new row
    attended to in its place, as ``attn_decode`` does. Returns the
    attention-weighted latents (B,H,R) in float32, which W_uv maps to
    each head's values. A score is (q_lat.c + q_pe.kr) * scale."""
    c_new, kr_new, slot = new
    Smax = c.shape[1]
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, c,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bsd->bhs", q_pe, kr,
                      preferred_element_type=jnp.float32)) * scale
    s_new = (jnp.einsum("bhr,br->bh", q_lat, c_new,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhd,bd->bh", q_pe, kr_new,
                          preferred_element_type=jnp.float32)) * scale
    pos = jnp.arange(Smax)
    valid = (pos[None, :] < kv_len[:, None]) & (pos[None, :] != slot[:, None])
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    m = jnp.maximum(jnp.max(s, axis=-1), s_new)
    e = jnp.exp(s - m[..., None])
    e_new = jnp.exp(s_new - m)
    total = jnp.sum(e, axis=-1) + e_new
    o = jnp.einsum("bhs,bsr->bhr", (e / total[..., None]).astype(c.dtype), c,
                   preferred_element_type=jnp.float32)
    return o + (e_new / total)[..., None] * c_new.astype(jnp.float32)[:, None]


def attn_cross(q, k, v):
    """Dense bidirectional cross-attention (image tokens are few)."""
    D = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q * D ** -0.5, k,
                   preferred_element_type=jnp.float32)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)
