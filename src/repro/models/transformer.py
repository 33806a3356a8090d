"""Unified layer stack: dense / MoE / hybrid-SSM / xLSTM / VLM / encoder.

An architecture is compiled into a list of ``Segment``s; each segment is a
homogeneous run of layers whose stacked parameters are swept with
``lax.scan`` (keeping HLO size and compile time bounded in depth).
Heterogeneous interleavings (zamba2's shared attention every 6 Mamba
layers, llama-vision's cross-attention every 5th layer, xLSTM's sLSTM
positions) become *grouped* segments: outer scan over groups, inner scan
over the group's homogeneous run, with the odd block applied per group.

Sequence-parallel layout: between blocks, activations are (B, S_loc, d)
(sharded over `model`); norms act per-token on shards; attention gathers
the sequence (``seq_unshard``), output projections reduce-scatter back
(``seq_shard``). All communication goes through ``Ops`` -> ``PeerComm``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..parallel import axes as A
from ..parallel.ops import Ops, ShardOps
from . import attention as ATT
from . import moe as MOE
from . import ssm as SSM
from . import xlstm as XL
from .common import (GQALayout, ModelConfig, ParamSpec, dense_col, dense_row,
                     head_mask, replicated, stacked)
from .layers import apply_rope, rmsnorm, yarn_mscale


@dataclasses.dataclass(frozen=True)
class Segment:
    name: str
    kind: str          # attn_mlp | attn_moe | zamba_group | mlstm | slstm | vlm_group
    count: int         # outer scan length
    inner: int = 1     # homogeneous layers per group (grouped kinds)


def build_schedule(cfg: ModelConfig) -> list[Segment]:
    L = cfg.n_layers
    if cfg.kind == "hybrid":
        groups = L // cfg.attn_every
        assert groups * cfg.attn_every == L
        return [Segment("seg0", "zamba_group", groups, inner=cfg.attn_every)]
    if cfg.kind == "xlstm":
        pos_s = {k for k in range(L)
                 if cfg.slstm_every and (k + 1) % cfg.slstm_every == 0}
        out: list[Segment] = []
        start = 0
        for k in range(L + 1):
            if k == L or k in pos_s:
                if k > start:
                    out.append(Segment(f"seg{len(out)}", "mlstm", k - start))
                if k < L:
                    out.append(Segment(f"seg{len(out)}", "slstm", 1))
                start = k + 1
        return out
    if cfg.cross_attn_every:
        inner = cfg.cross_attn_every - 1
        groups = L // cfg.cross_attn_every
        assert groups * cfg.cross_attn_every == L
        return [Segment("seg0", "vlm_group", groups, inner=inner)]
    if cfg.kind == "moe":
        segs = []
        if cfg.first_dense_layers:
            segs.append(Segment("seg0", "attn_mlp", cfg.first_dense_layers))
        segs.append(Segment(f"seg{len(segs)}", "attn_moe",
                            L - cfg.first_dense_layers))
        return segs
    return [Segment("seg0", "attn_mlp", L)]


# ---------------------------------------------------------------------------
# Per-kind parameter specs (single layer; caller stacks)
# ---------------------------------------------------------------------------

def attn_specs(cfg: ModelConfig, layout: GQALayout) -> dict:
    d, dh = cfg.d_model, cfg.dh
    qm = head_mask(layout, dh)
    sp = {
        "ln1": replicated(d),
        "wq": dense_col(d, layout.n_q_pad * dh, mask=qm),
        "wk": dense_col(d, layout.kv_eff * dh),
        "wv": dense_col(d, layout.kv_eff * dh),
        "wo": dense_row(layout.n_q_pad * dh, d, fan_in=cfg.n_layers,
                        mask=layout.q_real_mask().repeat(dh)),
    }
    if cfg.qk_norm:
        sp["q_norm"] = replicated(dh)
        sp["k_norm"] = replicated(dh)
    return sp


def mla_specs(cfg: ModelConfig) -> dict:
    """Latent attention (no query compression, DeepSeek-V2-Lite): per-head
    query columns [nope | rope], the shared down-projection to the latent
    and the rotary key, the latent's norm, the per-head up-projection
    [k_nope | v] and the output projection. Heads shard over `model`;
    the down-projection and its norm are replicated."""
    d, H, R = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {"ln1": replicated(d),
            "wq": dense_col(d, H * (dn + dr)),
            "w_dkv": ParamSpec((d, R + dr), P(A.DATA_AXIS, None)),
            "kv_norm": replicated(R),
            "w_ukv": dense_col(R, H * (dn + dv)),
            "wo": dense_row(H * dv, d, fan_in=cfg.n_layers)}


def mlp_specs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    sp = {"ln2": replicated(d),
          "w_up": dense_col(d, f),
          "w_down": dense_row(f, d, fan_in=cfg.n_layers)}
    if cfg.act == "swiglu":
        sp["w_gate"] = dense_col(d, f)
    return sp


def layer_specs(cfg: ModelConfig, layout: GQALayout, kind: str) -> dict:
    attn = mla_specs(cfg) if cfg.mla else attn_specs(cfg, layout)
    if kind == "attn_mlp":
        return {**attn, **mlp_specs(cfg)}
    if kind == "attn_moe":
        sp = {**attn, "ln2": replicated(cfg.d_model)}
        sp["moe"] = MOE.moe_param_specs(cfg)
        pd = cfg.n_shared_experts * cfg.moe_d_ff
        if cfg.dense_residual:
            pd = cfg.d_ff
        if pd:
            m = mlp_specs(cfg, pd)
            m.pop("ln2")
            sp["par"] = m
        return sp
    if kind == "mamba":
        return {"ln1": replicated(cfg.d_model),
                **SSM.mamba2_param_specs(cfg, 0)}
    if kind == "mlstm":
        return {"ln1": replicated(cfg.d_model), **XL.mlstm_param_specs(cfg)}
    if kind == "slstm":
        return {"ln1": replicated(cfg.d_model), **XL.slstm_param_specs(cfg)}
    if kind == "cross_attn":
        d, dh = cfg.d_model, cfg.dh
        qm = head_mask(layout, dh)
        return {"ln": replicated(d),
                "wq": dense_col(d, layout.n_q_pad * dh, mask=qm),
                "wk": dense_col(d, layout.kv_eff * dh),
                "wv": dense_col(d, layout.kv_eff * dh),
                "wo": dense_row(layout.n_q_pad * dh, d, fan_in=cfg.n_layers,
                                mask=layout.q_real_mask().repeat(dh)),
                "gate": ParamSpec((), P(), init="zeros"),
                **mlp_specs(cfg)}
    raise ValueError(kind)


def _stack_tree(n: int, tree):
    return jax.tree.map(lambda s: stacked(n, s), tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def segment_specs(cfg: ModelConfig, layout: GQALayout, seg: Segment):
    if seg.kind == "zamba_group":
        return _stack_tree(seg.count, _stack_tree(
            seg.inner, layer_specs(cfg, layout, "mamba")))
    if seg.kind == "vlm_group":
        return {"self": _stack_tree(seg.count, _stack_tree(
                    seg.inner, layer_specs(cfg, layout, "attn_mlp"))),
                "cross": _stack_tree(seg.count,
                                     layer_specs(cfg, layout, "cross_attn"))}
    return _stack_tree(seg.count, layer_specs(cfg, layout, seg.kind))


# ---------------------------------------------------------------------------
# Blocks (forward)
# ---------------------------------------------------------------------------

def _qkv(ops: Ops, p, hf, cfg: ModelConfig, rope, pos=None, prefix=""):
    """hf: (B,S,d) full-seq -> q (B,S,nq_l,dh), k,v (B,S,kv_l,dh)."""
    B, S, d = hf.shape
    dh = cfg.dh
    q = hf @ ops.weight(p[prefix + "wq"], P(A.DATA_AXIS, A.MODEL_AXIS))
    k = hf @ ops.weight(p[prefix + "wk"], P(A.DATA_AXIS, A.MODEL_AXIS))
    v = hf @ ops.weight(p[prefix + "wv"], P(A.DATA_AXIS, A.MODEL_AXIS))
    q = q.reshape(B, S, -1, dh)
    k = k.reshape(B, S, -1, dh)
    v = v.reshape(B, S, -1, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if rope is not None:
        cos, sin = rope
        q = apply_rope(q, cos, sin, cfg.rope_pct)
        k = apply_rope(k, cos, sin, cfg.rope_pct)
    return q, k, v


def _mlp(ops: Ops, p, hf, cfg: ModelConfig):
    wu = ops.weight(p["w_up"], P(A.DATA_AXIS, A.MODEL_AXIS))
    u = hf @ wu
    if cfg.act == "swiglu":
        g = hf @ ops.weight(p["w_gate"], P(A.DATA_AXIS, A.MODEL_AXIS))
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(u)
    return h @ ops.weight(p["w_down"], P(A.MODEL_AXIS, A.DATA_AXIS))


def block_attn(ops: Ops, p, x, cfg: ModelConfig, rope, cache=None, pos=None,
               mode: str = "train", s_max: int = 0, layer=None):
    """Self-attention sub-block. x: (B,S_loc,d) sharded / (B,S,d).
    ``layer``: in decode, ``cache`` is the segment's stacked cache and
    this block is its ``layer``-th (see ``_cached_attn``)."""
    if cfg.mla:
        return block_mla(ops, p, x, cfg, rope, cache, pos, mode, s_max, layer)
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    hf = ops.seq_unshard(h)
    q, k, v = _qkv(ops, p, hf, cfg, rope)
    if mode == "decode":
        o, new_cache = _cached_attn(q, k, v, cfg, cache, pos, layer)
    else:
        o = ATT.attention(q, k, v, causal=cfg.causal, window=cfg.window,
                          impl=cfg.attn_impl)
        new_cache = (_prefill_cache(k, v, cfg, s_max)
                     if mode == "prefill" else None)
    B, S = hf.shape[:2]
    o = o.reshape(B, S, -1)
    o = o @ ops.weight(p["wo"], P(A.MODEL_AXIS, A.DATA_AXIS))
    return x + ops.seq_shard(o), new_cache


def block_mla(ops: Ops, p, x, cfg: ModelConfig, rope, cache=None, pos=None,
              mode: str = "train", s_max: int = 0, layer=None):
    """Latent attention (DeepSeek-V2 MLA) sub-block. Train and prefill run
    the expanded form: per-head keys [k_nope | k_pe] and values from the
    latent, through the blocked causal scan. Decode runs the absorbed
    form against the latent cache {c: (L,B,Smax,R), kr: (L,B,Smax,Dr)}
    (``layer`` as in ``block_attn``): q_nope moves into the latent space
    through W_uk, attention weights the cached latents, and W_uv maps the
    result to each head's values, so no per-position key or value is
    ever formed. Returns (x, prefill cache | decode rows | None)."""
    B = x.shape[0]
    R, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    hf = ops.seq_unshard(h)
    S = hf.shape[1]
    cos, sin = rope
    q = (hf @ ops.weight(p["wq"], P(A.DATA_AXIS, A.MODEL_AXIS))
         ).reshape(B, S, -1, dn + dr)
    q_nope, q_pe = q[..., :dn], apply_rope(q[..., dn:], cos, sin)
    ckv = hf @ ops.weight(p["w_dkv"], P(A.DATA_AXIS, None))
    c = rmsnorm(ckv[..., :R], p["kv_norm"], cfg.norm_eps)        # (B,S,R)
    kr = apply_rope(ckv[..., None, R:], cos, sin)[:, :, 0]       # (B,S,Dr)
    w_ukv = ops.weight(p["w_ukv"], P(A.DATA_AXIS, A.MODEL_AXIS)
                       ).reshape(R, -1, dn + dv)
    scale = (dn + dr) ** -0.5
    if cfg.rope_yarn is not None and cfg.rope_yarn.mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_yarn.factor,
                             cfg.rope_yarn.mscale_all_dim) ** 2
    if mode == "decode":
        Smax = cache["c"].shape[2]
        slot = _slot(cfg, pos, Smax)
        with jax.named_scope("mla.absorb"):
            q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_ukv[..., :dn],
                               preferred_element_type=jnp.float32)
        with jax.named_scope("mla.latent_attn"):
            o_lat = ATT.attn_latent_decode(
                q_lat.astype(c.dtype), q_pe[:, 0], cache["c"][layer],
                cache["kr"][layer], kv_len=jnp.minimum(pos + 1, Smax),
                new=(c[:, 0], kr[:, 0], slot), scale=scale)
        with jax.named_scope("mla.absorb"):
            o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(c.dtype),
                           w_ukv[..., dn:],
                           preferred_element_type=jnp.float32
                           ).astype(x.dtype)[:, None]
        new_cache = {"c": c[:, 0], "kr": kr[:, 0]}
    else:
        kv = jnp.einsum("bsr,rhe->bshe", c, w_ukv)
        H = kv.shape[2]
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(kr[:, :, None], (B, S, H, dr))],
            axis=-1)
        o = ATT.attn_kv_scan(jnp.concatenate([q_nope, q_pe], -1), k,
                             kv[..., dn:], causal=cfg.causal, scale=scale)
        new_cache = None
        if mode == "prefill":
            pad = ((0, 0), (0, s_max - S), (0, 0))
            new_cache = {"c": jnp.pad(c, pad), "kr": jnp.pad(kr, pad)}
    o = o.reshape(B, S, -1) @ ops.weight(p["wo"], P(A.MODEL_AXIS, A.DATA_AXIS))
    return x + ops.seq_shard(o), new_cache


def _prefill_cache(k, v, cfg: ModelConfig, s_max: int):
    """Lay out prefill K/V for decode: ring buffer of `window` slots for
    SWA (slot = abs_pos % window), else right-padded to s_max."""
    B, S = k.shape[:2]
    if cfg.window:
        W = min(cfg.window, s_max) if s_max else cfg.window
        idx = jnp.arange(W) + max(S - W, 0)        # last W absolute positions
        idx = jnp.minimum(idx, S - 1)
        kc = jnp.zeros((B, W) + k.shape[2:], k.dtype)
        kc = kc.at[:, idx % W].set(k[:, idx])
        vc = jnp.zeros((B, W) + v.shape[2:], v.dtype)
        vc = vc.at[:, idx % W].set(v[:, idx])
        return {"k": kc, "v": vc}
    pad = ((0, 0), (0, s_max - S), (0, 0), (0, 0))
    return {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}


def _cached_attn(q, k, v, cfg: ModelConfig, cache, pos, layer=None):
    """Decode-mode attention against a (ring) cache. q/k/v: (B,1,h,dh);
    cache: {k,v: (B,Smax,kv_l,dh)}; pos: (B,) absolute positions. With
    ``layer``, cache is a stacked {k,v: (L,B,Smax,kv_l,dh)} that this
    step's rows are not in yet: attention reads the layer's slice where
    it lies, with the new row in place of the one it will overwrite, and
    the new rows {k,v: (B,kv_l,dh)} are returned for ``write_rows``."""
    B = q.shape[0]
    Smax = cache["k"].shape[-3]
    slot = _slot(cfg, pos, Smax)
    kv_len = jnp.minimum(pos + 1, Smax)
    if layer is None:
        bidx = jnp.arange(B)
        kc = cache["k"].at[bidx, slot].set(k[:, 0])
        vc = cache["v"].at[bidx, slot].set(v[:, 0])
        o = ATT.attn_decode(q, kc, vc, kv_len=kv_len)  # grouped: no KV repeat
        return o, {"k": kc, "v": vc}
    o = ATT.attn_decode(q, cache["k"][layer], cache["v"][layer],
                        kv_len=kv_len, new=(k[:, 0], v[:, 0], slot))
    return o, {"k": k[:, 0], "v": v[:, 0]}


def _slot(cfg: ModelConfig, pos, Smax: int):
    """Cache row of absolute position ``pos``: a ring for SWA."""
    return pos % Smax if cfg.window else jnp.minimum(pos, Smax - 1)


def write_rows(cfg: ModelConfig, cache, rows, pos):
    """Write one decode step's rows (each leaf (L,B,...): {k,v:
    (L,B,kv_l,dh)}, or MLA's {c: (L,B,R), kr: (L,B,Dr)}) into the stacked
    cache (each leaf (L,B,Smax,...)), at each batch row's cache row for
    ``pos``: one dynamic_update_slice per batch row and leaf, all layers
    at once. On the TPU these update a donated cache in place, in the
    layout attention reads it in; a scatter of all rows at once makes
    the compiler relayout the whole cache around it."""
    slot = _slot(cfg, pos, next(iter(cache.values())).shape[2])
    out = {}
    for name, c in cache.items():
        r = rows[name]
        tail = (0,) * (c.ndim - 3)
        for b in range(r.shape[1]):
            c = lax.dynamic_update_slice(c, r[:, b][:, None, None],
                                         (0, b, slot[b]) + tail)
        out[name] = c
    return out


def block_mlp(ops: Ops, p, x, cfg: ModelConfig):
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    hf = ops.seq_unshard(h)
    return x + ops.seq_shard(_mlp(ops, p, hf, cfg))


def block_moe(ops: Ops, p, x, cfg: ModelConfig, layer=None):
    """MoE sub-block (+ optional parallel dense branch). Returns (x, aux,
    counts) with ``moe_ffn``'s counts; ``layer`` as ``moe_ffn`` takes it.

    Token layout cases (mpignite path): sequence-parallel training hands
    each model shard its own token slice (all-to-all dispatch); without SP
    we slice the replicated sequence when it divides tp, else (decode:
    S=1) fall back to replicated dispatch + local experts + psum."""
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    shard = isinstance(ops, ShardOps) and ops.tp > 1
    sliced = False
    h_tok = h
    if shard and not ops.pcfg.sequence_parallel:
        Bs, Ss, d = h.shape
        if Ss % ops.tp == 0:
            s_loc = Ss // ops.tp
            h_tok = lax.dynamic_slice_in_dim(h, ops.tp_index() * s_loc,
                                             s_loc, 1)
            sliced = True
    replicated = shard and not ops.pcfg.sequence_parallel and not sliced
    Bh, Sh, d = h_tok.shape
    routed, aux, counts = MOE.moe_ffn(ops, p["moe"], h_tok.reshape(-1, d),
                                      cfg, tokens_replicated=replicated,
                                      layer=layer)
    routed = routed.reshape(Bh, Sh, d)
    if sliced:
        routed = ops.tp_all_gather(routed, dim=1)
    out = routed
    if "par" in p:
        hf = ops.seq_unshard(h)
        out = out + ops.seq_shard(_mlp(ops, p["par"], hf, cfg))
    return x + out, aux, counts


def block_mamba(ops: Ops, p, x, cfg: ModelConfig, cache=None,
                mode: str = "train"):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    hf = ops.seq_unshard(h)
    y, new_cache = SSM.mamba2_mixer(ops, p, hf, cfg, cache, mode)
    return x + ops.seq_shard(y), new_cache


def block_mlstm(ops: Ops, p, x, cfg: ModelConfig, cache=None,
                mode: str = "train"):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    hf = ops.seq_unshard(h)
    y, new_cache = XL.mlstm_block(ops, p, hf, cfg, cache, mode)
    return x + ops.seq_slice(y), new_cache


def block_slstm(ops: Ops, p, x, cfg: ModelConfig, cache=None,
                mode: str = "train"):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    hf = ops.seq_unshard(h)
    y, new_cache = XL.slstm_block(ops, p, hf, cfg, cache, mode)
    return x + ops.seq_slice(y), new_cache


def cross_kv(ops: Ops, p, img, cfg: ModelConfig):
    """Project image embeddings to this cross layer's K/V: (B,n_img,kv_l,dh)."""
    B, T = img.shape[:2]
    dh = cfg.dh
    ik = (img @ ops.weight(p["wk"], P(A.DATA_AXIS, A.MODEL_AXIS))
          ).reshape(B, T, -1, dh)
    iv = (img @ ops.weight(p["wv"], P(A.DATA_AXIS, A.MODEL_AXIS))
          ).reshape(B, T, -1, dh)
    return ik, iv


def block_cross(ops: Ops, p, x, cfg: ModelConfig, img=None, cache=None,
                mode: str = "train"):
    """Cross-attention + MLP (llama-vision style, tanh-gated).
    ``img``: (B, n_img, d) projected image embeddings (train/prefill);
    decode reads K/V from ``cache``."""
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    hf = ops.seq_unshard(h)
    B, S, d = hf.shape
    dh = cfg.dh
    q = (hf @ ops.weight(p["wq"], P(A.DATA_AXIS, A.MODEL_AXIS))
         ).reshape(B, S, -1, dh)
    if mode == "decode":
        ik, iv = cache["ik"], cache["iv"]
    else:
        ik, iv = cross_kv(ops, p, img, cfg)
    gq = q.shape[2] // ik.shape[2]
    o = ATT.attn_cross(q, jnp.repeat(ik, gq, 2) if gq > 1 else ik,
                       jnp.repeat(iv, gq, 2) if gq > 1 else iv)
    o = o.reshape(B, S, -1) @ ops.weight(p["wo"], P(A.MODEL_AXIS, A.DATA_AXIS))
    x = x + jnp.tanh(p["gate"].astype(jnp.float32)).astype(x.dtype) * \
        ops.seq_shard(o)
    x = block_mlp(ops, p, x, cfg)
    new_cache = {"ik": ik, "iv": iv} if mode != "train" else None
    return x, new_cache
