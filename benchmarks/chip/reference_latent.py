"""Plain float32 reference of DeepSeek-V2-Lite's layer equations
(arXiv:2405.04434; the model's config.json) on one chip's share of an
expert-parallel deployment: latent attention (MLA) without query
compression and with YaRN rotary positions, a dense first layer, then
layers whose feed-forward is the routed experts held here plus the
shared experts, and an untied output head.

For a token with normed input h, in each layer:

- queries q = h W_q, per head [q_nope (dn) | q_pe (dr)];
- [c_raw | k_pe_raw] = h W_dkv; c = RMSNorm(c_raw) (the latent,
  ``kv_norm``); k_pe = rope(k_pe_raw), one rotary key for every head;
- per head [k_nope | v] = c W_ukv;
- score(t) = (q_nope . k_nope(t) + rope(q_pe) . k_pe(t)) * (dn+dr)^-0.5
  * m^2, m = 0.1 * mscale_all_dim * ln(factor) + 1; a causal softmax;
  o = concat_h softmax . v, times W_o;
- the feed-forward: layer 0 a SwiGLU MLP; the others p = softmax(h W_r)
  over all routed experts, the top k taken (renormalised only when
  ``norm_topk_prob``), y = sum over the top k of p_e E_e(h) for the
  experts held here (``experts_held``, the block [0, held)), plus the
  shared experts' SwiGLU S(h) once.

Departure: DeepSeek rotates interleaved pairs of the rotary dimensions;
here the halves are rotated together, as the program does (with seeded
weights a fixed permutation of the rotary columns of W_q and W_dkv).

It imports nothing of the program. Sizes come from the configuration
file's ``model`` block. Weights are drawn here from the seed by the
recipe the program's initialiser follows (one normal draw per stacked
leaf, in sorted-key order, from ``split(PRNGKey(seed), n_leaves)``,
scaled, then rounded to the served dtype), so program and reference see
the same numbers without one taking them from the other. Every matrix
product runs at ``Precision.HIGHEST`` in float32, with no cache and no
batching of requests; queries are taken ``Q_BLOCK`` at a time. ``quant``
gives the controls: ``"w8a16"``, every weight matrix rounded to int8
(one scale per output column) with bfloat16 activations, one precision
below the served bfloat16; and ``"bf16"``, every product's operands
rounded to bfloat16, one below float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32, BF16 = jnp.float32, jnp.bfloat16
HIGHEST = lax.Precision.HIGHEST
#: queries per attention block: the (heads, block, keys) score tile is
#: what bounds the reference's memory, not the sequence length
Q_BLOCK = 512

DTYPES = {"bfloat16": BF16, "float32": F32}


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------

def _attn(m: dict, L: int) -> dict:
    d, H, R = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return {"kv_norm": ((L, R), "ones"), "ln1": ((L, d), "ones"),
            "ln2": ((L, d), "ones"), "w_dkv": ((L, d, R + dr), "normal"),
            "w_ukv": ((L, R, H * (dn + dv)), "normal"),
            "wo": ((L, H * dv, d), "scaled"),
            "wq": ((L, d, H * (dn + dr)), "normal")}


def _mlp(d: int, f: int, L: int) -> dict:
    return {"w_down": ((L, f, d), "scaled"), "w_gate": ((L, d, f), "normal"),
            "w_up": ((L, d, f), "normal")}


def leaf_specs(m: dict) -> dict:
    """(shape, init) of every leaf, nested as the program nests them.
    init is "ones", "normal" (std ``init_std``) or "scaled" (std
    ``init_std / sqrt(2 * n_layers)``, the residual output projections)."""
    d, v, f = m["d_model"], m["vocab"], m["moe_d_ff"]
    L0 = m["first_dense_layers"]
    L1 = m["n_layers"] - L0
    held = m["experts_held"] or m["n_experts"]
    dense = {**_attn(m, L0), **_mlp(d, m["d_ff"], L0)}
    moe = {**_attn(m, L1),
           "moe": {"router": ((L1, d, m["n_experts"]), "normal"),
                   "wd": ((L1, held, f, d), "scaled"),
                   "wg": ((L1, held, d, f), "normal"),
                   "wu": ((L1, held, d, f), "normal")},
           "par": _mlp(d, m["n_shared_experts"] * f, L1)}
    return {"blocks": {"seg0": dense, "seg1": moe},
            "embed": ((v, d), "normal"), "final_norm": ((d,), "ones"),
            "head": ((d, v), "normal")}


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def make_weights(m: dict, key, dtype=None):
    """All weights from ``key`` (``weights_key``), drawn on the device in
    one program: call under ``jax.jit`` with the key as an argument."""
    if m["vocab"] % 128:
        raise ValueError("the program pads the vocabulary to a multiple "
                         "of 128 rows; give one that needs no padding")
    dtype = dtype or DTYPES[m["dtype"]]
    leaves, tdef = jax.tree.flatten(leaf_specs(m), is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    std = m["init_std"]
    out = []
    for (shape, init), key in zip(leaves, keys):
        if init == "ones":
            out.append(jnp.ones(shape, dtype))
            continue
        s = std if init == "normal" else std / math.sqrt(2.0 * m["n_layers"])
        out.append((jax.random.normal(key, shape, F32) * s).astype(dtype))
    return jax.tree.unflatten(tdef, out)


def weights_seed(seed: int) -> int:
    """The 32 bits of ``--seed`` that key the weights."""
    return int(seed) % (1 << 32)


def weights_key(seed: int):
    """The PRNG key the program's initialiser gets for ``--seed``."""
    return jax.random.PRNGKey(weights_seed(seed))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _q8(x, axis: int):
    """int8 rounding, one scale per slice along ``axis`` (control only)."""
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0,
                    1e-30)
    return (jnp.clip(jnp.round(x / s), -127, 127) * s).astype(BF16)


def _mm(x, w, quant):
    if quant is None:
        return jnp.matmul(x.astype(F32), w.astype(F32), precision=HIGHEST)
    w = w.astype(BF16) if quant == "bf16" else _q8(w, -2)
    return jnp.matmul(x.astype(BF16), w, preferred_element_type=F32)


def _rmsnorm(x, w, eps):
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(F32)


def mscale(factor: float, scale: float) -> float:
    """0.1 * scale * ln(factor) + 1 (1 for factor <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * scale * math.log(factor) + 1.0


def yarn_freqs(dr: int, theta: float, y: dict) -> np.ndarray:
    """(dr/2,) rotary frequencies: theta's for the pairs that turn more
    than ``beta_fast`` times over ``original_max`` positions, theta's
    over ``factor`` for those that turn less than ``beta_slow`` times,
    a linear ramp between (DeepSeek-V2's ``yarn_find_correction_range``
    and ``yarn_linear_ramp_mask``)."""
    base = 1.0 / theta ** (np.arange(0, dr, 2, dtype=np.float64) / dr)

    def pair(rotations):            # the pair index that turns that often
        return dr * math.log(y["original_max"] / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(pair(y["beta_fast"])), 0)
    hi = min(math.ceil(pair(y["beta_slow"])), dr - 1)
    hi = hi + 0.001 if hi == lo else hi
    ramp = np.clip((np.arange(dr // 2) - lo) / (hi - lo), 0.0, 1.0)
    return (base * (1 - ramp) + base / y["factor"] * ramp).astype(np.float32)


def _rope(x, pos, m: dict):
    """Rotary embedding of the last dim, halves rotated together, at
    YaRN's frequencies and its cos/sin factor."""
    y = m["rope_yarn"]
    dr = x.shape[-1]
    ang = pos.astype(F32)[:, None] * jnp.asarray(
        yarn_freqs(dr, m["rope_theta"], y))
    k = mscale(y["factor"], y["mscale"]) / mscale(y["factor"],
                                                  y["mscale_all_dim"])
    cos, sin = (jnp.cos(ang) * k)[:, None, :], (jnp.sin(ang) * k)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def score_scale(m: dict) -> float:
    y = m["rope_yarn"]
    s = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    return s * mscale(y["factor"], y["mscale_all_dim"]) ** 2


def _attention(q, k, v, scale: float, quant):
    """Causal attention of one sequence. q/k: (S, H, dq), v: (S, H, dv)."""
    S, H, _ = q.shape
    qb = min(Q_BLOCK, S)
    nb = -(-S // qb)
    qp = jnp.pad(q, ((0, nb * qb - S), (0, 0), (0, 0)))
    kpos = jnp.arange(S)
    prec = HIGHEST if quant is None else None
    cast = (lambda a: a) if quant is None else (lambda a: a.astype(BF16))

    def block(args):
        qi, i = args
        s = jnp.einsum("qhd,khd->hqk", cast(qi), cast(k), precision=prec,
                       preferred_element_type=F32) * scale
        ok = kpos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", cast(p), cast(v), precision=prec,
                          preferred_element_type=F32)

    o = lax.map(block, (qp.reshape(nb, qb, H, -1), jnp.arange(nb)))
    return o.reshape(nb * qb, H, -1)[:S]


def _mla(x, p, pos, m: dict, quant):
    S = x.shape[0]
    H, R = m["n_heads"], m["kv_lora_rank"]
    dn, dr = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    h = _rmsnorm(x, p["ln1"], m["norm_eps"])
    q = _mm(h, p["wq"], quant).reshape(S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, m)], -1)
    ckv = _mm(h, p["w_dkv"], quant)
    c = _rmsnorm(ckv[:, :R], p["kv_norm"], m["norm_eps"])
    k_pe = _rope(ckv[:, None, R:], pos, m)                      # (S, 1, dr)
    kv = _mm(c, p["w_ukv"], quant).reshape(S, H, -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (S, H, dr))], -1)
    o = _attention(q, k, kv[..., dn:], score_scale(m), quant)
    return x + _mm(o.reshape(S, -1), p["wo"], quant)


def _swiglu(h, p, quant):
    u = jax.nn.silu(_mm(h, p["w_gate"], quant)) * _mm(h, p["w_up"], quant)
    return _mm(u, p["w_down"], quant)


def routed(h, p, m: dict, quant=None):
    """The held experts' part of the routed feed-forward: (S, d) -> (S, d).
    Each held expert runs on every position, weighted by its router
    probability where it is among the position's top k, else by 0."""
    probs = jax.nn.softmax(_mm(h, p["router"], quant), axis=-1)
    topv, topi = lax.top_k(probs, m["top_k"])
    if m["norm_topk_prob"]:
        topv = topv / jnp.sum(topv, -1, keepdims=True)
    held = p["wg"].shape[0]
    gate = jnp.sum(jnp.where(topi[..., None] == jnp.arange(held),
                             topv[..., None], 0.0), axis=1)    # (S, held)
    ys = jax.vmap(lambda g, u, dn: _swiglu(
        h, {"w_gate": g, "w_up": u, "w_down": dn}, quant))(
            p["wg"], p["wu"], p["wd"])                          # (held, S, d)
    return jnp.einsum("se,esd->sd", gate, ys, precision=HIGHEST) \
        * m["routed_scaling_factor"]


def hidden(w, tokens, m: dict, quant=None):
    """Final-normed hidden states of one sequence: (S,) -> (S, d)."""
    pos = jnp.arange(tokens.shape[0])
    eps = m["norm_eps"]
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)
    if quant is not None:
        x = x.astype(BF16).astype(F32)

    def dense(x, p):
        x = _mla(x, p, pos, m, quant)
        return x + _swiglu(_rmsnorm(x, p["ln2"], eps), p, quant), None

    def moe(x, p):
        x = _mla(x, p, pos, m, quant)
        h = _rmsnorm(x, p["ln2"], eps)
        return x + routed(h, p["moe"], m, quant) + \
            _swiglu(h, p["par"], quant), None

    x, _ = lax.scan(dense, x, w["blocks"]["seg0"])
    x, _ = lax.scan(moe, x, w["blocks"]["seg1"])
    return _rmsnorm(x, w["final_norm"], eps)


def logits(w, tokens, m: dict, quant=None):
    """(S,) token ids -> (S, vocab) float32 next-token logits."""
    return _mm(hidden(w, tokens, m, quant), w["head"], quant)[:, :m["vocab"]]


def logits_at(w, tokens, positions, m: dict, quant=None):
    """``logits`` at ``positions`` only: (P,) -> (P, vocab). The head's
    product is formed for those positions, not the whole sequence."""
    x = jnp.take(hidden(w, tokens, m, quant), positions, axis=0)
    return _mm(x, w["head"], quant)[:, :m["vocab"]]
