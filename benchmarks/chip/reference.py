"""Plain float32 reference of a dense sliding-window transformer
(h2o-danube-1.8b's layer equations, arXiv:2401.16818: Llama-style
pre-RMSNorm blocks, grouped-query attention with rotary positions over a
sliding window, SwiGLU MLP, untied output head).

It imports nothing of the program. Sizes come from the configuration
file's ``model`` block. Weights are drawn here from the seed by the
recipe the program's initialiser follows (one normal draw per stacked
leaf, in sorted-key order, from ``split(PRNGKey(seed), n_leaves)``,
scaled, then rounded to the served dtype), so program and reference see
the same numbers without one taking them from the other.

Every matrix product runs at ``Precision.HIGHEST`` in float32, with no
cache and no batching of requests. ``quant`` gives the controls, the
same equations one precision below the configuration's bfloat16, as the
step a later change might be tempted to take: ``"w8a16"`` rounds every
weight matrix to int8 (one scale per output column) and keeps bfloat16
activations, as weight-only quantised serving does; ``"w8a8"`` also
rounds the activations entering each product to int8 (one scale per
row), as int8 training does.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

F32, BF16 = jnp.float32, jnp.bfloat16
HIGHEST = lax.Precision.HIGHEST
#: queries per attention block: the (heads, block, keys) score tile is
#: what bounds the reference's memory, not the sequence length
Q_BLOCK = 512
#: positions per slice of the training loss's head and softmax
LOSS_CHUNK = 1024

DTYPES = {"bfloat16": BF16, "float32": F32}


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------

def leaf_specs(m: dict) -> dict:
    """(shape, init) of every leaf, nested as the program nests them.
    init is "ones", "normal" (std ``init_std``) or "scaled" (std
    ``init_std / sqrt(2 * n_layers)``, the residual output projections)."""
    L, d, f = m["n_layers"], m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    v = m["vocab"]
    block = {"ln1": ((L, d), "ones"), "ln2": ((L, d), "ones"),
             "w_down": ((L, f, d), "scaled"), "w_gate": ((L, d, f), "normal"),
             "w_up": ((L, d, f), "normal"), "wk": ((L, d, kv), "normal"),
             "wo": ((L, q, d), "scaled"), "wq": ((L, d, q), "normal"),
             "wv": ((L, d, kv), "normal")}
    return {"blocks": {"seg0": block}, "embed": ((v, d), "normal"),
            "final_norm": ((d,), "ones"), "head": ((d, v), "normal")}


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def make_weights(m: dict, key, dtype=None):
    """All weights from ``key`` (``weights_key``), drawn on the device in
    one program: call under ``jax.jit`` with the key as an argument, so
    that one compiled program serves every seed."""
    dtype = dtype or DTYPES[m["dtype"]]
    specs = leaf_specs(m)
    leaves, tdef = jax.tree.flatten(specs, is_leaf=_is_spec)
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
    q = jnp.clip(jnp.round(x / s), -127, 127) * s
    # straight-through: the rounding passes the gradient unchanged
    return (x + lax.stop_gradient(q - x)).astype(BF16)


def _mm(x, w, quant):
    if quant is None:
        return jnp.matmul(x.astype(F32), w.astype(F32), precision=HIGHEST)
    # weights per output column; activations per row under "w8a8"
    x = _q8(x, -1) if quant == "w8a8" else x.astype(BF16)
    return jnp.matmul(x, _q8(w, -2), preferred_element_type=F32)


def _rmsnorm(x, w, eps):
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(F32)


def _rope(x, pos, theta):
    """Rotary embedding over the whole head, halves rotated together."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = pos.astype(F32)[:, None] * inv                     # (S, dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window: int, quant, remat: bool = False):
    """Causal sliding-window attention of one sequence. q: (S, H, dh),
    k/v: (S, KV, dh); query head h reads key head h // (H / KV)."""
    S, H, dh = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    qb = min(Q_BLOCK, S)
    nb = -(-S // qb)
    qp = jnp.pad(q, ((0, nb * qb - S), (0, 0), (0, 0)))
    kpos = jnp.arange(S)
    prec = HIGHEST if quant is None else None
    cast = (lambda a: a) if quant is None else (lambda a: a.astype(BF16))

    def block(args):
        qi, i = args
        s = jnp.einsum("qhd,khd->hqk", cast(qi), cast(k), precision=prec,
                       preferred_element_type=F32) * dh ** -0.5
        qpos = i * qb + jnp.arange(qb)
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", cast(p), cast(v), precision=prec,
                          preferred_element_type=F32)

    if remat:               # keep one score tile, not one per block
        block = jax.checkpoint(block)
    o = lax.map(block, (qp.reshape(nb, qb, H, dh), jnp.arange(nb)))
    return o.reshape(nb * qb, H, dh)[:S]


def hidden(w, tokens, m: dict, quant=None, remat: bool = False):
    """Final-normed hidden states of one sequence: (S,) -> (S, d)."""
    S = tokens.shape[0]
    H, KV, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps, pos = m["norm_eps"], jnp.arange(S)
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)
    if quant is not None:
        x = x.astype(BF16).astype(F32)

    def layer(x, p):
        h = _rmsnorm(x, p["ln1"], eps)
        q = _rope(_mm(h, p["wq"], quant).reshape(S, H, dh), pos,
                  m["rope_theta"])
        k = _rope(_mm(h, p["wk"], quant).reshape(S, KV, dh), pos,
                  m["rope_theta"])
        v = _mm(h, p["wv"], quant).reshape(S, KV, dh)
        o = _attention(q, k, v, m["window"], quant, remat).reshape(S, H * dh)
        x = x + _mm(o, p["wo"], quant)
        h = _rmsnorm(x, p["ln2"], eps)
        u = jax.nn.silu(_mm(h, p["w_gate"], quant)) * _mm(h, p["w_up"], quant)
        return x + _mm(u, p["w_down"], quant), None

    if remat:
        layer = jax.checkpoint(layer)
    x, _ = lax.scan(layer, x, w["blocks"]["seg0"])
    return _rmsnorm(x, w["final_norm"], eps)


def logits(w, tokens, m: dict, quant=None):
    """(S,) token ids -> (S, vocab) float32 next-token logits."""
    x = hidden(w, tokens, m, quant)
    return _mm(x, w["head"], quant)[:, :m["vocab"]]


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------

def mean_nll(w, tokens, m: dict, quant=None):
    """Mean next-token negative log-likelihood over a (B, S) batch. The
    head and the softmax run over ``LOSS_CHUNK`` positions at a time, so
    the (S, vocab) logits never exist at once."""
    def one(t):
        x = hidden(w, t, m, quant, remat=True)
        S, d = x.shape
        c = min(LOSS_CHUNK, S)
        pad = -S % c
        labels = jnp.pad(t[1:], (0, 1 + pad))
        valid = jnp.arange(S + pad) < S - 1
        xs = jnp.pad(x, ((0, pad), (0, 0)))

        @jax.checkpoint
        def chunk(args):
            xc, lc, vc = args
            z = _mm(xc, w["head"], quant)[:, :m["vocab"]]
            nll = jax.nn.logsumexp(z, axis=-1) - \
                jnp.take_along_axis(z, lc[:, None], axis=1)[:, 0]
            return jnp.sum(jnp.where(vc, nll, 0.0))

        return jnp.sum(lax.map(chunk, (xs.reshape(-1, c, d),
                                       labels.reshape(-1, c),
                                       valid.reshape(-1, c))))
    total = jnp.sum(jax.vmap(one)(tokens))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def lr_at(opt: dict, step):
    """Linear warm-up then cosine decay; ``step`` counts updates from 1."""
    step = step.astype(F32)
    warm = opt["lr_peak"] * (step + 1) / max(opt["warmup_steps"], 1)
    t = jnp.clip((step - opt["warmup_steps"])
                 / max(opt["total_steps"] - opt["warmup_steps"], 1), 0., 1.)
    cos = opt["lr_peak"] * 0.5 * (1.0 + jnp.cos(jnp.pi * t))
    return jnp.where(step < opt["warmup_steps"], warm, cos)


def adamw_step(opt: dict, w, grads, m1, m2, step):
    """One AdamW update after global-norm clipping. Returns the clipped
    gradient too (what the optimizer was given)."""
    gl = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in gl))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12)) \
        if opt["grad_clip"] else 1.0
    g = jax.tree.map(lambda x: x * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    t = step.astype(F32)
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    lr = lr_at(opt, step)
    m1 = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m1, g)
    m2 = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, m2, g)
    w = jax.tree.map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + opt["eps"])
                                  + opt["weight_decay"] * p), w, m1, m2)
    return w, m1, m2, g, gnorm


def leaf_norms(tree) -> list:
    """Euclidean norm of every leaf, in flattening order."""
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
            for x in jax.tree.leaves(tree)]


def worst_leaf_gap(prog: list[float], ref: list[float]) -> float:
    """Widest |program norm - reference norm| over the leaves, each taken
    against the larger of its own reference norm and the median leaf's."""
    import numpy as np
    med = float(np.median(ref))
    return max(abs(p - r) / max(r, med) for p, r in zip(prog, ref))
