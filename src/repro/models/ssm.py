"""Mamba2 (SSD) mixer -- chunked matmul form, TPU-friendly.

The GPU reference implementation is a fused warp-level scan; per DESIGN.md
the TPU adaptation recasts SSD as the Mamba-2 paper's block-decomposition:
intra-chunk work is dense matmuls (MXU-shaped), and only the O(S/Q) chunk
boundary states are carried through a ``lax.scan`` (the Pallas ``ssd_scan``
kernel implements the same decomposition with VMEM-resident state).

Head sharding: SSD heads are sharded over the `model` axis; the (small)
B/C group projections are replicated per shard (G=1 for zamba2).

Shapes (local): x (B,S,Hl,P), dt (B,S,Hl), A (Hl,), Bm/Cm (B,S,N).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..parallel import axes as A
from ..parallel.ops import Ops
from .common import ModelConfig, ParamSpec
from .layers import rmsnorm


def segsum(a):
    """(..., Q) log-decays -> (..., Q, Q) lower-tri cumulative sums:
    out[i, j] = sum_{l=j+1..i} a[l] for i >= j, -inf otherwise."""
    Q = a.shape[-1]
    c = jnp.cumsum(a, axis=-1)
    out = c[..., :, None] - c[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x, dt, a_log, Bm, Cm, chunk: int, impl: str = "xla"):
    """SSD scan. x: (B,S,H,P) f32-able, dt: (B,S,H) (post-softplus),
    a_log: (H,) (A = -exp(a_log)), Bm/Cm: (B,S,N). Returns y: (B,S,H,P)
    and the final state (B,H,P,N)."""
    if impl == "pallas":
        from ..kernels.ssd_scan import ssd_scan
        y = ssd_scan(x, dt, a_log, Bm, Cm, chunk=chunk)
        return y, None   # train path; prefill uses impl="xla" for the state
    B, S, H, Pd = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    pad = -S % Q
    S_orig = S
    if pad:
        # zero-pad the tail: dt=0 => decay exp(0)=1 and zero update, so
        # real-position outputs and the final state stay exact.
        zp = lambda t: jnp.pad(t, [(0, 0), (0, pad)] +
                               [(0, 0)] * (t.ndim - 2))
        x, dt, Bm, Cm = zp(x), zp(dt), zp(Bm), zp(Cm)
        S = S + pad
    nc = S // Q
    A_h = -jnp.exp(a_log.astype(jnp.float32))                  # (H,)
    a = dt.astype(jnp.float32) * A_h[None, None, :]            # (B,S,H)
    xdt = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])

    # chunked views: (B, nc, Q, ...)
    ac = a.reshape(B, nc, Q, H)
    xc = xdt.reshape(B, nc, Q, H, Pd)
    Bc = Bm.astype(jnp.float32).reshape(B, nc, Q, N)
    Cc = Cm.astype(jnp.float32).reshape(B, nc, Q, N)

    # ---- intra-chunk (diagonal) term ---------------------------------------
    L = jnp.exp(segsum(ac.transpose(0, 1, 3, 2)))              # (B,nc,H,Q,Q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)             # (B,nc,Q,Q)
    y_diag = jnp.einsum("bchqk,bcqk,bckhp->bcqhp",
                        L, scores, xc)

    # ---- chunk states + inter-chunk recurrence ------------------------------
    cum = jnp.cumsum(ac, axis=2)                               # (B,nc,Q,H)
    total = cum[:, :, -1:, :]                                  # (B,nc,1,H)
    decay_in = jnp.exp(total - cum)                            # weight to chunk end
    states = jnp.einsum("bckn,bckh,bckhp->bchnp",
                        Bc, decay_in, xc)                      # (B,nc,H,N,P)
    chunk_decay = jnp.exp(total[:, :, 0, :])                   # (B,nc,H)

    def step(s_prev, inp):
        st, dec = inp                                          # (B,H,N,P),(B,H)
        s_new = s_prev * dec[..., None, None] + st
        return s_new, s_prev

    s0 = jnp.zeros((B, H, N, Pd), jnp.float32)
    s_final, s_before = lax.scan(
        step, s0, (states.transpose(1, 0, 2, 3, 4),
                   chunk_decay.transpose(1, 0, 2)))
    s_before = s_before.transpose(1, 0, 2, 3, 4)               # (B,nc,H,N,P)

    decay_out = jnp.exp(cum)                                   # (B,nc,Q,H)
    y_off = jnp.einsum("bcqn,bcqh,bchnp->bcqhp",
                       Cc, decay_out, s_before)

    y = (y_diag + y_off).reshape(B, S, H, Pd)[:, :S_orig]
    return y.astype(x.dtype), s_final.transpose(0, 1, 3, 2)    # (B,H,P,N)


def ssd_decode_step(state, x_t, dt_t, a_log, B_t, C_t):
    """One-token recurrence. state: (B,H,P,N); x_t: (B,H,P); dt_t: (B,H);
    B_t/C_t: (B,N). Returns (y_t, new_state)."""
    A_h = -jnp.exp(a_log.astype(jnp.float32))
    dec = jnp.exp(dt_t.astype(jnp.float32) * A_h[None, :])     # (B,H)
    upd = jnp.einsum("bhp,bn->bhpn",
                     x_t.astype(jnp.float32) * dt_t[..., None], B_t.astype(jnp.float32))
    new = state * dec[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new, C_t.astype(jnp.float32))
    return y.astype(x_t.dtype), new


# ---------------------------------------------------------------------------
# Mamba2 block (projections + depthwise conv + SSD + gated norm + out proj)
# ---------------------------------------------------------------------------

def mamba2_param_specs(cfg: ModelConfig, tp: int):
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    H = d_in // cfg.ssm_head_dim
    N = cfg.ssm_state
    K = 4  # conv width
    return {
        "w_zx": ParamSpec((d, 2 * d_in), P(A.DATA_AXIS, A.MODEL_AXIS)),
        "w_bc": ParamSpec((d, 2 * N), P(A.DATA_AXIS, None)),
        "w_dt": ParamSpec((d, H), P(A.DATA_AXIS, A.MODEL_AXIS)),
        "dt_bias": ParamSpec((H,), P(A.MODEL_AXIS), init="zeros"),
        "a_log": ParamSpec((H,), P(A.MODEL_AXIS), init="zeros"),
        "skip_d": ParamSpec((H,), P(A.MODEL_AXIS), init="ones"),
        "conv_x": ParamSpec((K, d_in), P(None, A.MODEL_AXIS)),
        "conv_bc": ParamSpec((K, 2 * N), P()),
        "gnorm": ParamSpec((d_in,), P(A.MODEL_AXIS), init="ones"),
        "w_out": ParamSpec((d_in, d), P(A.MODEL_AXIS, A.DATA_AXIS),
                           init="scaled", fan_in=cfg.n_layers),
    }


def _tail_pad(x, n: int):
    """Last n positions of x (B,S,C), left-zero-padded if S < n."""
    S = x.shape[1]
    if S >= n:
        return x[:, S - n:, :]
    return jnp.pad(x, ((0, 0), (n - S, 0), (0, 0)))


def _causal_conv(x, w, state=None):
    """Depthwise causal conv. x: (B,S,C), w: (K,C). If ``state`` (B,K-1,C)
    is given, operates in streaming mode and returns (y, new_state)."""
    K = w.shape[0]
    if state is not None:
        xx = jnp.concatenate([state, x], axis=1)
        new_state = xx[:, -(K - 1):, :]
    else:
        xx = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
        new_state = None
    y = sum(xx[:, i:i + x.shape[1], :] * w[i][None, None, :] for i in range(K))
    return (y, new_state) if state is not None else y


def mamba2_mixer(ops: Ops, p, x, cfg: ModelConfig, cache=None,
                 mode: str = "train"):
    """x: (B, S, d) full-seq activations (already seq-gathered).
    mode: "train" | "prefill" (build cache) | "decode" (consume ``cache``).
    Returns (y, new_cache)."""
    B, S, d = x.shape
    d_in = cfg.ssm_expand * d
    Pd = cfg.ssm_head_dim
    K = p["conv_x"].shape[0]

    w_zx = ops.weight(p["w_zx"], P(A.DATA_AXIS, A.MODEL_AXIS))
    w_bc = ops.weight(p["w_bc"], P(A.DATA_AXIS, None))
    w_dt = ops.weight(p["w_dt"], P(A.DATA_AXIS, A.MODEL_AXIS))
    zx = x @ w_zx                                      # (B,S,2*d_in_loc)
    z, xs = jnp.split(zx, 2, axis=-1)
    bc = x @ w_bc                                      # (B,S,2N) replicated
    dt_raw = x @ w_dt                                  # (B,S,H_loc)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    h_loc = xs.shape[-1] // Pd

    xs_raw, bc_raw = xs, bc
    if mode == "decode":
        xs, cx = _causal_conv(xs, p["conv_x"], cache["conv_x"])
        bc, cbc = _causal_conv(bc, p["conv_bc"], cache["conv_bc"])
    else:
        xs = _causal_conv(xs, p["conv_x"])
        bc = _causal_conv(bc, p["conv_bc"])
    xs = jax.nn.silu(xs)
    bc = jax.nn.silu(bc)
    Bm, Cm = jnp.split(bc, 2, axis=-1)                 # (B,S,N) each

    xh = xs.reshape(B, S, h_loc, Pd)
    if mode == "decode":
        y_t, s_new = ssd_decode_step(cache["ssd"], xh[:, 0], dt[:, 0],
                                     p["a_log"], Bm[:, 0], Cm[:, 0])
        y = y_t[:, None]
        new_cache = {"conv_x": cx, "conv_bc": cbc, "ssd": s_new}
    else:
        impl = ("pallas" if cfg.attn_impl == "pallas" and mode == "train"
                else "xla")
        y, s_final = ssd_chunked(xh, dt, p["a_log"], Bm, Cm,
                                 chunk=cfg.ssm_chunk, impl=impl)
        new_cache = None
        if mode == "prefill":
            tail = lambda t: _tail_pad(t, K - 1)
            new_cache = {"conv_x": tail(xs_raw), "conv_bc": tail(bc_raw),
                         "ssd": s_final}

    y = y + xs.reshape(B, S, h_loc, Pd) * p["skip_d"][None, None, :, None]
    y = y.reshape(B, S, h_loc * Pd)
    y = rmsnorm(y * jax.nn.silu(z), p["gnorm"], cfg.norm_eps)  # gated norm
    w_out = ops.weight(p["w_out"], P(A.MODEL_AXIS, A.DATA_AXIS))
    out = y @ w_out                                    # partial over model
    return out, new_cache


def mamba2_cache_specs(cfg: ModelConfig, batch: int, tp: int,
                       bspec=A.DATA_AXIS):
    """Decode-cache ParamSpecs (per layer; caller stacks)."""
    d_in = cfg.ssm_expand * cfg.d_model
    H = d_in // cfg.ssm_head_dim
    N = cfg.ssm_state
    K = 4
    import jax.numpy as _jnp
    return {
        "conv_x": ParamSpec((batch, K - 1, d_in),
                            P(bspec, None, A.MODEL_AXIS), init="zeros"),
        "conv_bc": ParamSpec((batch, K - 1, 2 * N),
                             P(bspec, None, None), init="zeros"),
        "ssd": ParamSpec((batch, H, cfg.ssm_head_dim, N),
                         P(bspec, A.MODEL_AXIS, None, None), init="zeros",
                         dtype=_jnp.float32),
    }
