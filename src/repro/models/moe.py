"""Mixture-of-Experts FFN: routing over all of a layer's experts, the share
of them this layer holds, and expert parallelism over the `model` axis.

Routing is a softmax over the router's ``n_experts`` outputs and a greedy
top-k, renormalised over the k only when ``cfg.norm_topk_prob``. The layer
holds ``cfg.experts_held`` experts (0: all), the block [0, held) of the
router's outputs; on a mesh each model shard holds its slice of that
block, [r*n, r*n + n) for shard r. A token-expert pair whose expert is not
held contributes nothing: what the absent experts would add lies on other
chips of an expert-parallel deployment, and is left out.

Two dispatches:

- dropless, where the layer runs without an exchange (one model shard, or
  tokens replicated over the shards, as in decode): the pairs routed to
  this shard's experts are sorted by expert and run as grouped matmuls
  (``jax.lax.ragged_dot``) over exactly those rows. No pair is dropped.
- capacity (the sharded all-to-all of training, and the gspmd path):
  sort-based placement into a capacity-bounded (held, C, d) buffer,
  exchanged with a single ``comm.alltoall`` on the model axis (the paper's
  all-to-all composed from PeerComm primitives on the mpignite path); the
  inverse all-to-all brings expert outputs home. Overflowed pairs are
  dropped (their residual passes through), standard for capacity-factor
  routing.

Token-shape contract: ``x`` is (T, d) -- the *local* token slice under the
mpignite path (sequence-parallel sharding over `model`), the global token set
under gspmd. ``moe_ffn`` returns (y, aux_loss, counts) with y matching x and
``counts`` int32 scalars over the experts held here: ``routed_rows``, the
pairs routed to them; ``expert_rows``, the rows their matmuls ran
(dropless: the routed rows; capacity: the whole buffer);
``experts_touched``, the held experts given at least one row; and
``dropped_rows``, routed pairs dropped for capacity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..parallel import axes as A
from ..parallel.ops import Ops, ShardOps
from .common import ModelConfig


def capacity(T: int, k: int, E: int, factor: float) -> int:
    c = int(T * k / E * factor)
    return max(A.pad_to(c, 4), 4)


def route(ops: Ops, p, x, cfg: ModelConfig):
    """Router probabilities over all experts and the top-k of each token:
    (probs (T, E), weights (T, k), experts (T, k))."""
    with jax.named_scope("moe.route"):
        router = ops.weight(p["router"], P(A.DATA_AXIS, None))
        logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)                # (T, E)
        topv, topi = lax.top_k(probs, cfg.top_k)               # (T, k)
        if cfg.norm_topk_prob:
            topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    return probs, topv, topi


def moe_ffn(ops: Ops, p, x, cfg: ModelConfig, tokens_replicated: bool = False):
    """p: {router:(d,E), wg:(held,d,f), wu:(held,d,f), wd:(held,f,d)};
    x: (T, d).

    tokens_replicated=True (decode path): every model shard sees the same
    tokens; each shard runs only its local experts' rows, and a model-axis
    psum combines -- no all-to-all (a 1-token step cannot be
    sequence-sharded)."""
    E, k = cfg.n_experts, cfg.top_k
    T = x.shape[0]
    probs, topv, topi = route(ops, p, x, cfg)
    shard = isinstance(ops, ShardOps) and ops.tp > 1
    exchange = (shard and not tokens_replicated) or \
        (not isinstance(ops, ShardOps) and ops.tp > 1)
    if exchange:
        out, counts = capacity_experts(ops, p, x, topv, topi, cfg)
    else:
        out, counts = dropless_experts(ops, p, x, topv, topi, cfg, shard)

    # ---- load-balance aux (Switch): E * sum_e f_e * pbar_e ------------------
    f_e = jnp.zeros((E,), jnp.float32).at[topi.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(f_e * probs.mean(0))
    return out, aux, counts


def _expert_weights(ops: Ops, p):
    return (ops.weight(p["wg"], P(A.MODEL_AXIS, A.DATA_AXIS, None)),
            ops.weight(p["wu"], P(A.MODEL_AXIS, A.DATA_AXIS, None)),
            ops.weight(p["wd"], P(A.MODEL_AXIS, None, A.DATA_AXIS)))


def dropless_experts(ops: Ops, p, x, topv, topi, cfg: ModelConfig,
                     shard: bool = False):
    """This shard's held experts over exactly the rows routed to them."""
    T, d = x.shape
    k = topi.shape[1]
    n = ops.local_experts(cfg.experts_held or cfg.n_experts)
    lo = ops.tp_index() * n if shard else 0
    wg, wu, wd = _expert_weights(ops, p)
    with jax.named_scope("moe.experts"):
        local = topi.reshape(-1) - lo                          # (T*k,)
        mine = (local >= 0) & (local < n)
        group = jnp.where(mine, local, n)          # pairs not held sort last
        order = jnp.argsort(group)                             # stable
        sizes = jnp.zeros((n + 1,), jnp.int32).at[group].add(1)[:n]
        rows = jnp.take(x, order // k, axis=0)                 # (T*k, d)
        h = lax.ragged_dot(rows, wg, sizes)
        u = lax.ragged_dot(rows, wu, sizes)
        y = lax.ragged_dot(jax.nn.silu(h) * u, wd, sizes)      # (T*k, d)
    with jax.named_scope("moe.combine"):
        # back to (token, choice) order: a gather, not a scatter-add
        y = jnp.take(y, jnp.argsort(order), axis=0).reshape(T, k, d)
        w = jnp.where(mine, topv.reshape(-1), 0.0).reshape(T, k)
        y = jnp.where(mine.reshape(T, k, 1), y.astype(jnp.float32), 0.0)
        out = jnp.einsum("tkd,tk->td", y, w).astype(x.dtype)
    routed = jnp.sum(sizes)
    counts = {"routed_rows": routed, "expert_rows": routed,
              "experts_touched": jnp.sum(sizes > 0, dtype=jnp.int32),
              "dropped_rows": jnp.int32(0)}
    if shard:
        out = ops.tp_psum(out)
        counts = {name: ops.tp_psum(v) for name, v in counts.items()}
    return out, counts


def capacity_experts(ops: Ops, p, x, topv, topi, cfg: ModelConfig):
    """The held experts through a capacity-bounded buffer and, on a
    sharded mesh, the all-to-all exchange; overflow pairs are dropped."""
    E, k = cfg.n_experts, cfg.top_k
    held = cfg.experts_held or E
    T, d = x.shape
    C = capacity(T, k, E, cfg.capacity_factor)
    shard = isinstance(ops, ShardOps) and ops.tp > 1

    # ---- sort-based dispatch ------------------------------------------------
    flat_e = topi.reshape(-1)                                  # (T*k,)
    mine = flat_e < held
    group = jnp.where(mine, flat_e, held)
    order = jnp.argsort(group)                                 # stable
    sorted_e = group[order]
    counts = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k, dtype=jnp.int32) - starts[sorted_e]
    keep = (pos < C) & (sorted_e < held)
    token_of = order // k
    src = jnp.take(x, token_of, axis=0)                        # (T*k, d)
    slot = jnp.where(keep, sorted_e * C + pos, held * C)       # overflow slot
    buf = jnp.zeros((held * C + 1, d), x.dtype).at[slot].set(src)[:held * C]
    buf = buf.reshape(held, C, d)

    # ---- expert exchange (paper's alltoall on the model axis) --------------
    if shard:
        recv = ops.tp_all_to_all(buf, split_dim=0, concat_dim=1)
        # (e_loc, tp*C, d): this shard's experts, everyone's tokens
    else:
        recv = ops.constrain(buf, P(A.MODEL_AXIS, None, None))

    # ---- expert FFN ---------------------------------------------------------
    wg, wu, wd = _expert_weights(ops, p)
    with jax.named_scope("moe.experts"):
        h = jnp.einsum("ecd,edf->ecf", recv, wg)
        u = jnp.einsum("ecd,edf->ecf", recv, wu)
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, wd)
        y = ops.constrain(y, P(A.MODEL_AXIS, None, None))

    # ---- return exchange + combine -----------------------------------------
    with jax.named_scope("moe.combine"):
        if shard:
            y = ops.tp_all_to_all(y, split_dim=1, concat_dim=0)  # (held, C, d)
        y = y.reshape(held * C, d)
        y = jnp.concatenate([y, jnp.zeros((1, d), y.dtype)], 0)  # overflow
        gathered = jnp.take(y, slot, axis=0)                     # (T*k, d)
        w_sorted = topv.reshape(-1)[order]
        contrib = gathered * jnp.where(keep, w_sorted, 0.0)[:, None] \
            .astype(y.dtype)
        out = jnp.zeros((T, d), x.dtype).at[token_of].add(contrib)
    routed = jnp.sum(mine, dtype=jnp.int32)
    return out, {"routed_rows": routed,
                 "expert_rows": jnp.int32(held * C),
                 "experts_touched": jnp.sum(counts[:held] > 0,
                                            dtype=jnp.int32),
                 "dropped_rows": routed - jnp.sum(keep, dtype=jnp.int32)}


def moe_param_specs(cfg: ModelConfig):
    """ParamSpecs for one MoE layer's router and held experts (to be
    `stacked`): the router keeps all ``n_experts`` outputs."""
    from .common import ParamSpec
    d, E, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    held = cfg.experts_held or E
    return {
        "router": ParamSpec((d, E), P(A.DATA_AXIS, None)),
        "wg": ParamSpec((held, d, f), P(A.MODEL_AXIS, A.DATA_AXIS, None)),
        "wu": ParamSpec((held, d, f), P(A.MODEL_AXIS, A.DATA_AXIS, None)),
        "wd": ParamSpec((held, f, d), P(A.MODEL_AXIS, None, A.DATA_AXIS),
                        init="scaled", fan_in=cfg.n_layers),
    }
