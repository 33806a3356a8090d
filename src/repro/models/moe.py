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
  (``jax.lax.ragged_dot``, or the ``stack_gmm`` kernel for a few rows in
  serving) over exactly those rows. No pair is dropped.
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

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
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


def moe_ffn(ops: Ops, p, x, cfg: ModelConfig, tokens_replicated: bool = False,
            layer=None):
    """p: {router:(d,E), wg:(held,d,f), wu:(held,d,f), wd:(held,f,d)};
    x: (T, d). With ``layer`` (an int32 scalar), ``wg``/``wu``/``wd`` are
    the whole layer stacks (L, held, ...) and this is layer ``layer`` of
    them (see ``dropless_experts``).

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
        if layer is not None:       # the capacity einsums fuse the slice
            p = {**p, **{w: p[w][layer] for w in EXPERT_WEIGHTS}}
        out, counts = capacity_experts(ops, p, x, topv, topi, cfg)
    else:
        out, counts = dropless_experts(ops, p, x, topv, topi, cfg, shard,
                                       layer=layer)

    # ---- load-balance aux (Switch): E * sum_e f_e * pbar_e ------------------
    f_e = jnp.zeros((E,), jnp.float32).at[topi.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(f_e * probs.mean(0))
    return out, aux, counts


EXPERT_WEIGHTS = ("wg", "wu", "wd")


def _expert_weights(ops: Ops, p, stacked: bool = False):
    lead = (None,) if stacked else ()
    return (ops.weight(p["wg"], P(*lead, A.MODEL_AXIS, A.DATA_AXIS, None)),
            ops.weight(p["wu"], P(*lead, A.MODEL_AXIS, A.DATA_AXIS, None)),
            ops.weight(p["wd"], P(*lead, A.MODEL_AXIS, None, A.DATA_AXIS)))


def dropless_experts(ops: Ops, p, x, topv, topi, cfg: ModelConfig,
                     shard: bool = False, layer=None):
    """This shard's held experts over exactly the rows routed to them.

    Without ``layer``, ``p["wg"]``/``p["wu"]`` are (n, d, f) and
    ``p["wd"]`` (n, f, d): one layer's n held experts (n local to this
    shard on a mesh), as a layer scan slices them out of the stack. With
    ``layer`` (an int32 scalar, the serving scans' layer index) they are
    the whole stacks (L, n, d, f) / (L, n, f, d), and the grouped matmuls
    read them where they lie (``stack_matmul``): over the L*n experts of
    the flattened stack, layer ``layer``'s n being experts layer*n ..
    layer*n + n - 1. A slice of a layer fed to the grouped matmul is a
    copy of all n experts, which a step that touches a few of them pays
    in full. Training keeps the sliced form: a stack closed over by the
    scan would accumulate a whole-stack gradient in every layer."""
    T, d = x.shape
    k = topi.shape[1]
    n = ops.local_experts(cfg.experts_held or cfg.n_experts)
    lo = ops.tp_index() * n if shard else 0
    wg, wu, wd = _expert_weights(ops, p, stacked=layer is not None)
    with jax.named_scope("moe.experts"):
        local = topi.reshape(-1) - lo                          # (T*k,)
        mine = (local >= 0) & (local < n)
        group = jnp.where(mine, local, n)          # pairs not held sort last
        order = jnp.argsort(group)                             # stable
        sizes = jnp.zeros((n + 1,), jnp.int32).at[group].add(1)[:n]
        rows = jnp.take(x, order // k, axis=0)                 # (T*k, d)
        if layer is None:
            mm = functools.partial(lax.ragged_dot, group_sizes=sizes)
        else:
            L = wg.shape[0]
            wg, wu, wd = (w.reshape(L * n, *w.shape[2:])
                          for w in (wg, wu, wd))
            mm = functools.partial(stack_matmul, sizes=sizes,
                                   first=layer * n)
        h = mm(rows, wg)
        u = mm(rows, wu)
        y = mm(jax.nn.silu(h) * u, wd)                         # (T*k, d)
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


#: at most this many rows, the grouped matmul over a stack is
#: ``stack_gmm`` on a TPU: every row times each touched expert costs less
#: than streaming that expert's weights while rows < peak FLOP/s / HBM
#: bytes/s (about 240 on a v5e); above it ``ragged_dot`` multiplies each
#: group's rows alone.
FEW_ROWS = 256
#: VMEM for one expert's (K, tn) weight block: two of them are in flight
_BLOCK_BYTES = 8 * 2 ** 20


def stack_matmul(rows, w, sizes, first):
    """Grouped matmul over a stack of experts: rows (M, K), sorted by
    group, times w (G, K, N), where groups first .. first+n-1 of ``w``
    take the first sum(sizes) rows, in order, and the rows past them come
    out zero (as ``lax.ragged_dot`` gives them). The stack is read where
    it lies: ``ragged_dot`` over all G groups, sizes zero outside the n,
    or, for a few rows on a TPU, ``stack_gmm``, which fetches only the
    touched experts' weights."""
    tn = _col_block(*w.shape[1:], w.dtype.itemsize)
    if rows.shape[0] > FEW_ROWS or tn is None:
        return _stack_ragged_dot(rows, w, sizes, first)
    return lax.platform_dependent(
        rows, w, sizes, first, default=_stack_ragged_dot,
        tpu=functools.partial(stack_gmm, block_n=tn))


def _stack_ragged_dot(rows, w, sizes, first):
    full = lax.dynamic_update_slice(jnp.zeros((w.shape[0],), jnp.int32),
                                    sizes, (first,))
    return lax.ragged_dot(rows, w, full)


def _col_block(K: int, N: int, itemsize: int):
    """The widest block of N columns (N itself, or a multiple of 128 that
    divides it) whose (K, tn) weights fit ``_BLOCK_BYTES``; None if none
    does."""
    for tn in [N] + [t for t in range(N - N % 128, 0, -128) if N % t == 0]:
        if K * tn * itemsize <= _BLOCK_BYTES:
            return tn
    return None


def _gmm_kernel(eid_ref, size_ref, start_ref, x_ref, w_ref, o_ref):
    e = pl.program_id(1)

    @pl.when(e == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    size = size_ref[e]

    @pl.when(size > 0)
    def _():
        y = jnp.dot(x_ref[...], w_ref[0], preferred_element_type=jnp.float32)
        row = lax.broadcasted_iota(jnp.int32, y.shape, 0)
        start = start_ref[e]
        o_ref[...] = jnp.where((row >= start) & (row < start + size),
                               y.astype(o_ref.dtype), o_ref[...])


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def stack_gmm(rows, w, sizes, first, *, block_n: int, interpret=False):
    """``stack_matmul`` as a Pallas kernel for a few rows: the grid walks
    (column block, group); each step multiplies all M rows by one
    expert's (K, block_n) weights and keeps the rows of its group. An
    empty group names the block of the last non-empty group before it
    (the first non-empty one where none is), so the pipeline fetches no
    weights for it: a layer's untouched experts are never read."""
    M, K = rows.shape
    N = w.shape[2]
    n = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    idx = jnp.arange(n, dtype=jnp.int32)
    last = lax.cummax(jnp.where(sizes > 0, idx, -1))
    eid = first + jnp.where(last >= 0, last,
                            jnp.argmax(sizes > 0).astype(jnp.int32))
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(N // block_n, n),
        in_specs=[pl.BlockSpec((M, K), lambda j, e, eid, s, st: (0, 0)),
                  pl.BlockSpec((1, K, block_n),
                               lambda j, e, eid, s, st: (eid[e], 0, j))],
        out_specs=pl.BlockSpec((M, block_n),
                               lambda j, e, eid, s, st: (0, j)))
    return pl.pallas_call(
        _gmm_kernel, grid_spec=grid, name="moe_stack_gmm",
        out_shape=jax.ShapeDtypeStruct((M, N), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=4 * _BLOCK_BYTES),
        interpret=interpret,
    )(eid, sizes, starts, rows, w)


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
