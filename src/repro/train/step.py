"""Step builders: wrap Model.loss / prefill / decode into compiled SPMD
steps on either distribution path.

- mpignite path: the whole step body (fwd, bwd, grad sync, optimizer) runs
  inside one ``shard_map``; every collective is an explicit PeerComm call
  (paper model). Parameters/optimizer state enter as local shards.
- gspmd path: the same body under ``jit`` with in/out shardings; XLA's
  SPMD partitioner inserts collectives.

Gradient clipping uses a sharding-aware global norm: each leaf's local
square-sum is psum'd only over the axes *present* in its PartitionSpec
(absent axes hold replicas -- summing them would double-count).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.common import tree_pspecs
from ..models.model import Model
from ..parallel import axes as A
from ..parallel.ops import ParallelConfig, ShardOps, make_ops
from . import compress as C
from .optim import Optimizer


def _flat_axes(spec, ndim):
    entries = tuple(spec) + (None,) * (ndim - len(spec))
    out = []
    for e in entries:
        if e is None:
            continue
        out.extend(e if isinstance(e, tuple) else (e,))
    return out


def global_grad_norm(ops, grads, pspecs):
    """Replication-aware global L2 norm (identical on every shard)."""
    total = jnp.float32(0.0)
    leaves, tdef = jax.tree.flatten(grads)
    specs = tdef.flatten_up_to(pspecs)
    for g, spec in zip(leaves, specs):
        sq = jnp.sum(g.astype(jnp.float32) ** 2)
        if isinstance(ops, ShardOps):
            axes_here = _flat_axes(spec, g.ndim)
            if A.MODEL_AXIS in axes_here and ops.tp > 1:
                sq = ops.comm_model.allreduce(sq)
            if A.DATA_AXIS in axes_here and ops.axes.data > 1:
                sq = ops.comm_data.allreduce(sq)
        total = total + sq
    return jnp.sqrt(total)


def make_train_step(model: Model, opt: Optimizer, mesh: Mesh,
                    global_batch: int,
                    use_compression: bool | None = None):
    """Returns (step_fn, state_pspecs). step_fn(params, opt_state, batch)
    -> (params, opt_state, metrics). opt_state includes 'ef' when
    cross-pod int8 compression is enabled."""
    pcfg = model.pcfg
    axes = model.axes
    compression = (pcfg.grad_compression == "int8"
                   if use_compression is None else use_compression)
    compression = compression and axes.pod > 1
    param_ps = model.pspecs
    opt_ps = opt.state_pspecs_from(model.specs)
    if compression:
        opt_ps = {**opt_ps, "ef": param_ps}

    def body(params, opt_state, batch):
        ops = make_ops(axes, pcfg)
        m = max(pcfg.microbatches, 1)

        def grad_of(b):
            return jax.value_and_grad(
                lambda p: model.loss(ops, p, b), has_aux=True)(params)

        if m == 1:
            (loss, metrics), grads = grad_of(batch)
        else:
            # gradient accumulation: scan over microbatches; each micro
            # loss is a global mean, so the accumulated grad averages by m.
            mb = jax.tree.map(
                lambda x: x.reshape((m, x.shape[0] // m) + x.shape[1:]),
                batch)

            def acc_step(acc, b):
                (l, met), g = grad_of(b)
                acc = jax.tree.map(
                    lambda a, gi: a + gi.astype(jnp.float32) / m, acc, g)
                return acc, (l, met)

            acc0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            from ..core.comm import cost_scope
            with cost_scope(m):
                grads, (losses, mets) = jax.lax.scan(acc_step, acc0, mb)
            metrics = {"nll_sum": jnp.sum(mets["nll_sum"]),
                       "n_valid": jnp.sum(mets["n_valid"]),
                       "aux": jnp.mean(mets["aux"])}
        ef = opt_state.get("ef") if compression else None
        comp_fn = C.pod_allreduce_int8 if compression else None
        grads, ef_new = ops.sync_grads(grads, param_ps, compress=comp_fn,
                                       ef=ef)
        gnorm = (global_grad_norm(ops, grads, param_ps)
                 if isinstance(ops, ShardOps)
                 else jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                                   for g in jax.tree.leaves(grads))))
        clip = opt.cfg.grad_clip
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12)) \
            if clip else jnp.float32(1.0)
        grads = jax.tree.map(lambda g: (g.astype(jnp.float32) * scale), grads)
        inner = ({k: v for k, v in opt_state.items() if k != "ef"}
                 if compression else opt_state)
        new_params, new_opt = opt.update(grads, inner, params)
        if compression:
            new_opt = {**new_opt, "ef": ef_new}
        # metrics: reduce the local sums to global means for reporting
        nll, nv = metrics["nll_sum"], metrics["n_valid"]
        if isinstance(ops, ShardOps):
            nll = ops.comm_data.allreduce(nll)
            if ops.comm_pod is not None:
                nll = ops.comm_pod.allreduce(nll)
            nv = nv * ops.dp
        out_metrics = {"loss": nll / nv, "gnorm": gnorm,
                       "aux": metrics["aux"],
                       "step": new_opt["step"].astype(jnp.float32)}
        return new_params, new_opt, out_metrics

    _, batch_ps = model.batch_specs(global_batch, 1)
    metrics_ps = {"loss": P(), "gnorm": P(), "aux": P(), "step": P()}

    if pcfg.path == "mpignite":
        smapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(param_ps, opt_ps, batch_ps),
            out_specs=(param_ps, opt_ps, metrics_ps),
            check_vma=False)
        step = jax.jit(smapped, donate_argnums=(0, 1))
    else:
        ns = lambda tree: jax.tree.map(
            lambda s: NamedSharding(mesh, s), tree)
        step = jax.jit(body,
                       in_shardings=(ns(param_ps), ns(opt_ps), ns(batch_ps)),
                       out_shardings=(ns(param_ps), ns(opt_ps),
                                      ns(metrics_ps)),
                       donate_argnums=(0, 1))
    return step, {"params": param_ps, "opt": opt_ps, "batch": batch_ps}


def init_opt_state(model: Model, opt: Optimizer, params,
                   use_compression: bool = False):
    state = opt.init(params)
    if use_compression and model.axes.pod > 1:
        state = {**state, "ef": C.ef_zeros_like(params)}
    return state


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------

def make_prefill_step(model: Model, mesh: Mesh, global_batch: int,
                      s_max: int):
    """Sequence-parallelism is disabled for serving steps (a 1-token decode
    cannot be sequence-sharded; prefill follows for cache-layout parity)."""
    pcfg = model.pcfg.replace(sequence_parallel=False)
    axes = model.axes
    serve_model = _with_pcfg(model, pcfg)

    def body(params, batch):
        ops = make_ops(axes, pcfg)
        return serve_model.prefill(ops, params, batch, s_max=s_max)

    param_ps = model.pspecs
    _, batch_ps = model.batch_specs(global_batch, 1)
    cache_ps = tree_pspecs(serve_model.cache_specs(global_batch, s_max))
    logits_ps = P(_first(batch_ps), None)
    if pcfg.path == "mpignite":
        smapped = jax.shard_map(body, mesh=mesh,
                                in_specs=(param_ps, batch_ps),
                                out_specs=(logits_ps, cache_ps),
                                check_vma=False)
        return jax.jit(smapped)
    ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree)
    return jax.jit(body, in_shardings=(ns(param_ps), ns(batch_ps)),
                   out_shardings=(ns(logits_ps), ns(cache_ps)))


def make_decode_step(model: Model, mesh: Mesh, batch: int, s_max: int):
    pcfg = model.pcfg.replace(sequence_parallel=False)
    axes = model.axes
    serve_model = _with_pcfg(model, pcfg)

    def body(params, caches, tokens, pos):
        ops = make_ops(axes, pcfg)
        return serve_model.decode(ops, params, caches, tokens, pos)

    param_ps = model.pspecs
    cache_ps = tree_pspecs(model.cache_specs(batch, s_max))
    bsp = model._bspec(batch)
    tok_ps = P(bsp, None)
    pos_ps = P(bsp)
    logits_ps = P(bsp, None)
    if pcfg.path == "mpignite":
        smapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(param_ps, cache_ps, tok_ps, pos_ps),
            out_specs=(logits_ps, cache_ps), check_vma=False)
        return jax.jit(smapped, donate_argnums=(1,))
    ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree)
    return jax.jit(body,
                   in_shardings=(ns(param_ps), ns(cache_ps), ns(tok_ps),
                                 ns(pos_ps)),
                   out_shardings=(ns(logits_ps), ns(cache_ps)),
                   donate_argnums=(1,))


def _first(batch_ps):
    spec = batch_ps[next(iter(batch_ps))]
    return tuple(spec)[0] if len(tuple(spec)) else None


def _with_pcfg(model: Model, pcfg: ParallelConfig) -> Model:
    m = object.__new__(Model)
    m.__dict__.update(model.__dict__)
    m.pcfg = pcfg
    return m
