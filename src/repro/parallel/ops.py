"""The two distribution paths behind one model-code interface.

Model code is written once against ``Ops``; the path is selected by
``ParallelConfig.path``:

- ``ShardOps`` ("mpignite" path): the program is a ``shard_map`` body and
  every distributed movement is an *explicit* ``PeerComm`` call -- the
  paper's model, with its ``linear`` (phase-1 master relay), ``ring``
  (phase-2 peer-to-peer) and ``native`` (beyond-paper XLA collectives)
  backends all available per communicator.

- ``GlobalOps`` ("gspmd" path): the same model code runs on global arrays
  under ``jit``; collective insertion is delegated to the XLA SPMD
  partitioner via sharding constraints. This is the beyond-paper ceiling
  reference for the §Perf comparison.

Shape contract: under ``ShardOps`` every tensor a model function touches is
the *local shard*; under ``GlobalOps`` it is the full array. All head/ffn
counts therefore flow through ``ops.local_*`` helpers instead of config
fields.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.comm import PeerComm
from . import axes as A


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """User-facing knobs for the distribution layer."""
    path: str = "mpignite"            # "mpignite" | "gspmd"
    backend: str = "native"           # PeerComm backend (mpignite path)
    sequence_parallel: bool = True    # keep activations seq-sharded between blocks
    fsdp: bool = True                 # ZeRO-3 parameter sharding over `data`
    remat: str = "block"              # "none" | "block" | "full"
    grad_compression: str = "none"    # "none" | "int8" (cross-pod allreduce)
    microbatches: int = 1             # grad-accumulation chunks per step

    def replace(self, **kw) -> "ParallelConfig":
        return dataclasses.replace(self, **kw)


class Ops:
    """Abstract distribution interface (see module docstring)."""

    axes: A.MeshAxes
    pcfg: ParallelConfig

    # ---- static sizes ----------------------------------------------------
    @property
    def tp(self) -> int:
        return self.axes.model

    @property
    def dp(self) -> int:
        return self.axes.dp_total

    def local_heads(self, n_padded: int) -> int:
        raise NotImplementedError

    def local_experts(self, n_experts: int) -> int:
        raise NotImplementedError

    # ---- weights ----------------------------------------------------------
    def weight(self, w: jax.Array, spec: P) -> jax.Array:
        """Materialize a weight for compute: gather FSDP (`data`) dims,
        keep TP (`model`) dims as-is."""
        raise NotImplementedError

    # ---- activation collectives (model/TP axis) ---------------------------
    def tp_psum(self, x):
        raise NotImplementedError

    def tp_reduce_scatter(self, x, dim: int):
        raise NotImplementedError

    def tp_all_gather(self, x, dim: int):
        raise NotImplementedError

    def tp_all_to_all(self, x, split_dim: int, concat_dim: int):
        raise NotImplementedError

    def tp_psum_scalar(self, x):
        """psum for scalars/small stats on the model axis."""
        raise NotImplementedError

    def dp_mean_scalar(self, x):
        """Mean over the full data-parallel extent (data [+ pod])."""
        raise NotImplementedError

    def tp_index(self):
        """This shard's model-axis index (0 under GlobalOps)."""
        raise NotImplementedError

    # ---- layout hints ------------------------------------------------------
    def constrain(self, x, spec: P):
        """Sharding hint; identity under ShardOps (layout already explicit)."""
        return x

    def seq_shard(self, x, dim: int = 1):
        """Sequence-parallel transition: scatter the sequence dim over
        `model` (no-op when sequence_parallel is off)."""
        raise NotImplementedError

    def seq_unshard(self, x, dim: int = 1):
        raise NotImplementedError

    def seq_slice(self, x, dim: int = 1):
        """Like seq_shard but for *replicated-computed* full tensors:
        take this shard's slice (no reduction)."""
        raise NotImplementedError


class ShardOps(Ops):
    """Explicit-communication path built on the paper's PeerComm."""

    def __init__(self, axes: A.MeshAxes, pcfg: ParallelConfig):
        self.axes = axes
        self.pcfg = pcfg
        be = pcfg.backend
        self.comm_model = PeerComm.world(A.MODEL_AXIS, axes.model, backend=be)
        self.comm_data = PeerComm.world(A.DATA_AXIS, axes.data, backend=be)
        self.comm_pod = (PeerComm.world(A.POD_AXIS, axes.pod, backend=be)
                         if axes.pod > 1 else None)

    # ---- static sizes ----------------------------------------------------
    def local_heads(self, n_padded: int) -> int:
        return A.divisible(n_padded, self.tp, "padded heads") // self.tp

    def local_experts(self, n_experts: int) -> int:
        return A.divisible(n_experts, self.tp, "experts") // self.tp

    # ---- weights ----------------------------------------------------------
    def weight(self, w, spec: P):
        if not self.pcfg.fsdp:
            return w
        entries = tuple(spec) + (None,) * (w.ndim - len(spec))
        for dim, entry in enumerate(entries):
            names = entry if isinstance(entry, tuple) else (entry,)
            if A.DATA_AXIS in names and self.axes.data > 1:
                w = self.comm_data.allgather(w, axis=dim, tiled=True)
        return w

    # ---- activation collectives -------------------------------------------
    def tp_psum(self, x):
        return self.comm_model.allreduce(x) if self.tp > 1 else x

    def tp_reduce_scatter(self, x, dim: int):
        return (self.comm_model.reducescatter(x, axis=dim)
                if self.tp > 1 else x)

    def tp_all_gather(self, x, dim: int):
        return (self.comm_model.allgather(x, axis=dim, tiled=True)
                if self.tp > 1 else x)

    def tp_all_to_all(self, x, split_dim: int, concat_dim: int):
        return (self.comm_model.alltoall(x, split_axis=split_dim,
                                         concat_axis=concat_dim)
                if self.tp > 1 else x)

    def tp_psum_scalar(self, x):
        return self.tp_psum(x)

    def dp_mean_scalar(self, x):
        if self.axes.data > 1:
            x = self.comm_data.allreduce(x)
        if self.comm_pod is not None:
            x = self.comm_pod.allreduce(x)
        return x / self.dp

    def tp_index(self):
        return lax.axis_index(A.MODEL_AXIS) if self.tp > 1 else jnp.int32(0)

    # ---- layout -------------------------------------------------------------
    def seq_shard(self, x, dim: int = 1):
        if self.pcfg.sequence_parallel and self.tp > 1:
            return self.tp_reduce_scatter(x, dim)
        return self.tp_psum(x)

    def seq_unshard(self, x, dim: int = 1):
        if self.pcfg.sequence_parallel and self.tp > 1:
            return self.tp_all_gather(x, dim)
        return x

    def seq_slice(self, x, dim: int = 1):
        if self.pcfg.sequence_parallel and self.tp > 1:
            c = x.shape[dim] // self.tp
            return jax.lax.dynamic_slice_in_dim(x, self.tp_index() * c, c,
                                                axis=dim)
        return x

    # ---- gradient sync (called by the train step after jax.grad) ------------
    def sync_grads(self, grads, specs, compress=None, ef=None):
        """Reduce gradients across every mesh axis *absent* from a param's
        spec. FSDP dims are already reduce-scattered by the transpose of the
        just-in-time all-gather; what remains is (a) the TP group for
        replicated params (norms, routers) and (b) the cross-pod replicas.
        ``compress(comm, g, ef_leaf) -> (g, ef_new)`` optionally wraps the
        cross-pod allreduce (int8 + error feedback -- train/compress.py).
        Returns (grads, ef_new_or_None). All reductions are sums: the loss
        already carries the 1/dp_total factor, so summed shard losses
        telescope to the global mean."""
        leaves_g, tdef = jax.tree.flatten(grads)
        leaves_s = tdef.flatten_up_to(specs)
        leaves_e = (tdef.flatten_up_to(ef) if ef is not None
                    else [None] * len(leaves_g))
        out_g, out_e = [], []
        for g, spec, e in zip(leaves_g, leaves_s, leaves_e):
            entries = tuple(spec) + (None,) * (g.ndim - len(spec))
            flat = [n for ent in entries if ent is not None
                    for n in (ent if isinstance(ent, tuple) else (ent,))]
            if A.MODEL_AXIS not in flat and self.tp > 1:
                g = self.comm_model.allreduce(g)
            if A.DATA_AXIS not in flat and self.axes.data > 1:
                g = self.comm_data.allreduce(g)
            if self.comm_pod is not None:
                if compress is not None:
                    g, e = compress(self.comm_pod, g, e)
                else:
                    g = self.comm_pod.allreduce(g)
            out_g.append(g)
            out_e.append(e)
        grads = jax.tree.unflatten(tdef, out_g)
        ef_new = jax.tree.unflatten(tdef, out_e) if ef is not None else None
        return grads, ef_new


class GlobalOps(Ops):
    """GSPMD path: global arrays + sharding constraints, XLA partitions."""

    def __init__(self, axes: A.MeshAxes, pcfg: ParallelConfig):
        self.axes = axes
        self.pcfg = pcfg

    def local_heads(self, n_padded: int) -> int:
        return n_padded

    def local_experts(self, n_experts: int) -> int:
        return n_experts

    def weight(self, w, spec: P):
        return w

    def tp_psum(self, x):
        return x

    def tp_reduce_scatter(self, x, dim: int):
        return x

    def tp_all_gather(self, x, dim: int):
        return x

    def tp_all_to_all(self, x, split_dim: int, concat_dim: int):
        return x

    def tp_psum_scalar(self, x):
        return x

    def dp_mean_scalar(self, x):
        return x

    def tp_index(self):
        return jnp.int32(0)

    def constrain(self, x, spec: P):
        if self.axes.n_devices > 1:
            return lax.with_sharding_constraint(x, spec)
        return x

    def seq_shard(self, x, dim: int = 1):
        if self.pcfg.sequence_parallel and self.tp > 1:
            spec = [None] * x.ndim
            spec[0] = (A.POD_AXIS, A.DATA_AXIS) if self.axes.pod > 1 else A.DATA_AXIS
            spec[dim] = A.MODEL_AXIS
            return self.constrain(x, P(*spec))
        return x

    def seq_unshard(self, x, dim: int = 1):
        return x

    def seq_slice(self, x, dim: int = 1):
        return x

    def sync_grads(self, grads, specs, compress=None, ef=None):
        # GSPMD reduces via partitioning of the global graph
        return grads, (ef if ef is not None else None)


def make_ops(axes: A.MeshAxes, pcfg: ParallelConfig) -> Ops:
    if pcfg.path == "mpignite":
        return ShardOps(axes, pcfg)
    if pcfg.path == "gspmd":
        return GlobalOps(axes, pcfg)
    raise ValueError(f"unknown parallel path {pcfg.path!r}")


# ---------------------------------------------------------------------------
# Remat policies applied to the per-layer body inside the layer scan.
# ---------------------------------------------------------------------------

def remat_wrap(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "block":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if policy == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    raise ValueError(f"unknown remat policy {policy!r}")
