"""PeerComm -- MPIgnite's SparkComm adapted to SPMD JAX ("cluster mode").

A ``PeerComm`` spans one mesh axis (optionally restricted to equal-size
rank groups, the result of ``split``) and exposes the paper's communicator
API inside ``shard_map``/``jit``. Three interchangeable backends implement
every collective:

- ``linear``  -- the paper's phase-1 implementation: every byte relays
                 through a master/root. Realized in SPMD as full-buffer
                 rotate/relay chains with the same wire-byte and
                 serialization structure (see DESIGN.md section 10).
- ``ring``    -- the paper's phase-2 true peer-to-peer mode: chunked
                 ring reduce-scatter/all-gather composed from
                 ``lax.ppermute`` (ICI collective-permute).
- ``native``  -- beyond-paper: XLA's fused collectives (psum/all_gather/
                 psum_scatter/all_to_all), overlappable by the compiler's
                 latency-hiding scheduler.

Every backend logs the analytic bytes and steps of each collective it
issues to a trace-time ``CostLog``, and as a tracer instant when tracing
is on, so a test or a trace can read what a program moves.

Restrictions relative to the Spark runtime (adaptation, not omission --
DESIGN.md section 2): routing is static (trace-time), receive-side
buffering does not exist (a p2p op is a rendezvous), and user reduction
functions must be elementwise-associative/commutative.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from . import groups as G
from .matching import Request
from .obs.trace import process_tracer

# ---------------------------------------------------------------------------
# Cost logging
# ---------------------------------------------------------------------------

_COST_LOG: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "mpignite_cost_log", default=None)
_COST_MULT: contextvars.ContextVar[int] = contextvars.ContextVar(
    "mpignite_cost_mult", default=1)
_COST_OVERLAP: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "mpignite_cost_overlap", default=False)


@contextlib.contextmanager
def cost_log():
    """Collect a CollectiveCost record for every comm call traced while the
    context is active (use around ``jax.eval_shape``/``.lower()``)."""
    log: list[G.CollectiveCost] = []
    tok = _COST_LOG.set(log)
    try:
        yield log
    finally:
        _COST_LOG.reset(tok)


@contextlib.contextmanager
def cost_scope(multiplier: int):
    """Scale costs logged inside (e.g. a ``lax.scan`` body traced once but
    executed ``multiplier`` times). Nests multiplicatively."""
    tok = _COST_MULT.set(_COST_MULT.get() * int(multiplier))
    try:
        yield
    finally:
        _COST_MULT.reset(tok)


def _log(op: str, backend: str, nbytes: int, steps: int) -> None:
    log = _COST_LOG.get()
    if log is not None:
        mult = _COST_MULT.get()
        log.append(G.CollectiveCost(op, backend, int(nbytes) * mult,
                                    int(steps) * mult,
                                    overlap=_COST_OVERLAP.get()))
    tracer = process_tracer()       # None unless $MPIGNITE_TRACE is set
    if tracer is not None:
        # SPMD collectives are priced at trace time, not observed at run
        # time (they live inside jit); mirror the analytic record as an
        # instant event so a traced session shows all three modes.
        tracer.instant(op, "spmd",
                       {"backend": backend,
                        "nbytes": int(nbytes) * _COST_MULT.get(),
                        "steps": int(steps) * _COST_MULT.get(),
                        "overlap": _COST_OVERLAP.get()})


@contextlib.contextmanager
def _overlap_scope():
    """Everything logged inside was issued through a nonblocking wrapper:
    mark it overlappable, since XLA's latency-hiding scheduler is free to
    move it under compute."""
    tok = _COST_OVERLAP.set(True)
    try:
        yield
    finally:
        _COST_OVERLAP.reset(tok)


_REDUCERS = {
    "add": (lax.psum, jnp.add),
    "max": (lax.pmax, jnp.maximum),
    "min": (lax.pmin, jnp.minimum),
}


def _resolve_op(op) -> tuple[Callable | None, Callable]:
    """-> (native collective or None, elementwise combine fn)."""
    if callable(op):
        return None, op
    if op in _REDUCERS:
        return _REDUCERS[op]
    raise ValueError(f"unknown reduction {op!r}; pass 'add'/'max'/'min' or a "
                     "binary elementwise function")


@dataclasses.dataclass(frozen=True)
class PeerComm:
    """SPMD communicator over mesh axis ``axis`` (paper's SparkComm)."""
    axis: str
    axis_size: int
    backend: str = "native"
    groups: G.Groups | None = None          # None => single world group
    ctx: int = 0

    # -- construction -------------------------------------------------------
    @staticmethod
    def world(axis: str, axis_size: int, backend: str = "native") -> "PeerComm":
        return PeerComm(axis, axis_size, backend, None, 0)

    def _groups(self) -> G.Groups:
        return (self.groups if self.groups is not None
                else G.world_groups(self.axis_size))

    @property
    def size(self) -> int:
        """Static size of (each) group -- the communicator size."""
        return len(self._groups()[0])

    def get_size(self) -> int:
        return self.size

    def with_backend(self, backend: str) -> "PeerComm":
        return dataclasses.replace(self, backend=backend)

    @property
    def _algo(self) -> str:
        """Collective algorithm after alias resolution: the message
        runtimes' ``segmented`` backend maps to ``ring`` here -- the SPMD
        ring collectives are already chunked (reduce-scatter/all-gather)
        at trace time, so segmentation is a no-op refinement and one
        closure text stays valid across all three modes."""
        if self.backend in ("segmented", "segmented-ring"):
            return "ring"
        return self.backend

    # -- traced introspection -------------------------------------------------
    def axis_index(self):
        return lax.axis_index(self.axis)

    def rank(self):
        """Traced comm rank of the calling program instance."""
        if self.groups is None:
            return lax.axis_index(self.axis)
        table = jnp.asarray(G.comm_rank_table(self._groups(), self.axis_size),
                            dtype=jnp.int32)
        return table[lax.axis_index(self.axis)]

    def get_rank(self):
        return self.rank()

    # -- split ------------------------------------------------------------------
    def split(self, colors: Sequence[int], keys: Sequence[int] | None = None
              ) -> "PeerComm":
        """MPI_Comm_split with *static* color/key tables indexed by comm rank
        (trace-time analogue of the paper's runtime color exchange; the
        LocalComm backend performs the real message-based exchange). All
        resulting color groups must be equal-size (SPMD restriction)."""
        if keys is None:
            keys = list(range(self.size))
        per_color = G.split_groups(self._groups(), list(colors), list(keys))
        merged: list[tuple[int, ...]] = []
        for color in sorted(per_color):
            merged.extend(per_color[color])
        merged_t = tuple(merged)
        G.validate_groups(merged_t, self.axis_size)
        return dataclasses.replace(
            self, groups=merged_t, ctx=G.context_id(merged_t, self.ctx))

    # -- point-to-point -----------------------------------------------------------
    def _ppermute(self, x, pairs_axis: list[tuple[int, int]], op: str = "p2p"):
        x = jnp.asarray(x)
        _log(op, self.backend, x.nbytes, 1)
        return lax.ppermute(x, self.axis, pairs_axis)

    def p2p(self, x, pairs: Sequence[tuple[int, int]], tag: int = 0):
        """Static sendrecv pattern: ``pairs`` are (src, dst) in comm-rank
        space; context isolation (no cross-group messages) is enforced at
        trace time. Ranks not named as a destination receive zeros."""
        del tag  # structural in SPMD; kept for API parity with the paper
        axis_pairs = G.p2p_perm(self._groups(), list(pairs), self.axis_size)
        return self._ppermute(x, axis_pairs)

    def shift(self, x, k: int = 1):
        """Ring shift by k within every group (the PP/ring primitive):
        rank r's value goes to rank (r+k) mod P."""
        return self._ppermute(x, G.ring_perm(self._groups(), k))

    # -- collectives ----------------------------------------------------------------
    def barrier(self):
        """Cross-group sync point; returns a (traced) zero token."""
        return self.allreduce(jnp.zeros((), jnp.int32), "add")

    def _native_groups_ok(self) -> bool:
        """XLA's SPMD collectives accept axis_index_groups under jit, but
        shard_map's psum/pmax rules do not implement them (verified on
        jax 0.8). Split communicators therefore realize `native` calls
        with the ring algorithms (identical wire bytes; the fused-overlap
        advantage only ever applied to whole-axis collectives anyway)."""
        return self.groups is None

    def allreduce(self, x, op="add", *, tag: int = 0):
        del tag
        x = jnp.asarray(x)
        if self.size == 1:
            return x
        native, combine = _resolve_op(op)
        if self._algo == "native" and native is not None \
                and self._native_groups_ok():
            _log("allreduce", "native",
                 2 * x.nbytes * (self.size - 1) // self.size,
                 2 * (self.size - 1))
            return native(x, self.axis, axis_index_groups=self._axis_groups())
        if self._algo in ("native", "ring"):
            return self._ring_allreduce(x, combine)
        return self._linear_allreduce(x, combine)

    def broadcast(self, x, root: int = 0):
        x = jnp.asarray(x)
        if self.size == 1:
            return x
        if self._algo == "native" and self._native_groups_ok():
            work = x.astype(jnp.int32) if x.dtype == jnp.bool_ else x
            sel = jnp.where(self.rank() == root, work, jnp.zeros_like(work))
            _log("broadcast", "native", x.nbytes, 1)
            out = lax.psum(sel, self.axis, axis_index_groups=self._axis_groups())
            return out.astype(x.dtype)
        # ring / linear: pass-along relay from root ((P-1) full-size steps --
        # under `linear` the root IS the paper's master, so relay == phase-1).
        return self._relay_from(x, root)

    def allgather(self, x, *, axis: int = 0, tiled: bool = False):
        """Gather per-rank contributions. ``tiled=False`` stacks a new
        leading group dimension at position ``axis``; ``tiled=True``
        concatenates along ``axis``."""
        x = jnp.asarray(x)
        if self.size == 1:
            return x if tiled else jnp.expand_dims(x, axis)
        if self._algo == "native" and self._native_groups_ok():
            _log("allgather", "native", x.nbytes * (self.size - 1),
                 self.size - 1)
            return lax.all_gather(x, self.axis, axis=axis, tiled=tiled,
                                  axis_index_groups=self._axis_groups())
        stacked = self._ring_allgather(x)          # (P, ...)
        if self._algo == "linear":
            # master relay-out: the root re-broadcasts the full P*S buffer
            # ((P-1) steps of P*S bytes -- the phase-1 cost structure).
            stacked = self._relay_from(stacked, root=0)
        if tiled:
            return jnp.concatenate([stacked[i] for i in range(self.size)],
                                   axis=axis)
        return stacked if axis == 0 else jnp.moveaxis(stacked, 0, axis)

    def reducescatter(self, x, op="add", *, axis: int = 0):
        """Tiled reduce-scatter: dim ``axis`` (size P*c) is reduced across
        ranks and this rank keeps its c-slice (slice index = comm rank)."""
        x = jnp.asarray(x)
        if self.size == 1:
            return x
        _, combine = _resolve_op(op)
        if self._algo == "native" and op == "add" \
                and self._native_groups_ok():
            _log("reducescatter", "native",
                 x.nbytes * (self.size - 1) // self.size, self.size - 1)
            return lax.psum_scatter(x, self.axis, scatter_dimension=axis,
                                    tiled=True,
                                    axis_index_groups=self._axis_groups())
        if self._algo in ("native", "ring"):
            return self._ring_reducescatter(x, combine, axis)
        # linear: the master computes the full reduction, then scatters.
        full = self._linear_allreduce(x, combine)
        c = x.shape[axis] // self.size
        return lax.dynamic_slice_in_dim(full, self.rank() * c, c, axis=axis)

    def alltoall(self, x, *, split_axis: int = 0, concat_axis: int = 0):
        """lax.all_to_all(tiled=True) semantics: split into P pieces along
        ``split_axis`` (piece i -> comm rank i), concatenate received pieces
        along ``concat_axis`` in source-rank order."""
        x = jnp.asarray(x)
        if self.size == 1:
            return x
        if self._algo == "native" and self._native_groups_ok():
            _log("alltoall", "native",
                 x.nbytes * (self.size - 1) // self.size, self.size - 1)
            return lax.all_to_all(x, self.axis, split_axis, concat_axis,
                                  tiled=True,
                                  axis_index_groups=self._axis_groups())
        return self._pairwise_alltoall(x, split_axis, concat_axis)

    def reduce(self, x, root: int = 0, op="add"):
        """MPI_Reduce in SPMD form: every rank computes the reduction (a
        rendezvous program cannot idle non-roots); non-roots receive
        zeros, mirroring 'significant only at root' semantics."""
        full = self.allreduce(x, op)
        return jnp.where(self.rank() == root, full, jnp.zeros_like(full))

    def gather(self, x, root: int = 0, *, axis: int = 0):
        """MPI_Gather: stacked (P, ...) at root, zeros elsewhere."""
        stacked = self.allgather(x, axis=axis)
        return jnp.where(self.rank() == root, stacked,
                         jnp.zeros_like(stacked))

    def scan(self, x, op="add"):
        """MPI_Scan (inclusive prefix reduction) via a shifted ring:
        after step k, rank r has folded ranks [r-2^k+1 .. r] -- a
        log-step Hillis-Steele scan over ppermute."""
        x = jnp.asarray(x)
        if self.size == 1:
            return x
        _, combine = _resolve_op(op)
        rank = self.rank()
        acc = x
        shift = 1
        while shift < self.size:
            moved = self._ppermute(acc, G.ring_perm(self._groups(), shift),
                                   op="scan")
            acc = jnp.where(rank >= shift, combine(acc, moved), acc)
            shift *= 2
        return acc

    def scatter(self, x, root: int = 0, *, axis: int = 0):
        """MPI_Scatter in SPMD form: dim ``axis`` (size P*c) of the
        root's buffer is split into P slices and rank i keeps slice i
        (every rank passes a congruent buffer -- rendezvous; only the
        root's content matters, mirroring 'significant only at root')."""
        x = jnp.asarray(x)
        if self.size == 1:
            return x
        if x.shape[axis] % self.size:
            raise ValueError(f"scatter dim {axis} size {x.shape[axis]} not "
                             f"divisible by group size {self.size}")
        full = self.broadcast(x, root)
        c = x.shape[axis] // self.size
        return lax.dynamic_slice_in_dim(full, self.rank() * c, c, axis=axis)

    # -- nonblocking wrappers (MPI-3 shape) ---------------------------------
    # In SPMD the runtime cannot defer a collective at the Python level --
    # XLA's latency-hiding scheduler IS the progress engine, free to
    # overlap any collective whose result is not yet consumed. These
    # wrappers keep one program text valid across all three modes: they
    # trace the collective eagerly, flag its logged cost as overlappable,
    # and return a born-complete ``Request`` whose ``wait`` yields the
    # traced value (the data dependency the compiler schedules around).

    def iallreduce(self, x, op="add", *, tag: int = 0) -> Request:
        with _overlap_scope():
            return Request.completed(self.allreduce(x, op, tag=tag),
                                     op="iallreduce")

    def iallgather(self, x, *, axis: int = 0, tiled: bool = False) -> Request:
        with _overlap_scope():
            return Request.completed(
                self.allgather(x, axis=axis, tiled=tiled), op="iallgather")

    def ibcast(self, x, root: int = 0) -> Request:
        with _overlap_scope():
            return Request.completed(self.broadcast(x, root), op="ibcast")

    ibroadcast = ibcast

    def ibarrier(self) -> Request:
        with _overlap_scope():
            return Request.completed(self.barrier(), op="ibarrier")

    def ireduce(self, x, root: int = 0, op="add") -> Request:
        with _overlap_scope():
            return Request.completed(self.reduce(x, root, op), op="ireduce")

    def igather(self, x, root: int = 0, *, axis: int = 0) -> Request:
        with _overlap_scope():
            return Request.completed(self.gather(x, root, axis=axis),
                                     op="igather")

    def iscatter(self, x, root: int = 0, *, axis: int = 0) -> Request:
        with _overlap_scope():
            return Request.completed(self.scatter(x, root, axis=axis),
                                     op="iscatter")

    def iscan(self, x, op="add") -> Request:
        with _overlap_scope():
            return Request.completed(self.scan(x, op), op="iscan")

    def ialltoall(self, x, *, split_axis: int = 0,
                  concat_axis: int = 0) -> Request:
        with _overlap_scope():
            return Request.completed(
                self.alltoall(x, split_axis=split_axis,
                              concat_axis=concat_axis), op="ialltoall")

    def ireducescatter(self, x, op="add", *, axis: int = 0) -> Request:
        with _overlap_scope():
            return Request.completed(self.reducescatter(x, op, axis=axis),
                                     op="ireducescatter")

    # -- pytree conveniences ----------------------------------------------------
    def tree_allreduce(self, tree, op="add"):
        return jax.tree.map(lambda v: self.allreduce(v, op), tree)

    def tree_allgather(self, tree, *, axis: int = 0, tiled: bool = False):
        return jax.tree.map(
            lambda v: self.allgather(v, axis=axis, tiled=tiled), tree)

    # -- internals -----------------------------------------------------------------
    def _axis_groups(self):
        return None if self.groups is None else [list(g) for g in self.groups]

    def _chunked(self, x):
        """Flatten + pad to (P, chunk)."""
        p = self.size
        flat = x.reshape(-1)
        padded = G.pad_to_multiple(flat.shape[0], p)
        if padded != flat.shape[0]:
            flat = jnp.pad(flat, (0, padded - flat.shape[0]))
        return flat.reshape(p, padded // p), x.shape, x.size

    def _relay_from(self, val, root: int):
        """Pass-along ring relay of ``val`` from ``root``; (P-1) full-size
        steps. After s hops, rank r holds root's copy iff (r-root)%P == s."""
        p = self.size
        rank = self.rank()
        v = val
        out = val
        for s in range(1, p):
            v = self._ppermute(v, G.ring_perm(self._groups(), 1),
                               op="broadcast")
            out = jnp.where((rank - root) % p == s, v, out)
        return out

    def _ring_allreduce(self, x, combine):
        """Chunked ring: reduce-scatter then all-gather; 2S(P-1)/P bytes."""
        p = self.size
        buf, orig_shape, orig_size = self._chunked(x)
        rank = self.rank()
        for step in range(p - 1):               # reduce-scatter phase
            send_idx = (rank - step) % p
            recv_idx = (rank - step - 1) % p
            msg = lax.dynamic_slice_in_dim(buf, send_idx, 1, axis=0)
            msg = self._ppermute(msg, G.ring_perm(self._groups(), 1),
                                 op="allreduce")
            cur = lax.dynamic_slice_in_dim(buf, recv_idx, 1, axis=0)
            buf = lax.dynamic_update_slice_in_dim(
                buf, combine(cur, msg), recv_idx, axis=0)
        for step in range(p - 1):               # all-gather phase
            send_idx = (rank - step + 1) % p
            recv_idx = (rank - step) % p
            msg = lax.dynamic_slice_in_dim(buf, send_idx, 1, axis=0)
            msg = self._ppermute(msg, G.ring_perm(self._groups(), 1),
                                 op="allreduce")
            buf = lax.dynamic_update_slice_in_dim(buf, msg, recv_idx, axis=0)
        return buf.reshape(-1)[:orig_size].reshape(orig_shape)

    def _linear_allreduce(self, x, combine):
        """Paper phase-1: gather-to-master + master-broadcast, emulated with
        2(P-1) full-buffer steps (same wire bytes / serialization depth).
        ``combine`` must be commutative: accumulation order is rank-relative."""
        p = self.size
        acc, v = x, x
        for _ in range(p - 1):                  # gather phase
            v = self._ppermute(v, G.ring_perm(self._groups(), 1),
                               op="allreduce")
            acc = combine(acc, v)
        return self._relay_from(acc, root=0)    # master-broadcast phase

    def _ring_allgather(self, x):
        """-> (P, ...) stacked in comm-rank order; (P-1) steps of S bytes."""
        p = self.size
        rank = self.rank()
        buf = jnp.zeros((p,) + x.shape, x.dtype)
        buf = lax.dynamic_update_slice_in_dim(buf, x[None], rank, axis=0)
        msg = x
        for step in range(p - 1):
            msg = self._ppermute(msg, G.ring_perm(self._groups(), 1),
                                 op="allgather")
            src = (rank - step - 1) % p
            buf = lax.dynamic_update_slice_in_dim(buf, msg[None], src, axis=0)
        return buf

    def _ring_reducescatter(self, x, combine, axis):
        p = self.size
        rank = self.rank()
        if x.shape[axis] % p:
            raise ValueError(f"reducescatter dim {axis} size {x.shape[axis]} "
                             f"not divisible by group size {p}")
        buf = jnp.moveaxis(x, axis, 0)
        c = buf.shape[0] // p
        buf = buf.reshape((p, c) + buf.shape[1:])
        for step in range(p - 1):
            send_idx = (rank - step) % p
            recv_idx = (rank - step - 1) % p
            msg = lax.dynamic_slice_in_dim(buf, send_idx, 1, axis=0)
            msg = self._ppermute(msg, G.ring_perm(self._groups(), 1),
                                 op="reducescatter")
            cur = lax.dynamic_slice_in_dim(buf, recv_idx, 1, axis=0)
            buf = lax.dynamic_update_slice_in_dim(
                buf, combine(cur, msg), recv_idx, axis=0)
        mine = lax.dynamic_slice_in_dim(buf, (rank + 1) % p, 1, axis=0)[0]
        return jnp.moveaxis(mine, 0, axis) if axis != 0 else mine

    def _pairwise_alltoall(self, x, split_axis, concat_axis):
        """ring: P-1 direct chunk exchanges ((P-1)/P * S bytes);
        linear: P-1 full-buffer relay hops ((P-1) * S bytes)."""
        p = self.size
        rank = self.rank()
        xs = jnp.moveaxis(x, split_axis, 0)
        if xs.shape[0] % p:
            raise ValueError("alltoall split dim not divisible by group size")
        c = xs.shape[0] // p
        xs = xs.reshape((p, c) + xs.shape[1:])   # xs[j] = piece for comm rank j
        res = jnp.zeros_like(xs)                 # res[j] = piece from comm rank j
        own = lax.dynamic_slice_in_dim(xs, rank, 1, axis=0)
        res = lax.dynamic_update_slice_in_dim(res, own, rank, axis=0)
        if self._algo == "linear":
            v = xs
            for s in range(1, p):
                v = self._ppermute(v, G.ring_perm(self._groups(), 1),
                                   op="alltoall")
                # v holds rank (r-s)'s full buffer; extract the piece for me.
                mine = lax.dynamic_slice_in_dim(v, rank, 1, axis=0)
                res = lax.dynamic_update_slice_in_dim(
                    res, mine, (rank - s) % p, axis=0)
        else:
            for s in range(1, p):
                # send the piece destined for rank+s directly (shift by s)
                msg = lax.dynamic_slice_in_dim(xs, (rank + s) % p, 1, axis=0)
                msg = self._ppermute(msg, G.ring_perm(self._groups(), s),
                                     op="alltoall")
                res = lax.dynamic_update_slice_in_dim(
                    res, msg, (rank - s) % p, axis=0)
        # Each piece restored to original rank layout with split dim = c,
        # then concatenated along concat_axis in source-rank order.
        pieces = [jnp.moveaxis(res[j], 0, split_axis) if split_axis != 0
                  else res[j] for j in range(p)]
        return jnp.concatenate(pieces, axis=concat_axis)
