"""Parallel closures -- the paper's ``sc.parallelizeFunc(f).execute(n)``.

Three execution modes mirror Spark's deployments:

- ``mode="local"``   : n lockstep python threads with a real message-matching
  runtime (``LocalComm``) -- arbitrary payloads, futures, runtime split.
- ``mode="cluster"`` : n genuinely separate executor *processes* joined by
  the TCP wire protocol in ``core.cluster`` -- same runtime semantics as
  local (receiver-side buffering, dynamic matching), plus heartbeat
  failure detection and checkpoint-restart supervision. Closures are
  dispatched as jobs to a persistent warm ``ExecutorPool`` (msg frames
  travel direct executor-to-executor channels, not through the driver).
- ``mode="spmd"``    : one program instance per device of a flat JAX mesh,
  compiled with ``shard_map``; the closure receives a ``PeerComm`` and its
  comm calls lower to ICI collectives. The closure's return values are
  gathered to the driver as a list (paper: "an array of return values from
  each process"), and the jit boundary is the implicit end-of-closure
  barrier the paper describes.

The same closure can run in all three modes when it restricts itself to the
static-routing subset (DESIGN.md section 2), which is how the equivalence
tests pin SPMD semantics to the runtime oracle and the cluster transport
to both.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .comm import PeerComm
from .local import ParallelFuncRDD

RANK_AXIS = "ranks"


def flat_mesh(n: int | None = None, devices=None) -> Mesh:
    """A 1-D mesh over the first n devices (paper's flat rank space)."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices) if n is None else n
    if n > len(devices):
        raise ValueError(f"execute({n}) exceeds available devices "
                         f"({len(devices)}); use mode='local' for "
                         "oversubscription")
    return jax.make_mesh((n,), (RANK_AXIS,),
                         devices=np.asarray(devices[:n]))


class ParallelClosure:
    """RDD-of-a-function (paper section 3.2)."""

    def __init__(self, fn: Callable, backend: str = "native",
                 timeout: float = 60.0, segment_bytes: int | None = None,
                 trace: bool | None = None):
        self._fn = fn
        self._backend = backend
        self._timeout = timeout
        # segmented-ring tuning for the message runtimes (local/cluster);
        # None defers to $MPIGNITE_SEGMENT_BYTES. SPMD mode ignores it:
        # PeerComm's ring collectives are already chunked at trace time.
        self._segment_bytes = segment_bytes
        # runtime tracing for the message runtimes; None defers to
        # $MPIGNITE_TRACE. The resulting obs.JobTrace of the most recent
        # traced execute() lands on ``self.last_trace``.
        self._trace = trace
        self.last_trace = None

    def execute(self, n: int | None = None, *, mode: str = "local",
                mesh: Mesh | None = None, jit: bool = True) -> list:
        if mode == "local":
            if n is None:
                raise ValueError("local mode requires an instance count")
            rdd = ParallelFuncRDD(self._fn, timeout=self._timeout,
                                  backend=self._backend,
                                  segment_bytes=self._segment_bytes,
                                  trace=self._trace)
            out = rdd.execute(n)
            self.last_trace = rdd.last_trace
            return out
        if mode == "cluster":
            from .cluster import get_pool
            if n is None:
                raise ValueError("cluster mode requires an instance count")
            # warm path: repeated execute() calls reuse the cached
            # ExecutorPool -- live processes, established peer channels --
            # so only the first call on a given (n, backend) pays fork +
            # connect + address brokering.
            pool = get_pool(n, backend=self._backend)
            out = pool.run(self._fn, backend=self._backend,
                           timeout=self._timeout,
                           segment_bytes=self._segment_bytes,
                           trace=self._trace)
            self.last_trace = pool.last_trace
            return out
        if mode != "spmd":
            raise ValueError(f"unknown mode {mode!r}")
        mesh = mesh if mesh is not None else flat_mesh(n)
        size = mesh.shape[RANK_AXIS]
        comm = PeerComm.world(RANK_AXIS, size, backend=self._backend)

        def body():
            out = self._fn(comm)
            if out is None:
                out = jnp.zeros((), jnp.int32)
            return jax.tree.map(lambda v: jnp.asarray(v)[None], out)

        smapped = jax.shard_map(body, mesh=mesh, in_specs=(),
                                out_specs=P(RANK_AXIS))
        run = jax.jit(smapped) if jit else smapped
        with jax.set_mesh(mesh):
            out = run()
        out = jax.tree.map(np.asarray, out)
        leaves = jax.tree.leaves(out)
        count = leaves[0].shape[0] if leaves else size
        return [jax.tree.map(lambda v: v[i], out) for i in range(count)]


def parallelize_func(fn: Callable, *, backend: str = "native",
                     timeout: float = 60.0,
                     segment_bytes: int | None = None,
                     trace: bool | None = None) -> ParallelClosure:
    """``sc.parallelizeFunc`` analogue. The closure takes the communicator
    as its only argument; other inputs arrive via python closure capture,
    exactly as in the paper's listings. ``segment_bytes`` tunes the
    segmented ring schedules per closure (None = $MPIGNITE_SEGMENT_BYTES,
    <= 0 disables the automatic segmented upgrade); ``trace`` enables
    runtime tracing for the message runtimes (None = $MPIGNITE_TRACE;
    the resulting ``obs.JobTrace`` lands on ``closure.last_trace``)."""
    return ParallelClosure(fn, backend=backend, timeout=timeout,
                           segment_bytes=segment_bytes, trace=trace)


class MPIgniteContext:
    """Small driver-side facade mirroring the SparkContext the listings use
    (``sc.parallelizeFunc(...)``)."""

    def __init__(self, *, default_mode: str = "local",
                 backend: str = "native"):
        self.default_mode = default_mode
        self.backend = backend

    def parallelize_func(self, fn: Callable) -> "_BoundClosure":
        return _BoundClosure(ParallelClosure(fn, backend=self.backend),
                             self.default_mode)

    parallelizeFunc = parallelize_func  # paper spelling


class _BoundClosure:
    def __init__(self, closure: ParallelClosure, mode: str):
        self._closure = closure
        self._mode = mode

    def execute(self, n: int | None = None, **kw) -> list:
        kw.setdefault("mode", self._mode)
        return self._closure.execute(n, **kw)
