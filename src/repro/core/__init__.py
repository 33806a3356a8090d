"""MPIgnite-JAX core: the paper's contribution as a composable JAX module.

- ``groups``    : pure rank/group math (split, rings, byte-cost model)
- ``matching``  : transport-agnostic mailbox matching + p2p-composed
                  collectives (``MessageComm`` base)
- ``local``     : thread-runtime communicator (paper's local mode; oracle)
- ``cluster``   : multi-process peer runtime over TCP (wire protocol,
                  persistent executor pool, direct peer data channels,
                  heartbeats, elastic ``ClusterSupervisor`` recovery:
                  shrink-to-survivors, grow-on-join, checkpoint-restart)
- ``comm``      : SPMD ``PeerComm`` over mesh axes (linear/ring/native)
- ``closures``  : ``parallelize_func(f).execute(n)`` in local, cluster or
                  SPMD mode
"""
from . import groups
from .comm import PeerComm, cost_log, cost_scope
from .closures import (MPIgniteContext, ParallelClosure, RANK_AXIS, flat_mesh,
                       parallelize_func)
from .cluster import (ClusterComm, ClusterFuncRDD, ClusterPool,
                      CommandLauncher, ExecutorFailure, ExecutorPool,
                      ForkLauncher, get_pool, shutdown_pools)
from .local import LocalComm, ParallelFuncRDD
from .matching import (Mailbox, MessageComm, PeerDeadError, ProgressEngine,
                       Request, waitall, waitany)

__all__ = [
    "groups", "PeerComm", "cost_log", "cost_scope",
    "MPIgniteContext", "ParallelClosure",
    "RANK_AXIS", "flat_mesh", "parallelize_func", "LocalComm",
    "ParallelFuncRDD", "ClusterComm", "ClusterFuncRDD", "ClusterPool",
    "ClusterSupervisor", "CommandLauncher", "ExecutorFailure",
    "ExecutorPool", "ForkLauncher", "RunContext",
    "get_pool", "shutdown_pools", "Mailbox", "MessageComm",
    "PeerDeadError", "ProgressEngine", "Request", "waitall", "waitany",
]


def __getattr__(name):
    # Lazy like cluster.__init__: the supervisor imports repro.train,
    # which imports repro.core back -- resolving it at package init
    # would cycle.
    if name in ("ClusterSupervisor", "RunContext"):
        from . import cluster
        return getattr(cluster, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
