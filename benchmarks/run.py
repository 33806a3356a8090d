"""Benchmark harness -- one benchmark per paper table/listing.

The paper's empirical artifacts are its four listings (section 4) and the
API-parity table (Figure 1); this harness times each listing on both
execution modes, quantifies the phase-1 (master relay) vs phase-2 (ring)
vs native byte/step costs that section 3.1 describes qualitatively.

Cluster rows come in four flavors spanning the PR-2 data-plane work:
``cold`` (a fresh executor world per call: fork + connect + address
brokering, the PR-1 cost model) vs ``warm`` (a persistent
``ExecutorPool``: the closure is dispatched as a job frame to live
processes), crossed with ``relay`` (every msg frame double-hops through
the driver, PR-1 routing) vs ``direct`` (peer-to-peer executor
channels). The ``steadystate_speedup`` row states warm+direct against
cold+relay -- the acceptance criterion is >= 5x.

This is a CPU harness. Its entry sets ``JAX_PLATFORMS=cpu`` for itself
and every process it starts, so it never takes a TPU chip and no row it
prints is a chip number: the rows are host wall-clock on the CPU
backend. The model stack is measured on the chip, by benchmarks/chip/.

Output: ``name,us_per_call,derived`` CSV on stdout, and the same rows as
machine-readable JSON with ``--json PATH`` (perf trajectory across PRs).
Usage: PYTHONPATH=src python -m benchmarks.run [--quick] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import statistics
import subprocess
import sys
import time

# Before numpy loads (this module is the process entry): single-threaded
# BLAS everywhere, including the executor worlds forked below us.
# Multi-threaded OpenBLAS spin-waiters oversubscribe the benchmark box
# and starve the comm threads the overlap rows measure.
os.environ.setdefault("OPENBLAS_NUM_THREADS", "1")
os.environ.setdefault("OMP_NUM_THREADS", "1")

import numpy as np                                      # noqa: E402

ROWS: list[tuple[str, float, str]] = []

# Liveness budget for benchmark pools. Benchmarks deliberately saturate
# every core (driver + n executors time-sharing the host), so the
# production-tuned 2s heartbeat budget false-positives on oversubscribed
# boxes; the failover benchmarks construct their own tight-budget pools.
POOL_HB = dict(hb_interval=0.25, hb_timeout=10.0)


def bench(name: str, fn, *, repeat: int = 5, derived: str = ""):
    fn()                                   # warmup
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e6)
    ROWS.append((name, statistics.median(ts), derived))


def row_value(name: str) -> float | None:
    for n, us, _ in ROWS:
        if n == name:
            return us
    return None


# ---------------------------------------------------------------------------
# Listings 1/2/4 across runtime deployments: threads (paper local mode)
# and real executor processes over the TCP transport, cold vs warm pool,
# relay vs direct data plane.
# ---------------------------------------------------------------------------

def _cluster_rows(name: str, run_closure, n: int, *, planes_cold=("relay",),
                  planes_warm=("direct",), repeat_cold=3, repeat_warm=5):
    """Time one listing closure cold (fresh world per call, PR-1 cost
    model) and warm (persistent pool, dispatched job) per data plane."""
    from repro.core.cluster import ClusterFuncRDD, get_pool

    for plane in planes_cold:
        def run_cold(plane=plane):
            run_closure(lambda fn:
                        ClusterFuncRDD(fn, data_plane=plane).execute(n))
        bench(f"{name}_cluster_cold_{plane}_n{n}", run_cold,
              repeat=repeat_cold,
              derived=f"fork+connect+broker every call ({plane} plane)")
    for plane in planes_warm:
        pool = get_pool(n, data_plane=plane, **POOL_HB)

        def run_warm(pool=pool):
            run_closure(pool.run)
        bench(f"{name}_cluster_warm_{plane}_n{n}", run_warm,
              repeat=repeat_warm,
              derived=f"persistent pool steady state ({plane} plane)")


def bench_listing1_matvec():
    from repro.core import parallelize_func
    mat = np.arange(1, 65, dtype=np.int64).reshape(8, 8)
    vec = np.arange(8)

    def closure(w):
        return int(mat[w.get_rank()] @ vec) if w.get_rank() < 8 else 0

    def check(execute):
        assert sum(execute(closure)) == int(mat @ vec @ np.ones(8))

    bench("listing1_matvec_local_n8",
          lambda: check(lambda fn: parallelize_func(fn).execute(
              8, mode="local")), repeat=3)
    _cluster_rows("listing1_matvec", check, 8)


def bench_listing2_ring(n=16):
    from repro.core import parallelize_func

    def ring(world):
        rank, size = world.get_rank(), world.get_size()
        if rank == 0:
            world.send(1, 0, 42)
            return world.receive(size - 1, 0)
        t = world.receive(rank - 1, 0)
        world.send((rank + 1) % size, 0, t)
        return t

    def check(execute):
        assert execute(ring)[0] == 42

    bench(f"listing2_ring_local_n{n}",
          lambda: check(lambda fn: parallelize_func(fn).execute(
              n, mode="local")), repeat=3, derived=f"{n} hops/round")
    # full matrix on the paper's ring: both planes, cold and warm
    _cluster_rows("listing2_ring", check, n,
                  planes_cold=("relay", "direct"),
                  planes_warm=("relay", "direct"))

    cold = row_value(f"listing2_ring_cluster_cold_relay_n{n}")
    warm = row_value(f"listing2_ring_cluster_warm_direct_n{n}")
    if cold and warm:
        ROWS.append((f"listing2_ring_steadystate_speedup_n{n}", 0.0,
                     f"{cold / warm:.1f}x warm+direct vs cold+relay "
                     "(acceptance: >=5x)"))


def _concurrency_gate_failure(msg: str) -> str:
    """FAILED verdict for an overlap gate -- waived on single-core hosts,
    where the progress engine has no second core to make progress *on*
    and the gate measures scheduler noise, not the implementation."""
    if (os.cpu_count() or 1) < 2:
        return (f"WAIVED (single-core host): {msg} -- no core for the "
                "progress engine to overlap on; gate enforced in CI")
    return f"FAILED: {msg}"


OVERLAP_ACCEPTANCE = 1.3    # overlapped must beat blocking by >= this


def bench_listing2_ring_overlap(quick: bool):
    """Communication/compute overlap on the listing-2 ring workload
    (warm pool, direct data plane, ring backend): K bucketed
    ``iallreduce`` requests posted up front and advanced by each
    executor's progress engine while the closure matmuls -- the
    DDP-style gradient-bucket pattern -- against the identical work with
    the K reductions serialized as blocking ``allreduce`` calls.

    Shape notes (chosen for honesty on small shared CI boxes): n=2
    ranks so each executor owns roughly one core; 64 KiB buckets keep
    the comm *latency*-bound (what overlap can genuinely hide) rather
    than memcpy-bound (which no scheduler can hide on saturated cores);
    the compute is a few large GIL-releasing matmuls, not many tiny
    ones, so the progress engine isn't starved by GIL convoying. Both
    legs pin BLAS to one thread and shrink the GIL switch interval.
    Timing is min-of-N with the legs interleaved, the standard
    noise-robust estimator on shared machines.

    A speedup below OVERLAP_ACCEPTANCE emits a FAILED row, which
    ``--check`` turns into a nonzero exit: overlap regressions fail CI
    loudly."""
    from repro.core.cluster import get_pool
    n, elems, K, dim, iters = 2, 8192, 24, 512, 3
    reps = 5 if quick else 9

    def _tuned():
        import sys
        sys.setswitchinterval(0.001)
        try:        # single-threaded BLAS: no spin-waiters starving comm
            from threadpoolctl import threadpool_limits
            threadpool_limits(1)
        except ImportError:
            pass

    def blocking(world):
        _tuned()
        xs = [np.ones(elems, np.float64) * (world.get_rank() + k)
              for k in range(K)]
        m = np.full((dim, dim), 1.0 / dim)
        world.barrier()
        t0 = time.perf_counter()
        reds = [world.allreduce(x, lambda a, b: a + b) for x in xs]
        acc = m
        for _ in range(iters):
            acc = acc @ m
        dt = time.perf_counter() - t0
        assert float(reds[0][0]) == float(sum(range(n)))
        return dt

    def overlapped(world):
        _tuned()
        xs = [np.ones(elems, np.float64) * (world.get_rank() + k)
              for k in range(K)]
        m = np.full((dim, dim), 1.0 / dim)
        world.barrier()
        t0 = time.perf_counter()
        reqs = [world.iallreduce(x, lambda a, b: a + b) for x in xs]
        acc = m
        for _ in range(iters):
            acc = acc @ m               # progress engine reduces meanwhile
        reds = [r.wait(timeout=120) for r in reqs]
        dt = time.perf_counter() - t0
        assert float(reds[0][0]) == float(sum(range(n)))
        return dt

    pool = get_pool(n, data_plane="direct", **POOL_HB)
    for fn in (blocking, overlapped):           # warm both code paths
        pool.run(fn, backend="ring", timeout=120)
    t_blocks, t_overs = [], []

    def measure(rounds):
        for _ in range(rounds):     # interleaved: drift hits both legs
            t_blocks.append(max(pool.run(blocking, backend="ring",
                                         timeout=120)))
            t_overs.append(max(pool.run(overlapped, backend="ring",
                                        timeout=120)))
        return min(t_blocks) * 1e6, min(t_overs) * 1e6

    t_block, t_over = measure(reps)
    if t_block / t_over < OVERLAP_ACCEPTANCE:
        # one deeper retry before declaring a regression: a transient
        # noisy neighbor compresses the ratio (both legs inflate, the
        # overlapped one proportionally more); min-of-more recovers the
        # true steady state, while a real regression stays below
        t_block, t_over = measure(2 * reps)

    kib = elems * 8 >> 10
    ROWS.append((f"listing2_ring_overlap_blocking_n{n}", t_block,
                 f"{K}x{kib}KiB ring allreduce THEN {iters} matmuls "
                 "(serial)"))
    ROWS.append((f"listing2_ring_overlap_iallreduce_n{n}", t_over,
                 f"{K}x{kib}KiB iallreduce UNDER {iters} matmuls "
                 "(engine overlap)"))
    speedup = t_block / t_over
    verdict = (f"{speedup:.2f}x overlapped vs blocking (acceptance: "
               f">={OVERLAP_ACCEPTANCE}x)")
    if speedup < OVERLAP_ACCEPTANCE:
        verdict = _concurrency_gate_failure(
            f"overlap speedup {speedup:.2f}x < {OVERLAP_ACCEPTANCE}x")
    ROWS.append((f"listing2_ring_overlap_speedup_n{n}", 0.0, verdict))


SEGMENTED_ACCEPTANCE = 2.0  # segmented ring must beat whole-buffer by >=2x


def bench_listing2_ring_segmented(quick: bool):
    """Bandwidth-bound ring allreduce at 8 MiB: the segmented
    reduce-scatter/all-gather schedule (~2S(p-1)/p bytes per rank,
    default 256 KiB segments) against the whole-buffer message ring
    ((p-1)S bytes per rank), both on the same warm direct-plane pool.
    At n=8 the wire-byte ratio is 4x, so the >=2x acceptance leaves
    headroom for per-segment overheads and noisy CI neighbors; a result
    below it emits a FAILED row that ``--check`` turns into a nonzero
    exit."""
    from repro.core.cluster import get_pool
    n = 8
    elems = (8 << 20) // 8              # 8 MiB of float64
    reps = 3 if quick else 5

    def closure(world):
        x = np.ones(elems, np.float64) * (world.get_rank() + 1)
        world.barrier()                 # clocks start together
        t0 = time.perf_counter()
        # np.add (a ufunc) is what makes plain `ring` eligible for the
        # automatic segmented upgrade -- the exact path users hit
        red = world.allreduce(x, np.add)
        dt = time.perf_counter() - t0
        assert float(red[0]) == float(sum(range(1, world.get_size() + 1)))
        return dt

    pool = get_pool(n, data_plane="direct", **POOL_HB)
    # whole-buffer leg: segment_bytes=0 disables the automatic segmented
    # upgrade; segmented leg: None defers to the 256 KiB default
    legs = {"whole": 0, "chunked": None}
    for seg in legs.values():           # warm both code paths
        pool.run(closure, backend="ring", timeout=120, segment_bytes=seg)
    times = {k: [] for k in legs}

    def measure(rounds):
        for _ in range(rounds):         # interleaved: drift hits both legs
            for k, seg in legs.items():
                times[k].append(max(pool.run(closure, backend="ring",
                                             timeout=120,
                                             segment_bytes=seg)))
        return min(times["whole"]) * 1e6, min(times["chunked"]) * 1e6

    t_whole, t_seg = measure(reps)
    if t_whole / t_seg < SEGMENTED_ACCEPTANCE:
        # one deeper retry before declaring a regression (noisy-neighbor
        # transients compress the ratio; a real regression stays below)
        t_whole, t_seg = measure(2 * reps)

    ROWS.append((f"listing2_ring_segmented_whole_n{n}", t_whole,
                 "8MiB allreduce, whole-buffer ring ((p-1)S bytes/rank)"))
    ROWS.append((f"listing2_ring_segmented_chunked_n{n}", t_seg,
                 "8MiB allreduce, segmented reduce-scatter+allgather "
                 "(2S(p-1)/p bytes/rank, 256KiB segments)"))
    speedup = t_whole / t_seg
    verdict = (f"{speedup:.2f}x segmented vs whole-buffer ring "
               f"(acceptance: >={SEGMENTED_ACCEPTANCE}x)")
    if speedup < SEGMENTED_ACCEPTANCE:
        verdict = (f"FAILED: segmented speedup {speedup:.2f}x < "
                   f"{SEGMENTED_ACCEPTANCE}x")
    ROWS.append((f"listing2_ring_segmented_speedup_n{n}", 0.0, verdict))


SHM_ACCEPTANCE = 1.5    # shm rings must beat TCP loopback at 8 MiB


def bench_listing2_ring_shm(quick: bool):
    """The shared-memory transport tier against TCP loopback on the
    identical workload: an 8 MiB segmented ring allreduce on a warm
    direct-plane pool, once with the shm rings brokered on (the
    same-host default) and once pinned to pure TCP (``shm=False``).
    Both worlds run the same schedule and the same wire frames -- the
    only difference is whether a frame crosses the kernel socket stack
    or a ``/dev/shm`` ring, so the ratio isolates the transport. A
    speedup below SHM_ACCEPTANCE emits a FAILED row (waived on
    single-core hosts, where both legs serialize on the one core and
    the transport is no longer what is being measured)."""
    from repro.core.cluster import get_pool
    n = 8
    elems = (8 << 20) // 8              # 8 MiB of float64
    reps = 3 if quick else 5

    def closure(world):
        x = np.ones(elems, np.float64) * (world.get_rank() + 1)
        world.barrier()                 # clocks start together
        t0 = time.perf_counter()
        red = world.allreduce(x, np.add)    # auto-segmented ring
        dt = time.perf_counter() - t0
        assert float(red[0]) == float(sum(range(1, world.get_size() + 1)))
        return dt

    pools = {"shm": get_pool(n, data_plane="direct", shm=True,
                              **POOL_HB),
             "tcp": get_pool(n, data_plane="direct", shm=False,
                             **POOL_HB)}
    for pool in pools.values():         # warm both transports
        pool.run(closure, backend="ring", timeout=120)
    times = {k: [] for k in pools}

    def measure(rounds):
        for _ in range(rounds):         # interleaved: drift hits both legs
            for k, pool in pools.items():
                times[k].append(max(pool.run(closure, backend="ring",
                                             timeout=120)))
        return min(times["tcp"]) * 1e6, min(times["shm"]) * 1e6

    t_tcp, t_shm = measure(reps)
    if t_tcp / t_shm < SHM_ACCEPTANCE:
        # one deeper retry before declaring a regression (noisy-neighbor
        # transients compress the ratio; a real regression stays below)
        t_tcp, t_shm = measure(2 * reps)

    ROWS.append((f"listing2_ring_shm_tcp_n{n}", t_tcp,
                 "8MiB segmented ring allreduce, TCP loopback (shm=False)"))
    ROWS.append((f"listing2_ring_shm_n{n}", t_shm,
                 "same schedule over /dev/shm rings (auto-selected for "
                 "same-host pairs)"))
    speedup = t_tcp / t_shm
    verdict = (f"{speedup:.2f}x shm vs TCP loopback "
               f"(acceptance: >={SHM_ACCEPTANCE}x)")
    if speedup < SHM_ACCEPTANCE:
        verdict = _concurrency_gate_failure(
            f"shm speedup {speedup:.2f}x < {SHM_ACCEPTANCE}x")
    ROWS.append((f"listing2_ring_shm_speedup_n{n}", 0.0, verdict))


TRACE_OVERHEAD_ACCEPTANCE = 1.05    # disabled-path tax on warm ring jobs


def bench_tracing_overhead(quick: bool, n: int = 16):
    """Observability-plane cost on the listing-2 warm/direct ring.

    With tracing off every instrumentation point in the runtime is a
    pointer compare (``tracer is None``), so an untraced warm job must
    stay within TRACE_OVERHEAD_ACCEPTANCE of the plain warm row measured
    above -- the same code path timed independently. The gate catches
    tracing accidentally left enabled (env leak, flag-resolution bug)
    and per-call work creeping into the disabled guards. The traced
    timing and its phase breakdown ride along as info rows: that cost is
    opt-in by construction."""
    from repro.core.cluster import get_pool

    def ring(world):
        rank, size = world.get_rank(), world.get_size()
        if rank == 0:
            world.send(1, 0, 42)
            return world.receive(size - 1, 0)
        t = world.receive(rank - 1, 0)
        world.send((rank + 1) % size, 0, t)
        return t

    base = row_value(f"listing2_ring_cluster_warm_direct_n{n}")
    pool = get_pool(n, data_plane="direct", **POOL_HB)
    reps = 5 if quick else 9

    def measure(rounds, trace):
        ts = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            out = pool.run(ring, trace=trace)
            ts.append((time.perf_counter() - t0) * 1e6)
            assert out[0] == 42
        return min(ts)

    measure(1, False)                       # warmup
    t_off = measure(reps, False)
    if base and t_off / base > TRACE_OVERHEAD_ACCEPTANCE:
        # one deeper retry before declaring a regression: min-of-more
        # shakes off a noisy neighbor, a real disabled-path tax stays
        t_off = measure(2 * reps, False)
    t_on = measure(max(3, reps // 2), True)
    breakdown = (pool.last_trace.phase_breakdown()
                 if pool.last_trace is not None else "")

    ROWS.append((f"listing2_ring_tracing_off_n{n}", t_off,
                 "warm direct ring, $MPIGNITE_TRACE unset (guards only)"))
    ROWS.append((f"listing2_ring_tracing_on_n{n}", t_on,
                 f"trace=True incl driver aggregation; {breakdown}"))
    if base:
        ratio = t_off / base
        verdict = (f"{ratio:.3f}x untraced vs plain warm row (acceptance: "
                   f"<={TRACE_OVERHEAD_ACCEPTANCE}x)")
        if ratio > TRACE_OVERHEAD_ACCEPTANCE:
            verdict = (f"FAILED: disabled-path overhead {ratio:.3f}x > "
                       f"{TRACE_OVERHEAD_ACCEPTANCE}x")
        ROWS.append((f"listing2_ring_tracing_overhead_n{n}", 0.0, verdict))


def bench_listing4_2d_matvec():
    from repro.core import parallelize_func
    n = 3
    mat = np.arange(1, 10, dtype=np.int64).reshape(3, 3)
    vec = np.array([1, 2, 3])

    def matvec2d(world):
        wr = world.get_rank()
        row = world.split(wr // n, wr)
        col = world.split(wr % n, wr)
        x = col.broadcast(0, int(vec[wr % n]) if wr // n == 0 else None)
        return row.allreduce(int(mat[wr // n, wr % n]) * x,
                             lambda a, b: a + b)

    def check(execute):
        assert execute(matvec2d)[0] == int(mat[0] @ vec)

    bench("listing4_2d_matvec_local_n9",
          lambda: check(lambda fn: parallelize_func(fn).execute(
              9, mode="local")), repeat=3)
    _cluster_rows("listing4_2d_matvec", check, 9)


# ---------------------------------------------------------------------------
# Asynchronous buddy checkpointing: the snapshot streams each rank's
# shard to its buddy via isend/irecv *overlapped* with the step's
# compute. The acceptance gate compares the overlapped per-step overhead
# against the stall of a stop-and-stream (synchronous) snapshot.
# ---------------------------------------------------------------------------

ASYNC_CKPT_ACCEPTANCE = 0.5   # overlapped overhead <= this x sync stall


def bench_listing4_ckpt_async_overhead(quick: bool):
    """Three step loops on a warm 4-rank pool: compute only, compute +
    synchronous buddy snapshot (stage, stream, commit -- all on the
    critical path), and compute with the snapshot issued *before* the
    compute and committed after (the ``train.buddy`` pattern: transfers
    progress under the compute). The gated row asserts the overlapped
    overhead stays <= ASYNC_CKPT_ACCEPTANCE of the synchronous stall; a
    miss emits a FAILED row, which ``--check`` turns into a nonzero
    exit."""
    from repro.core.cluster import get_pool
    n = 4
    steps = 7 if quick else 11
    # the step's compute must be long enough to hide the stream under
    # (overlap can only save what the critical path spends computing)
    shard_elems = (1 << 18) if quick else (1 << 20)   # 1 MiB / 4 MiB f32
    mat_dim, width, iters = 512, (64 if quick else 128), (96 if quick else 128)

    def make(mode):
        def closure(comm):
            from repro.train import buddy as B
            B.reset("bench")
            bc = B.BuddyCheckpointer("bench", history=2)
            rng = np.random.default_rng(comm.get_rank())
            shard = rng.standard_normal(shard_elems).astype(np.float32)
            m = rng.standard_normal((mat_dim, mat_dim)).astype(np.float32)
            v = rng.standard_normal((mat_dim, width)).astype(np.float32)
            comm.barrier()
            ts = []
            for step in range(1, steps + 1):
                t0 = time.perf_counter()
                h = None
                if mode == "async":
                    h = bc.snapshot(comm, step, shard)   # overlaps below
                for _ in range(iters):
                    v = m @ v                    # GIL-free GEMM: the
                    v /= np.linalg.norm(v)       # engine streams under it
                if mode == "sync":
                    h = bc.snapshot(comm, step, shard)   # full stall
                if h is not None:
                    bc.commit(comm, h)
                else:
                    comm.barrier()   # match the commit's synchronization
                ts.append(time.perf_counter() - t0)
            # median over steps (first dropped as warmup): on shared CI
            # boxes the per-step noise floor rivals the stall itself, and
            # a mean lets one descheduled step decide the gate
            ts = sorted(ts[1:])
            return ts[len(ts) // 2] * 1e6
        return closure

    pool = get_pool(n, **POOL_HB)
    pool.run(make("none"), timeout=300)                  # warmup
    t_none = max(pool.run(make("none"), timeout=300))
    t_sync = max(pool.run(make("sync"), timeout=300))
    t_async = max(pool.run(make("async"), timeout=300))
    stall = max(t_sync - t_none, 1.0)
    overhead = max(t_async - t_none, 0.0)
    ratio = overhead / stall
    ROWS.append((f"listing4_ckpt_sync_stall_n{n}", stall,
                 f"stop-and-stream buddy snapshot added per step "
                 f"(compute-only baseline {t_none:.0f}us)"))
    verdict = (f"{ratio:.2f}x of the synchronous stall (acceptance: "
               f"<={ASYNC_CKPT_ACCEPTANCE}x)")
    if ratio > ASYNC_CKPT_ACCEPTANCE:
        verdict = _concurrency_gate_failure(
            f"overlapped overhead {ratio:.2f}x > "
            f"{ASYNC_CKPT_ACCEPTANCE}x of the sync stall")
    ROWS.append((f"listing4_ckpt_async_overhead_n{n}", overhead, verdict))


def bench_shrink_recovery_latency(quick: bool):
    """Recovery latency after a SIGKILLed rank: shrink-to-survivors
    (re-broker the live ranks, first job on the shrunken world) vs the
    legacy full relaunch (tear down, fork a fresh world, first job).
    Shrink keeps warm processes, so it should win by a wide margin."""
    from repro.core.cluster import ExecutorPool
    n = 4
    kw = dict(hb_interval=0.05, hb_timeout=0.8, timeout=30)

    def boot_and_break():
        pool = ExecutorPool(n, **kw)
        pool.run(lambda c: c.get_rank())
        os.kill(pool.pids[1], signal.SIGKILL)
        time.sleep(0.3)
        try:
            pool.run(lambda c: c.barrier(), timeout=10)
        except Exception:   # noqa: BLE001 - the break is the point
            pass
        return pool

    pool = boot_and_break()
    t0 = time.perf_counter()
    pool.shrink_to_survivors()
    pool.run(lambda c: c.get_rank())
    t_shrink = (time.perf_counter() - t0) * 1e6
    pool.shutdown()

    pool = boot_and_break()
    t0 = time.perf_counter()
    pool.shutdown()
    pool2 = ExecutorPool(n - 1, **kw)
    pool2.run(lambda c: c.get_rank())
    t_relaunch = (time.perf_counter() - t0) * 1e6
    pool2.shutdown()

    ROWS.append((f"shrink_recovery_latency_n{n}", t_shrink,
                 "re-broker survivors + first job, no process launch"))
    ROWS.append((f"relaunch_recovery_latency_n{n}", t_relaunch,
                 f"teardown + fresh {n - 1}-wide world + first job"))
    ROWS.append((f"shrink_vs_relaunch_speedup_n{n}", 0.0,
                 f"{t_relaunch / max(t_shrink, 1.0):.1f}x"))


# ---------------------------------------------------------------------------
# Dataset shuffle: the Spark-shaped layer's wordcount on the collectives
# shuffle (map-side combine + pipelined ireducescatter between warm
# executors) vs the naive driver-gather baseline (every raw record
# relayed through the driver and merged single-threaded). The workload
# shape follows the Spark-on-HPC study's shuffle-heavy kernels.
# ---------------------------------------------------------------------------

DATASET_SHUFFLE_ACCEPTANCE = 2.0    # collectives must beat driver-gather


def bench_dataset_shuffle(quick: bool):
    from repro.data import DataContext
    n, nparts, vocab = 4, 8, 997
    nrec = 60_000 if quick else 200_000
    reps = 3 if quick else 5

    with DataContext(n, mode="cluster", timeout=120) as ctx:
        def build(sort=False):
            # range roots regenerate executor-side: the rows time the
            # shuffle, not driver->executor argument shipping
            words = ctx.range(nrec, nparts).map(
                lambda i: (f"w{(i * 2654435761) % vocab:03d}", 1))
            counts = words.reduceByKey(lambda a, b: a + b, nparts=nparts)
            return counts.sortByKey(nparts=4) if sort else counts

        build().collect()                       # warm the pool + plan path
        bench(f"dataset_wordcount_collectives_n{n}",
              lambda: build().collect(), repeat=reps,
              derived=f"{nrec} records -> {vocab} keys, map-side combine "
                      "+ pipelined ireducescatter, never via driver")
        bench(f"dataset_wordcount_gather_n{n}",
              lambda: build().collect(shuffle="gather"), repeat=reps,
              derived="naive baseline: all raw records relayed through "
                      "the driver, merged single-threaded")
        bench(f"dataset_sort_collectives_n{n}",
              lambda: build(sort=True).collect(), repeat=reps,
              derived="wordcount + sampled range-partition sortByKey on "
                      "alltoall")

    t_coll = row_value(f"dataset_wordcount_collectives_n{n}")
    t_gather = row_value(f"dataset_wordcount_gather_n{n}")
    speedup = t_gather / max(t_coll, 1.0)
    verdict = (f"{speedup:.1f}x shuffle-on-collectives vs driver-gather "
               f"(acceptance: >={DATASET_SHUFFLE_ACCEPTANCE}x)")
    if speedup < DATASET_SHUFFLE_ACCEPTANCE:
        verdict = (f"FAILED: {verdict}; collectives shuffle must beat "
                   "the driver relay")
    ROWS.append((f"dataset_shuffle_speedup_n{n}", 0.0, verdict))


# ---------------------------------------------------------------------------
# Serving: multi-replica continuous batching under an open-loop (Poisson)
# arrival process, at three traffic intensities. The gated row states
# the n=4 replica cluster against the identical single engine at the
# saturating intensity -- replica sharding must scale tokens/sec. A
# second server on the same pool runs speculative decoding (1-layer
# draft) and must surface its acceptance ratio in the traced snapshot.
# ---------------------------------------------------------------------------

SERVING_ACCEPTANCE = 2.0    # cluster n4 vs single engine, saturating load


def _serve_open_loop(server, reqs, rate_hz, seed, max_new):
    """Poisson (exponential inter-arrival) open-loop submission: clients
    do not wait for completions, so queueing delay is visible in the
    latencies. Returns (tokens, wall_seconds, sorted latencies)."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, len(reqs)))
    uids = []
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        while i < len(reqs) and arrivals[i] <= now:
            uids.append(server.submit(reqs[i], max_new_tokens=max_new))
            i += 1
        if server.outstanding():
            server.step_round()
        elif i < len(reqs):
            time.sleep(min(0.002, arrivals[i] - now))
        else:
            break
    wall = time.perf_counter() - t0
    res = server.results()
    tokens = sum(len(res[u]) for u in uids)
    lats = sorted(server.latency(u) for u in uids)
    return tokens, wall, lats


def bench_serving(quick: bool):
    from repro.core.cluster.driver import ExecutorPool
    from repro.core.cluster.launcher import CommandLauncher
    from repro.serve.cluster import ClusterServer, smoke_engine_spec

    n, s_max, slots, plen = 4, 64, 4, 6
    n_req = 12 if quick else 32
    max_new = 10 if quick else 16
    rates = (10.0, 100.0, 1000.0)   # req/s: light / moderate / saturating
    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, 100, plen).astype(np.int32)
            for _ in range(n_req)]
    build_engine, load_params = smoke_engine_spec(s_max=s_max, slots=slots)

    # single-replica baseline: the identical engine + admission
    # machinery, one driver-local replica
    single = ClusterServer(1, build_engine, load_params, mode="local",
                           quantum=8)
    for p in reqs[:2]:                  # compile outside the timed loop
        single.submit(p, max_new_tokens=2)
    single.run_until_drained()
    toks, wall, _ = _serve_open_loop(single, reqs, rates[-1], seed=7,
                                     max_new=max_new)
    tok_s_single = toks / wall
    ROWS.append(("serving_throughput_single_n1", 1e6 * wall / toks,
                 f"{tok_s_single:.1f} tok/s, {n_req} reqs at "
                 f"lam={rates[-1]:.0f}/s open-loop"))

    # serving executors run jax: spawned interpreters, never forks of a
    # jax-initialized driver. Generous liveness budget -- each replica
    # compiles its engine steps during the untimed warm-up drain.
    pool = ExecutorPool(n, backend="ring", timeout=600,
                        launcher=CommandLauncher(),
                        hb_interval=0.25, hb_timeout=60.0)
    try:
        srv = ClusterServer(n, build_engine, load_params, pool=pool,
                            quantum=8, round_timeout=600)
        for p in reqs[:n]:
            srv.submit(p, max_new_tokens=2)
        srv.run_until_drained()         # compile every replica, untimed
        tok_s_cluster = 0.0
        for rate, tag in zip(rates, ("low", "mid", "high")):
            toks, wall, lats = _serve_open_loop(srv, reqs, rate, seed=8,
                                                max_new=max_new)
            p50 = lats[len(lats) // 2]
            p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
            note = (f"lam={rate:.0f}/s open-loop Poisson, {n_req} reqs "
                    f"x {max_new} toks, n={n} replicas")
            ROWS.append((f"serving_latency_p50_{tag}_n{n}", p50 * 1e6,
                         note))
            ROWS.append((f"serving_latency_p99_{tag}_n{n}", p99 * 1e6,
                         note))
            if tag == "high":
                tok_s_cluster = toks / wall
        ROWS.append((f"serving_throughput_cluster_n{n}",
                     1e6 / tok_s_cluster,
                     f"{tok_s_cluster:.1f} tok/s at lam={rates[-1]:.0f}/s"))
        speedup = tok_s_cluster / tok_s_single
        verdict = (f"{speedup:.1f}x cluster n{n} vs single replica at "
                   f"lam={rates[-1]:.0f}/s (acceptance: "
                   f">={SERVING_ACCEPTANCE}x)")
        if speedup < SERVING_ACCEPTANCE:
            verdict = _concurrency_gate_failure(
                verdict + "; replica sharding must scale serving "
                "throughput")
        ROWS.append((f"serving_throughput_speedup_n{n}", 0.0, verdict))

        # speculative decoding on the same warm pool: fresh namespace,
        # 1-layer draft, traced rounds -- the acceptance ratio must be
        # visible in the traced snapshot (this presence check is never
        # waived; it needs no second core)
        spec_be, spec_lp = smoke_engine_spec(s_max=s_max, slots=slots,
                                             gamma=3, draft_layers=1)
        spec_srv = ClusterServer(n, spec_be, spec_lp, pool=pool,
                                 quantum=8, round_timeout=600, trace=True)
        for p in reqs[:6]:
            spec_srv.submit(p, max_new_tokens=max_new)
        spec_srv.run_until_drained()
        acc = spec_srv.acceptance_summary()
        tr = pool.last_trace
        traced = tr is not None and any(
            tr.counters(r).get("serve.spec.accept_ratio") is not None
            for r in range(pool.size))
        d = (f"accept_ratio={acc['ratio']:.3f} over {acc['rounds']} spec "
             f"rounds (gamma=3, 1-layer draft); traced counters "
             f"{'present' if traced else 'MISSING'}")
        if not traced:
            d = "FAILED: " + d
        ROWS.append((f"serving_spec_accept_ratio_n{n}", 0.0, d))
    finally:
        pool.shutdown()


# ---------------------------------------------------------------------------
# Wire codec: array payload round trip (decode copies exactly once via
# memoryview -- this row tracks the data-plane byte-moving cost).
# ---------------------------------------------------------------------------

def bench_wire_codec(quick: bool):
    from repro.core.cluster import wire
    mib = 8 if quick else 64
    arr = np.arange((mib << 20) // 8, dtype=np.float64)
    blob = wire.encode(arr)

    def roundtrip():
        out = wire.decode(wire.encode(arr))
        assert out.shape == arr.shape

    def decode_only():
        wire.decode(blob)

    bench(f"wire_codec_roundtrip_{mib}MiB", roundtrip, repeat=5)
    name, us, _ = ROWS[-1]
    ROWS[-1] = (name, us, f"{2 * arr.nbytes / (us * 1e-6) / 2**30:.1f} "
                "GiB/s enc+dec")
    bench(f"wire_codec_decode_{mib}MiB", decode_only, repeat=5)
    name, us, _ = ROWS[-1]
    ROWS[-1] = (name, us, f"{arr.nbytes / (us * 1e-6) / 2**30:.1f} GiB/s; "
                "one copy per array payload")


def bench_shm_ring_codec(quick: bool):
    """Raw SPSC ring throughput: one wire-frame-sized record written
    into and popped out of a shared-memory ring (one copy in, one copy
    out -- the same two copies the executor hot path pays). The TCP
    analogue is the kernel socket stack this tier bypasses."""
    from repro.core.cluster import shm as shm_mod
    mib = 4 if quick else 16
    payload = b"\xab" * (mib << 20)
    rings = shm_mod.ShmRings.create(nrings=1, cap=(mib << 20) + (1 << 12))
    try:
        def roundtrip():
            assert rings.write(0, payload)
            out = rings.try_read(0)
            assert len(out) == len(payload)

        bench(f"shm_ring_roundtrip_{mib}MiB", roundtrip, repeat=5)
        name, us, _ = ROWS[-1]
        ROWS[-1] = (name, us,
                    f"{2 * len(payload) / (us * 1e-6) / 2**30:.1f} GiB/s "
                    "write+read, one copy per side")
    finally:
        rings.close()
        shm_mod.unlink(rings.name)


def bench_spawn_launcher(quick: bool):
    """Quantify the multi-host bootstrap: a world spawned through the
    module-entry CLI (fresh interpreter + import + HMAC handshake per
    rank) vs the fork path, cold bootstrap and warm steady state. The
    point of the warm rows: once booted, a spawned world dispatches jobs
    exactly as fast as a forked one -- bootstrap cost is a one-time tax
    the persistent pool amortizes away."""
    from repro.core.cluster import ClusterPool, CommandLauncher, ForkLauncher
    n = 2 if quick else 4

    def ring(world):
        rank, size = world.get_rank(), world.get_size()
        if rank == 0:
            world.send(1, 0, 42)
            return world.receive(size - 1, 0)
        t = world.receive(rank - 1, 0)
        world.send((rank + 1) % size, 0, t)
        return t

    def boot_and_run(launcher):
        with ClusterPool(n, launcher=launcher, timeout=120) as pool:
            assert pool.run(ring)[0] == 42

    bench(f"listing2_ring_boot_fork_n{n}",
          lambda: boot_and_run(ForkLauncher()), repeat=2,
          derived="fork + HMAC handshakes + broker + 1 job")
    bench(f"listing2_ring_boot_spawn_n{n}",
          lambda: boot_and_run(CommandLauncher()), repeat=2,
          derived="module-entry subprocess: interpreter + import + "
                  "HMAC handshakes + broker + 1 job")
    fork_boot = row_value(f"listing2_ring_boot_fork_n{n}")
    spawn_boot = row_value(f"listing2_ring_boot_spawn_n{n}")

    pool = ClusterPool(n, launcher=CommandLauncher(), timeout=120)
    try:
        bench(f"listing2_ring_spawn_warm_n{n}",
              lambda: pool.run(ring), repeat=5,
              derived="persistent spawned pool steady state (direct "
                      "plane, authenticated channels)")
    finally:
        pool.shutdown()
    warm = row_value(f"listing2_ring_spawn_warm_n{n}")
    if fork_boot and spawn_boot and warm:
        ROWS.append((f"listing2_ring_spawn_bootstrap_tax_n{n}", 0.0,
                     f"spawn boot {spawn_boot / fork_boot:.1f}x fork boot; "
                     f"amortized over warm jobs ({spawn_boot / warm:.0f} "
                     "jobs repay it)"))


def bench_figure1_api_parity():
    """Figure 1: every MPIgnite method exists with the documented
    signature on both communicator implementations."""
    from repro.core import LocalComm, PeerComm, parallelize_func
    methods = ["send", "receive", "receive_async", "get_rank", "get_size",
               "split", "broadcast", "allreduce", "allgather",
               "reduce", "gather", "scatter",  # paper section-6 extensions
               "scan", "alltoall", "reducescatter",
               "isend", "irecv", "ibarrier", "ibcast",  # MPI-3 nonblocking
               "iallreduce", "iallgather", "ireduce", "igather",
               "iscatter", "iscan", "ialltoall", "ireducescatter"]
    missing = [m for m in methods if not hasattr(LocalComm, m)]
    peer = ["p2p", "shift", "rank", "size", "split", "broadcast",
            "allreduce", "allgather", "reducescatter", "alltoall",
            "reduce", "gather", "scatter", "scan",
            "ibarrier", "ibcast", "iallreduce", "iallgather",
            "ireduce", "igather", "iscatter", "iscan", "ialltoall",
            "ireducescatter"]
    missing += [m for m in peer if not hasattr(PeerComm, m)]
    assert not missing, missing
    ROWS.append(("figure1_api_parity", 0.0,
                 f"{len(methods)}+{len(peer)} methods present"))


# ---------------------------------------------------------------------------
# Phase-1 vs phase-2 vs native: analytic wire bytes (section 3.1) and
# measured SPMD step costs (subprocess with 8 forced host devices).
# ---------------------------------------------------------------------------

def bench_backend_byte_model():
    from repro.core import groups as G
    S = 64 * 2 ** 20   # 64 MiB payload
    for p in (16, 256):
        lin = G.collective_cost("allreduce", "linear", S, p)
        ring = G.collective_cost("allreduce", "ring", S, p)
        ROWS.append((f"allreduce_bytes_linear_p{p}", 0.0,
                     f"{lin.bytes_per_device/2**20:.0f}MiB/dev "
                     f"{lin.steps}steps"))
        ROWS.append((f"allreduce_bytes_ring_p{p}", 0.0,
                     f"{ring.bytes_per_device/2**20:.0f}MiB/dev "
                     f"{ring.steps}steps "
                     f"({lin.bytes_per_device/ring.bytes_per_device:.1f}x "
                     "less than phase-1)"))


def bench_spmd_backends_subprocess(quick: bool):
    """Wall-time of one 4 MiB allreduce on an 8-way SPMD mesh per backend
    (separate process: needs forced host devices)."""
    if quick:
        ROWS.append(("spmd_allreduce_backends", 0.0,
                     "skipped (--quick: compile-heavy)"))
        return
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time, jax, jax.numpy as jnp
from repro.core import parallelize_func
for backend in ["native", "ring", "linear"]:
    def f(world):
        return world.allreduce(
            jnp.ones((512, 1024), jnp.float32) * world.rank(), "add")
    c = parallelize_func(f, backend=backend)
    c.execute(8, mode="spmd")  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(5):
        c.execute(8, mode="spmd")
    print(f"{backend},{(time.perf_counter()-t0)/5*1e6:.0f}")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    if r.returncode != 0:
        ROWS.append(("spmd_allreduce_backends", -1.0,
                     "FAILED: " + r.stderr.strip()[-200:]))
        return
    for line in r.stdout.strip().splitlines():
        backend, us = line.split(",")
        ROWS.append((f"spmd_allreduce_4MiB_8dev_{backend}", float(us),
                     "wall time incl dispatch"))


#: row-name prefixes every run must produce -- the paper's empirical
#: artifacts. `--check` turns their absence into a nonzero exit so a CI
#: smoke step cannot silently pass while producing nothing.
REQUIRED_ROW_PREFIXES = (
    "listing1_matvec_local", "listing1_matvec_cluster",
    "listing2_ring_local", "listing2_ring_cluster",
    "listing2_ring_boot_spawn", "listing2_ring_spawn_warm",
    "listing2_ring_overlap_blocking", "listing2_ring_overlap_iallreduce",
    "listing2_ring_overlap_speedup",
    "listing2_ring_segmented_whole", "listing2_ring_segmented_chunked",
    "listing2_ring_segmented_speedup",
    "listing2_ring_shm_tcp", "listing2_ring_shm_n",
    "listing2_ring_shm_speedup", "shm_ring_roundtrip",
    "listing2_ring_tracing_off", "listing2_ring_tracing_on",
    "listing2_ring_tracing_overhead",
    "listing4_2d_matvec_local", "listing4_2d_matvec_cluster",
    "listing4_ckpt_sync_stall", "listing4_ckpt_async_overhead",
    "shrink_recovery_latency", "relaunch_recovery_latency",
    "shrink_vs_relaunch_speedup",
    "dataset_wordcount_collectives", "dataset_wordcount_gather",
    "dataset_shuffle_speedup",
    "serving_throughput_single", "serving_throughput_cluster",
    "serving_throughput_speedup", "serving_latency_p50",
    "serving_latency_p99", "serving_spec_accept_ratio",
    "figure1_api_parity", "wire_codec_roundtrip",
)


def check_rows(rows) -> list[str]:
    """Names of missing/failed expectations ([] means healthy)."""
    names = [n for n, _, _ in rows]
    problems = [f"missing required row {p}*" for p in REQUIRED_ROW_PREFIXES
                if not any(nm.startswith(p) for nm in names)]
    problems += [f"row {nm} FAILED: {d}" for nm, us, d in rows
                 if us < 0 or d.startswith("FAILED")]
    return problems


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: skip compile-heavy benches, shrink "
                         "payloads")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows as JSON (e.g. BENCH_<date>.json) "
                         "so the perf trajectory is tracked across PRs")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless every required listing row "
                         "was produced and none failed (CI smoke gate)")
    args = ap.parse_args()
    # before anything imports jax; spawned and forked children inherit it
    os.environ["JAX_PLATFORMS"] = "cpu"

    bench_listing1_matvec()
    bench_listing2_ring()
    bench_listing2_ring_overlap(args.quick)
    bench_listing2_ring_segmented(args.quick)
    bench_listing2_ring_shm(args.quick)
    bench_tracing_overhead(args.quick)
    bench_listing4_2d_matvec()
    bench_listing4_ckpt_async_overhead(args.quick)
    bench_shrink_recovery_latency(args.quick)
    bench_dataset_shuffle(args.quick)
    bench_serving(args.quick)
    bench_spawn_launcher(args.quick)
    bench_figure1_api_parity()
    bench_wire_codec(args.quick)
    bench_shm_ring_codec(args.quick)
    bench_backend_byte_model()
    bench_spmd_backends_subprocess(args.quick)

    from repro.core.cluster import shutdown_pools
    shutdown_pools()                       # warm benchmark pools

    print("name,us_per_call,derived")
    for name, us, derived in ROWS:
        print(f"{name},{us:.1f},{derived}")

    if args.json:
        doc = {
            "schema": "mpignite-bench-v1",
            "generated": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "quick": bool(args.quick),
            "rows": [{"name": n, "us_per_call": round(us, 1), "derived": d}
                     for n, us, d in ROWS],
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"# wrote {args.json} ({len(ROWS)} rows)", file=sys.stderr)

    if args.check:
        problems = check_rows(ROWS)
        if problems:
            for p in problems:
                print(f"# BENCH CHECK FAILED: {p}", file=sys.stderr)
            sys.exit(1)
        print(f"# bench check OK ({len(ROWS)} rows)", file=sys.stderr)


if __name__ == "__main__":
    main()
