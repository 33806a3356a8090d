"""Per-rank runtime tracing: a low-overhead event recorder + exporters.

The message runtime (mailbox matching, segmented ring schedules, the
progress engines, the wire channels) is instrumented with *spans* --
``perf_counter_ns`` intervals recorded into a preallocated per-rank ring
buffer -- and merged at the driver into a per-job :class:`JobTrace` that
exports Chrome trace-event JSON (loadable in Perfetto / ``chrome://
tracing``) and plain metrics tables.

Design constraints, in order:

1. **The disabled path must cost nothing.** Tracing is off unless
   ``$MPIGNITE_TRACE`` is set (or a job was dispatched with
   ``trace=True``). Every instrumentation point in the runtime guards on
   ``tracer is not None`` / ``current_span() is not None`` -- a pointer
   compare -- and allocates nothing when the answer is no. Tests pin
   this with a tracemalloc filter over this module.
2. **The enabled path must be cheap.** Events are plain tuples appended
   to a preallocated ring buffer under one lock; when the buffer wraps,
   the *oldest* events are dropped (a counter records how many), so a
   long job degrades to "most recent window" instead of unbounded
   memory.
3. **Cross-process mergeable.** ``perf_counter_ns`` has a per-process
   epoch, so each tracer also records a wall-clock anchor
   (``time_ns - perf_counter_ns`` at construction); the exporter shifts
   every rank onto the wall clock, which same-host ranks share to well
   under a scheduling quantum. Multi-host merges inherit NTP skew --
   documented, not hidden.

Scoped host work outside the message runtime (the serving engine's
admission and decode steps) uses ``Tracer.span``: one context manager
that records the span into the ring *and* enters a
``jax.profiler.TraceAnnotation``, so the same span also lands on the
profiler's clock beside the device's operations. ``maybe_span`` is its
guard for instrumentation points whose tracer may be None.

Event tuples are ``(ph, cat, name, ts_ns, dur_ns, tid, args)`` where
``ph`` is the Chrome trace phase (``"X"`` complete span, ``"i"``
instant, ``"C"`` counter), ``ts_ns`` is raw ``perf_counter_ns``, and
``args`` is a small dict or None.

Track layout in the export: one *process* per rank (``pid = rank``,
named ``rank R/N``; the driver is ``pid = world``), and within a rank
one *thread* track per concurrency context (the calling thread for
blocking ops; one synthetic track per outstanding nonblocking schedule)
so overlapping spans never interleave on a single track and nesting --
collective > schedule step > segment -- renders correctly.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any

TRACE_ENV = "MPIGNITE_TRACE"
TRACE_EVENTS_ENV = "MPIGNITE_TRACE_EVENTS"
TRACE_FLUSH_ENV = "MPIGNITE_TRACE_FLUSH"
DEFAULT_CAPACITY = 32768
DEFAULT_FLUSH_INTERVAL = 1.0

#: pid used for driver-side events in the merged export (ranks use their
#: own number; the driver sits after them).
DRIVER_RANK = -1


def trace_enabled() -> bool:
    """Whether ``$MPIGNITE_TRACE`` asks for tracing ("", "0", "false",
    "off" and unset all mean no)."""
    raw = os.environ.get(TRACE_ENV)
    if not raw:                 # unset/empty: allocation-free fast path
        return False
    return raw.lower() not in ("0", "false", "off", "no")


def env_capacity() -> int:
    raw = os.environ.get(TRACE_EVENTS_ENV)
    if not raw:
        return DEFAULT_CAPACITY
    try:
        return max(16, int(raw))
    except ValueError:
        return DEFAULT_CAPACITY


def trace_flush_interval() -> float:
    """Seconds between *mid-job* incremental trace flushes from traced
    executors (``$MPIGNITE_TRACE_FLUSH``; values <= 0 disable streaming
    -- the end-of-job flush always happens). Each incremental frame is
    a cumulative snapshot that replaces the previous one driver-side,
    which is what makes ``pool.last_trace`` recoverable while a job is
    still running (or hung). Read in each traced executor at job start."""
    raw = os.environ.get(TRACE_FLUSH_ENV)
    if not raw:
        return DEFAULT_FLUSH_INTERVAL
    try:
        return float(raw)
    except ValueError:
        return DEFAULT_FLUSH_INTERVAL


# -- the active-collective span, per thread ---------------------------------
#
# Schedules perform their sends deep inside ``MessageComm._send_coll``,
# which does not know which collective it is serving. The span of the
# collective currently advancing *on this thread* lives here; senders
# attribute payload bytes to it. Blocking collectives set it around
# ``_run_sched``; the progress engine sets it around every generator
# resume (schedules interleave on the engine thread, but only one
# advances at a time, so a thread-local is exact).

_tls = threading.local()


def current_span() -> "CollSpan | None":
    return getattr(_tls, "span", None)


def set_current_span(span: "CollSpan | None") -> "CollSpan | None":
    """Install ``span`` as this thread's active collective; returns the
    previous one (restore it when done -- collectives nest via
    ``reducescatter``'s inner allgather)."""
    prev = getattr(_tls, "span", None)
    _tls.span = span
    return prev


class CollSpan:
    """One in-flight collective: accumulates the bytes/messages its
    schedule sends, plus identity for the exported span. Created only
    when tracing is enabled."""
    __slots__ = ("op", "backend", "p", "nbytes", "bytes", "msgs",
                 "t0", "tid", "overlap")

    #: total CollSpans ever constructed in this process -- the
    #: zero-allocation test pins that the disabled path creates none.
    created = 0

    def __init__(self, op: str, backend: str, p: int, nbytes: int,
                 t0: int, tid: str, overlap: bool = False):
        self.op = op
        self.backend = backend
        self.p = p
        self.nbytes = nbytes        # input payload size (cost-model S)
        self.bytes = 0              # payload bytes actually sent
        self.msgs = 0               # messages actually sent
        self.t0 = t0
        self.tid = tid
        self.overlap = overlap
        CollSpan.created += 1

    def add(self, nbytes: int) -> None:
        self.bytes += nbytes
        self.msgs += 1


class Tracer:
    """Per-rank event recorder over a preallocated ring buffer.

    Thread-safe: transport readers, the progress engine, heartbeat
    threads and the closure thread all record concurrently. ``events()``
    returns the surviving window oldest-first; ``snapshot()`` packages
    everything (events, drop counter, clock anchor, runtime counters)
    for shipment to the driver.
    """

    def __init__(self, rank: int, world: int, job: int = 0,
                 capacity: int | None = None):
        self.rank = rank
        self.world = world
        self.job = job
        self.capacity = env_capacity() if capacity is None else int(capacity)
        self._buf: list = [None] * self.capacity
        self._i = 0                 # next write slot
        self._n = 0                 # live events (<= capacity)
        self.dropped = 0
        self._lock = threading.Lock()
        self._track_seq = 0
        #: wall-clock anchor: add to any perf_counter_ns timestamp from
        #: this process to land on the (shared) wall clock.
        self.wall_minus_perf = time.time_ns() - time.perf_counter_ns()
        #: free-form runtime counters merged into the snapshot at flush
        #: (mailbox highs, channel byte totals, engine gauges).
        self.counters: dict[str, Any] = {}

    # -- recording ----------------------------------------------------------
    @staticmethod
    def now() -> int:
        return time.perf_counter_ns()

    def _record(self, ev: tuple) -> None:
        with self._lock:
            if self._buf[self._i] is not None:
                self.dropped += 1           # overwriting the oldest event
            self._buf[self._i] = ev
            self._i = (self._i + 1) % self.capacity
            if self._n < self.capacity:
                self._n += 1

    def complete(self, name: str, cat: str, t0: int, t1: int | None = None,
                 args: dict | None = None, tid: str | None = None) -> None:
        """Record a complete span ("X") from ``t0`` to ``t1`` (now if
        omitted), both ``perf_counter_ns``."""
        if t1 is None:
            t1 = time.perf_counter_ns()
        if tid is None:
            tid = threading.current_thread().name
        self._record(("X", cat, name, t0, max(0, t1 - t0), tid, args))

    def instant(self, name: str, cat: str = "", args: dict | None = None,
                tid: str | None = None) -> None:
        if tid is None:
            tid = threading.current_thread().name
        self._record(("i", cat, name, time.perf_counter_ns(), 0, tid, args))

    def counter(self, name: str, value: float, cat: str = "") -> None:
        self._record(("C", cat, name, time.perf_counter_ns(), 0, "counters",
                      {"value": value}))

    # -- scoped spans (both clocks) -----------------------------------------
    def span(self, name: str, cat: str = "", args: dict | None = None
             ) -> "Span":
        """Context manager: a complete span around the body on this
        thread's track, recorded when the body ends (also when it
        raises, with the exception's type under ``args["error"]``).
        The body also runs inside ``jax.profiler.TraceAnnotation(name,
        **args)``, so the span lands on the host plane of any running
        profiler trace -- the clock the device's operations are on.
        Spans nest by thread; a span's parent is the innermost one
        open around it."""
        return Span(self, name, cat, args)

    # -- collective spans ---------------------------------------------------
    def coll_begin(self, op: str, backend: str, p: int, nbytes: int,
                   overlap: bool = False) -> CollSpan:
        if overlap:
            with self._lock:
                self._track_seq += 1
                tid = f"sched-{self._track_seq}"
        else:
            tid = threading.current_thread().name
        return CollSpan(op, backend, p, nbytes, time.perf_counter_ns(),
                        tid, overlap=overlap)

    def coll_end(self, span: CollSpan, error: str | None = None) -> None:
        args = {"backend": span.backend, "p": span.p,
                "nbytes": span.nbytes, "sent_bytes": span.bytes,
                "sent_msgs": span.msgs, "overlap": span.overlap}
        if error is not None:
            args["error"] = error
        self.complete(span.op, "coll", span.t0, args=args, tid=span.tid)

    # -- readback -----------------------------------------------------------
    def events(self) -> list:
        """Surviving events, oldest first."""
        with self._lock:
            if self._n < self.capacity:
                return [e for e in self._buf[:self._n]]
            return (self._buf[self._i:] + self._buf[:self._i])

    def __len__(self) -> int:
        return self._n

    def snapshot(self) -> dict:
        """Everything the driver needs to merge this rank into a
        JobTrace (plain picklable data)."""
        return {"rank": self.rank, "world": self.world, "job": self.job,
                "wall_minus_perf": self.wall_minus_perf,
                "dropped": self.dropped, "events": self.events(),
                "counters": dict(self.counters)}


class Span:
    """One open ``Tracer.span``: the ring record and the profiler
    annotation around the same body."""
    __slots__ = ("tracer", "name", "cat", "args", "t0", "ann")

    def __init__(self, tracer: Tracer, name: str, cat: str,
                 args: dict | None):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self) -> "Span":
        # imported here, so that processes which never open a span (the
        # message runtime's executors) never import JAX for it
        from jax.profiler import TraceAnnotation
        self.ann = (TraceAnnotation(self.name, **self.args) if self.args
                    else TraceAnnotation(self.name))
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter_ns()
        self.ann.__exit__(exc_type, exc, tb)
        args = self.args
        if exc_type is not None:
            args = dict(args or {}, error=exc_type.__name__)
        self.tracer.complete(self.name, self.cat, self.t0, t1, args)


#: what ``maybe_span`` hands back with tracing off: one shared, stateless
#: context, so a disabled instrumentation point allocates nothing
_NO_SPAN = contextlib.nullcontext()


def maybe_span(tracer: Tracer | None, name: str, cat: str = "",
               args: dict | None = None):
    """``tracer.span(name, cat, args)``, or a shared do-nothing context
    when ``tracer`` is None (build ``args`` only when it is not)."""
    return _NO_SPAN if tracer is None else tracer.span(name, cat, args)


# ---------------------------------------------------------------------------
# Process-level tracer (SPMD trace-time records, boot-time spans)
# ---------------------------------------------------------------------------

_PROCESS: tuple[int, Tracer | None] | None = None
_PROCESS_LOCK = threading.Lock()


def process_tracer() -> Tracer | None:
    """The per-process tracer used outside any job (SPMD trace-time cost
    records, executor bootstrap spans). None when tracing is disabled.
    Keyed by pid so forked executors get their own."""
    global _PROCESS
    with _PROCESS_LOCK:
        if _PROCESS is None or _PROCESS[0] != os.getpid():
            _PROCESS = (os.getpid(),
                        Tracer(0, 1) if trace_enabled() else None)
        return _PROCESS[1]


def reset_process_tracer() -> None:
    """Test hook: force re-evaluation of ``$MPIGNITE_TRACE``."""
    global _PROCESS
    with _PROCESS_LOCK:
        _PROCESS = None


# ---------------------------------------------------------------------------
# Driver-side aggregation + exporters
# ---------------------------------------------------------------------------

class JobTrace:
    """One job's merged trace: per-rank snapshots plus (optionally) the
    driver's own events, on a common wall-clock timebase.

    ``to_chrome()`` emits Chrome trace-event JSON: one process per rank
    (named ``rank R/N``), spans nested collective -> schedule step ->
    segment on per-context thread tracks. ``table()`` is the plain
    metrics summary; ``cross_check()`` compares measured wire bytes per
    collective against the analytic ``groups.collective_cost`` model.
    """

    def __init__(self, job: int, world: int,
                 snapshots: dict[int, dict],
                 driver_snapshot: dict | None = None):
        self.job = job
        self.world = world
        self.snapshots = dict(snapshots)
        self.driver_snapshot = driver_snapshot

    @classmethod
    def from_tracers(cls, tracers, job: int = 0,
                     driver: "Tracer | None" = None) -> "JobTrace":
        """Build directly from in-process tracers (local mode)."""
        snaps = {t.rank: t.snapshot() for t in tracers if t is not None}
        world = max((t.world for t in tracers if t is not None), default=0)
        return cls(job, world, snaps,
                   driver.snapshot() if driver is not None else None)

    @property
    def ranks(self) -> list[int]:
        return sorted(self.snapshots)

    def dropped(self) -> int:
        return sum(s.get("dropped", 0) for s in self.snapshots.values())

    def events(self, rank: int) -> list:
        """One rank's events with timestamps shifted onto the wall clock
        (ns), oldest first."""
        snap = self.snapshots[rank]
        off = snap["wall_minus_perf"]
        return [(ph, cat, name, ts + off, dur, tid, args)
                for ph, cat, name, ts, dur, tid, args in snap["events"]]

    def counters(self, rank: int) -> dict:
        return dict(self.snapshots[rank].get("counters") or {})

    # -- Chrome trace-event export ------------------------------------------
    def to_chrome(self) -> dict:
        """Trace-event JSON (dict; ``json.dump`` it or use
        ``write_chrome``). Timestamps are wall-clock microseconds."""
        out: list[dict] = []

        def emit(pid: int, pname: str, snap: dict) -> None:
            out.append({"ph": "M", "pid": pid, "tid": 0,
                        "name": "process_name",
                        "args": {"name": pname}})
            off = snap["wall_minus_perf"]
            for ph, cat, name, ts, dur, tid, args in snap["events"]:
                ev = {"ph": ph, "pid": pid, "tid": str(tid), "name": name,
                      "cat": cat or "runtime",
                      "ts": (ts + off) / 1000.0}
                if ph == "X":
                    ev["dur"] = dur / 1000.0
                if ph == "i":
                    ev["s"] = "t"       # thread-scoped instant
                if ph == "C":
                    ev["args"] = {"value": (args or {}).get("value", 0)}
                elif args:
                    ev["args"] = args
                out.append(ev)

        for rank in self.ranks:
            emit(rank, f"rank {rank}/{self.world}", self.snapshots[rank])
        if self.driver_snapshot is not None:
            emit(self.world, "driver", self.driver_snapshot)
        return {"traceEvents": out,
                "displayTimeUnit": "ms",
                "otherData": {"job": self.job, "world": self.world,
                              "dropped_events": self.dropped()}}

    def write_chrome(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path

    # -- metrics summary ----------------------------------------------------
    def collectives(self) -> list[dict]:
        """Every collective span across ranks: op, backend, rank, group
        size, input nbytes, measured sent bytes/messages, duration."""
        rows = []
        for rank in self.ranks:
            for ph, cat, name, ts, dur, tid, args in self.events(rank):
                if ph == "X" and cat == "coll":
                    a = args or {}
                    rows.append({"rank": rank, "op": name,
                                 "backend": a.get("backend", "?"),
                                 "p": a.get("p", 0),
                                 "nbytes": a.get("nbytes", 0),
                                 "sent_bytes": a.get("sent_bytes", 0),
                                 "sent_msgs": a.get("sent_msgs", 0),
                                 "overlap": bool(a.get("overlap")),
                                 "dur_ns": dur, "ts_ns": ts})
        return rows

    def op_summary(self) -> dict[str, dict]:
        """Per-op totals across ranks: calls, wall ns (sum over ranks),
        wire bytes, messages."""
        summary: dict[str, dict] = {}
        for row in self.collectives():
            s = summary.setdefault(row["op"], {
                "calls": 0, "wall_ns": 0, "bytes": 0, "msgs": 0})
            s["calls"] += 1
            s["wall_ns"] += row["dur_ns"]
            s["bytes"] += row["sent_bytes"]
            s["msgs"] += row["sent_msgs"]
        return summary

    def table(self) -> str:
        """Plain-text metrics summary: per-op wall time + wire bytes,
        then per-rank runtime counters (wire totals, queue-depth highs,
        engine gauges)."""
        lines = [f"job {self.job} trace: {len(self.ranks)} ranks, "
                 f"{sum(len(self.snapshots[r]['events']) for r in self.ranks)}"
                 f" events, {self.dropped()} dropped"]
        summary = self.op_summary()
        if summary:
            lines.append(f"{'op':<16}{'calls':>6}{'wall_ms':>10}"
                         f"{'MiB_sent':>10}{'msgs':>7}")
            for op in sorted(summary, key=lambda o: -summary[o]["wall_ns"]):
                s = summary[op]
                lines.append(f"{op:<16}{s['calls']:>6}"
                             f"{s['wall_ns'] / 1e6:>10.2f}"
                             f"{s['bytes'] / 2**20:>10.3f}{s['msgs']:>7}")
        for rank in self.ranks:
            ctr = self.counters(rank)
            if ctr:
                kv = " ".join(f"{k}={v}" for k, v in sorted(ctr.items()))
                lines.append(f"rank {rank}: {kv}")
        return "\n".join(lines)

    def phase_breakdown(self) -> str:
        """One-line per-phase breakdown (benchmarks embed this in a
        derived column): top categories by total span time."""
        by_cat: dict[str, int] = {}
        for rank in self.ranks:
            for ph, cat, name, ts, dur, tid, args in self.events(rank):
                if ph == "X":
                    by_cat[cat or "runtime"] = \
                        by_cat.get(cat or "runtime", 0) + dur
        top = sorted(by_cat.items(), key=lambda kv: -kv[1])[:4]
        return " ".join(f"{c}={ns / 1e6:.1f}ms" for c, ns in top)

    def cross_check(self, rel_tol: float = 0.25,
                    abs_tol: int = 4096) -> list[dict]:
        """Measured-vs-analytic wire bytes per collective (the message
        runtime's twin of the SPMD HLO cross-check). See
        ``obs.metrics.cross_check_collectives`` for the rules."""
        from .metrics import cross_check_collectives
        return cross_check_collectives(self.collectives(), rel_tol=rel_tol,
                                       abs_tol=abs_tol)
