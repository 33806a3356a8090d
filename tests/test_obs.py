"""Observability plane: the per-rank tracer (ring buffer, scoped spans
on the ring and the profiler's clock, zero-cost disabled path), driver-side aggregation across real executor
processes, Perfetto/Chrome export, the measured-vs-analytic byte
cross-check, always-on runtime health counters, rank-tagged logging, and
heartbeat-RTT rank health."""
import json
import logging
import os
import signal
import threading
import time
import tracemalloc

import numpy as np
import pytest

from repro.core import parallelize_func
from repro.core.matching import Mailbox, ProgressEngine
from repro.core.obs import (ChannelStats, CollSpan, JobTrace, Tracer,
                            cross_check_collectives, get_logger,
                            trace_enabled)
from repro.core.obs import trace as trace_mod


# ---------------------------------------------------------------------------
# Tracer mechanics
# ---------------------------------------------------------------------------

def test_trace_enabled_parsing(monkeypatch):
    for off in [None, "", "0", "false", "OFF", "no"]:
        if off is None:
            monkeypatch.delenv(trace_mod.TRACE_ENV, raising=False)
        else:
            monkeypatch.setenv(trace_mod.TRACE_ENV, off)
        assert not trace_enabled(), off
    for on in ["1", "true", "yes", "perfetto"]:
        monkeypatch.setenv(trace_mod.TRACE_ENV, on)
        assert trace_enabled(), on


def test_ring_buffer_wraps_oldest_first():
    tr = Tracer(0, 1, capacity=8)
    for i in range(20):
        tr.instant(str(i))
    assert len(tr) == 8
    assert tr.dropped == 12                 # the 12 oldest were overwritten
    names = [e[2] for e in tr.events()]
    assert names == [str(i) for i in range(12, 20)]     # newest window,
    ts = [e[3] for e in tr.events()]                    # oldest first
    assert ts == sorted(ts)


def test_span_nesting_args_and_error():
    tr = Tracer(0, 1, capacity=64)
    with tr.span("outer", "t"):
        with tr.span("inner", "t", {"uid": 7}) as sp:
            assert sp.name == "inner"
    with pytest.raises(ValueError):
        with tr.span("fails", "t", {"uid": 8}):
            raise ValueError("boom")
    (inner, outer, fails) = tr.events()
    # recorded as each body ends: the inner span first, inside the outer
    assert [e[2] for e in (inner, outer)] == ["inner", "outer"]
    assert outer[3] <= inner[3]
    assert inner[3] + inner[4] <= outer[3] + outer[4]
    assert inner[0] == outer[0] == "X" and inner[1] == "t"
    assert inner[6] == {"uid": 7} and outer[6] is None
    # a body that raises still records its span, and says what it raised
    assert fails[6] == {"uid": 8, "error": "ValueError"}
    assert inner[5] == outer[5] == threading.current_thread().name


PROFILED_SPAN = r'''
import glob, json, sys
import jax, jax.numpy as jnp
from jax.profiler import ProfileData
from repro.core.obs import Tracer

tr = Tracer(0, 1, capacity=16)
jax.profiler.start_trace(sys.argv[1])
try:
    with tr.span("serve.admit", "serve", {"uid": 3, "prompt_len": 9}):
        jnp.ones(8).block_until_ready()
finally:
    jax.profiler.stop_trace()
(path,) = glob.glob(sys.argv[1] + "/plugins/profile/*/*.xplane.pb")
found = [{"plane": plane.name, "dur": e.duration_ns,
          "stats": {k: v for k, v in e.stats}}
         for plane in ProfileData.from_file(path).planes
         for line in plane.lines for e in line.events
         if e.name == "serve.admit"]
print(json.dumps({"found": found, "ring": [e[2] for e in tr.events()]}))
'''


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    """The same span, read back from a ``jax.profiler`` trace on the CPU:
    named as in the ring, its args as the event's stats, on the host
    plane whose clock the device's operations share. (Its own process:
    this one forks executors, which must not follow a JAX start-up.)"""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", PROFILED_SPAN, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    (ev,) = out["found"]
    assert ev["plane"].startswith("/host:") and ev["dur"] > 0
    assert ev["stats"] == {"uid": 3, "prompt_len": 9}
    assert out["ring"] == ["serve.admit"]


def test_coll_span_accumulates_and_exports():
    tr = Tracer(2, 4, job=7)
    span = tr.coll_begin("allreduce", "segmented", 4, 1000)
    span.add(300)
    span.add(450)
    tr.coll_end(span)
    (ph, cat, name, ts, dur, tid, args), = tr.events()
    assert (ph, cat, name) == ("X", "coll", "allreduce")
    assert args["sent_bytes"] == 750 and args["sent_msgs"] == 2
    assert args["backend"] == "segmented" and args["p"] == 4
    # overlap spans land on synthetic tracks so they never interleave
    s2 = tr.coll_begin("iallreduce", "ring", 4, 1000, overlap=True)
    assert s2.tid.startswith("sched-")


# ---------------------------------------------------------------------------
# Local mode end to end: spans balanced, export valid, bytes cross-check
# ---------------------------------------------------------------------------

def _traced_local(n=4, segment_bytes=4096):
    def closure(comm):
        r = comm.get_rank()
        x = np.full(2048, float(r), np.float64)     # 16 KiB
        s = comm.with_segment_bytes(segment_bytes).with_backend("ring")
        r1 = s.allreduce(x, np.add)                 # segmented upgrade
        r2 = s.iallreduce(x, np.add).wait()         # nonblocking twin
        b = comm.broadcast(0, x if r == 0 else None)
        comm.barrier()
        return float(r1.sum() + r2.sum() + b.sum())

    closure_rdd = parallelize_func(closure, trace=True)
    out = closure_rdd.execute(n, mode="local")
    assert len(set(out)) == 1
    jt = closure_rdd.last_trace
    assert isinstance(jt, JobTrace)
    return jt


def test_local_trace_spans_balanced_per_rank():
    jt = _traced_local()
    assert jt.ranks == [0, 1, 2, 3]
    for rank in jt.ranks:
        colls = [e for e in jt.events(rank)
                 if e[0] == "X" and e[1] == "coll"]
        # every collective the closure ran closed exactly once, no errors
        assert sorted(e[2] for e in colls) == sorted(
            ["allreduce", "iallreduce", "broadcast", "barrier"])
        assert all("error" not in (e[6] or {}) for e in colls)
        ctr = jt.counters(rank)
        assert ctr["engine.pending"] == 0       # nothing leaked
        assert ctr["mb.waiting"] == 0
        assert ctr["mb.total_matched"] > 0


def test_local_trace_cross_check_exact():
    jt = _traced_local()
    checks = jt.cross_check()
    assert checks, "expected checkable collectives"
    assert all(v["ok"] for v in checks), checks
    # the segmented ring realizes the analytic model *exactly*
    seg = [v for v in checks if v["backend"] == "segmented"]
    assert seg and all(v["measured"] == v["expected"] for v in seg)
    # both the blocking and the nonblocking allreduce produced rows
    assert len(seg) == 2 * len(jt.ranks)


def test_chrome_export_roundtrips_and_nests(tmp_path):
    jt = _traced_local()
    path = jt.write_chrome(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.loads(f.read())              # valid JSON end to end
    evs = doc["traceEvents"]
    metas = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert metas == {f"rank {r}/4" for r in range(4)}   # one track per rank
    for ev in evs:
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
    # segment spans nest inside their owning collective's [ts, ts+dur]
    for pid in range(4):
        colls = [e for e in evs if e["ph"] == "X" and e.get("cat") == "coll"
                 and e["pid"] == pid
                 and e.get("args", {}).get("backend") == "segmented"]
        segs = [e for e in evs if e["ph"] == "X" and e.get("cat") == "seg"
                and e["pid"] == pid]
        assert colls and segs
        for s in segs:
            assert any(c["ts"] <= s["ts"] + 1e-3 and
                       s["ts"] + s["dur"] <= c["ts"] + c["dur"] + 1e-3
                       for c in colls if c["tid"] == s["tid"]), \
                (s, [c for c in colls if c["tid"] == s["tid"]])
    assert doc["otherData"]["dropped_events"] == 0


def test_disabled_mode_zero_events_zero_allocations(monkeypatch):
    """The whole point of the guards: with $MPIGNITE_TRACE unset a run
    creates no spans, no tracers, and performs zero allocations inside
    the trace module (tracemalloc filename filter pins it)."""
    monkeypatch.delenv(trace_mod.TRACE_ENV, raising=False)

    def closure(comm):
        x = np.full(512, float(comm.get_rank()), np.float64)
        s = comm.with_segment_bytes(1024).with_backend("ring")
        r = s.allreduce(x, np.add)
        r2 = s.iallreduce(x, np.add).wait()
        comm.barrier()
        return float(r.sum() + r2.sum())

    rdd = parallelize_func(closure)
    rdd.execute(2, mode="local")                # warm code paths first
    created_before = CollSpan.created
    tracemalloc.start()
    try:
        rdd.execute(2, mode="local")
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    assert rdd.last_trace is None
    assert CollSpan.created == created_before   # no spans constructed
    stats = snap.filter_traces(
        [tracemalloc.Filter(True, trace_mod.__file__)]).statistics("lineno")
    assert not stats, [str(s) for s in stats]   # zero trace.py allocations


def test_env_flag_enables_local_tracing(monkeypatch):
    monkeypatch.setenv(trace_mod.TRACE_ENV, "1")

    def closure(comm):
        comm.barrier()
        return comm.get_rank()

    rdd = parallelize_func(closure)             # trace=None: follow env
    rdd.execute(2, mode="local")
    assert isinstance(rdd.last_trace, JobTrace)
    assert rdd.last_trace.collectives()


# ---------------------------------------------------------------------------
# Always-on health counters (no tracing required)
# ---------------------------------------------------------------------------

def test_mailbox_health_counters():
    mb = Mailbox()
    mb.put(0, 1, 0, "a")
    mb.put(0, 2, 0, "b")
    h = mb.health()
    assert h["depth"] == 2 and h["peak_depth"] == 2
    assert mb.get(0, 1, 0, 1.0) == "a"
    h = mb.health()
    assert h["depth"] == 1 and h["peak_depth"] == 2
    assert h["total_matched"] == 1 and h["poisoned_waiters"] == 0


def test_progress_engine_gauges():
    eng = ProgressEngine(name="gauge-test")
    g = eng.gauges()
    assert g["submitted"] == 0 and g["completed"] == 0
    assert g["pending"] == 0 and not g["thread_alive"]

    def closure(comm):
        r = comm.iallreduce(np.ones(4), np.add).wait()
        return float(r[0])

    rdd = parallelize_func(closure, trace=True)
    rdd.execute(2, mode="local")
    for rank in rdd.last_trace.ranks:
        ctr = rdd.last_trace.counters(rank)
        assert ctr["engine.submitted"] == 1
        assert ctr["engine.completed"] == 1
        assert ctr["engine.wakeups"] >= 1
        assert ctr["engine.peak_pending"] == 1


def test_channel_stats_totals_and_per_peer():
    st = ChannelStats()
    st.on_tx(-1, 100)
    st.on_tx(2, 50)
    st.on_rx(2, 70)
    s = st.summary()
    assert s["tx_frames"] == 2 and s["tx_bytes"] == 150
    assert s["rx_frames"] == 1 and s["rx_bytes"] == 70
    assert s["peers"][-1] == {"tx_frames": 1, "tx_bytes": 100,
                              "rx_frames": 0, "rx_bytes": 0,
                              "shm_tx_bytes": 0, "shm_rx_bytes": 0}
    assert s["peers"][2]["rx_bytes"] == 70


def test_channel_stats_shm_counters_are_subsets_of_totals():
    """An shm frame counts in *both* the shm counters and the totals
    (the frame is byte-identical to its TCP form), so the byte
    cross-check holds whatever transport the broker picked."""
    st = ChannelStats()
    st.on_tx(3, 100, shm=True)
    st.on_tx(3, 40)
    st.on_rx(3, 60, shm=True)
    s = st.summary()
    assert s["tx_frames"] == 2 and s["tx_bytes"] == 140
    assert s["shm_tx_frames"] == 1 and s["shm_tx_bytes"] == 100
    assert s["rx_frames"] == 1 and s["rx_bytes"] == 60
    assert s["shm_rx_frames"] == 1 and s["shm_rx_bytes"] == 60
    assert s["peers"][3]["shm_tx_bytes"] == 100
    assert s["peers"][3]["shm_rx_bytes"] == 60
    assert s["shm_tx_bytes"] <= s["tx_bytes"]
    assert s["shm_rx_bytes"] <= s["rx_bytes"]


# ---------------------------------------------------------------------------
# Cross-check unit behavior (scopes, skips, failure detection)
# ---------------------------------------------------------------------------

def _row(op, backend, p, nbytes, sent, rank=0, overlap=False):
    return {"rank": rank, "op": op, "backend": backend, "p": p,
            "nbytes": nbytes, "sent_bytes": sent, "sent_msgs": 1,
            "overlap": overlap, "dur_ns": 1, "ts_ns": 0}


def test_cross_check_scopes_and_skips():
    p, S = 4, 16384
    rows = []
    for r in range(p):      # segmented allreduce: per-rank, 2S(p-1)/p
        rows.append(_row("allreduce", "segmented", p, S,
                         2 * S * (p - 1) // p, rank=r))
    # linear broadcast: group total (p-1)*S concentrated at the root
    rows.append(_row("broadcast", "linear", p, S, (p - 1) * S, rank=0))
    for r in range(1, p):
        rows.append(_row("broadcast", "linear", p, S, 0, rank=r))
    # whole-buffer ring allreduce: deliberately unpriced -> skipped
    rows.append(_row("allreduce", "ring", p, S, (p - 1) * S))
    rows.append(_row("barrier", "linear", p, 0, 0))     # no byte model
    checks = cross_check_collectives(rows)
    assert all(v["ok"] for v in checks), checks
    assert len([v for v in checks if v["scope"] == "per-rank"]) == p
    assert len([v for v in checks if v["scope"] == "group-total"]) == 1
    assert not any(v["backend"] == "ring" for v in checks)


def test_cross_check_flags_byte_drift():
    p, S = 4, 1 << 20
    rows = [_row("allreduce", "segmented", p, S, 2 * S * (p - 1) // p // 2,
                 rank=r) for r in range(p)]     # half the modeled bytes
    checks = cross_check_collectives(rows)
    assert checks and all(not v["ok"] for v in checks)
    # the i-prefixed twin maps onto the same model
    irows = [_row("iallreduce", "segmented", p, S, 2 * S * (p - 1) // p,
                  rank=r, overlap=True) for r in range(p)]
    assert all(v["ok"] for v in cross_check_collectives(irows))


# ---------------------------------------------------------------------------
# Rank-tagged logging
# ---------------------------------------------------------------------------

def test_rank_logger_prefixes():
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    log = logging.getLogger("mpignite.obs_test")
    log.addHandler(handler)
    log.setLevel(logging.DEBUG)
    try:
        rl = get_logger("obs_test")
        rl.bound(rank=2, world=8, job=5).warning("boom %d", 7)
        rl.bound(rank=1).info("partial")
        rl.debug("unbound")
        msgs = [r.getMessage() for r in records]
        assert msgs == ["[rank 2/8 job 5] boom 7", "[rank 1] partial",
                        "unbound"]
    finally:
        log.removeHandler(handler)


# ---------------------------------------------------------------------------
# Cluster mode: aggregation at the driver, RTT health, the acceptance job
# ---------------------------------------------------------------------------

@pytest.mark.cluster
@pytest.mark.timeout(180)
def test_cluster_traced_8rank_segmented_iallreduce(tmp_path):
    """The PR's acceptance scenario: a traced 8-rank cluster job running
    segmented iallreduce on the direct data plane produces a valid
    Chrome trace with one track per rank and nested spans, and the
    measured wire bytes agree with ``groups.collective_cost``."""
    from repro.core.cluster import ExecutorPool

    def closure(comm):
        r = comm.get_rank()
        x = np.full(4096, float(r), np.float64)     # 32 KiB
        s = comm.with_segment_bytes(8192).with_backend("ring")
        red = s.iallreduce(x, np.add).wait()
        comm.barrier()
        return float(red.sum())

    with ExecutorPool(8, backend="linear", timeout=120.0,
                      data_plane="direct") as pool:
        out = pool.run(closure, trace=True)
        assert len(set(out)) == 1
        jt = pool.last_trace
        assert isinstance(jt, JobTrace) and jt.ranks == list(range(8))
        assert pool.frame_counts["msg"] == 0        # stayed on the
        assert pool.frame_counts["trace"] == 8      # direct plane

        checks = jt.cross_check()
        seg = [v for v in checks if v["backend"] == "segmented"
               and v["op"] == "allreduce"]
        assert len(seg) == 8 and all(v["ok"] for v in seg), checks
        # exact agreement: 2*S*(p-1)/p per rank
        assert all(v["measured"] == v["expected"] == 2 * 32768 * 7 // 8
                   for v in seg)

        path = jt.write_chrome(str(tmp_path / "trace.json"))
        with open(path) as f:
            doc = json.loads(f.read())
        evs = doc["traceEvents"]
        metas = {e["args"]["name"] for e in evs if e["ph"] == "M"}
        assert metas == {f"rank {r}/8" for r in range(8)}
        # the overlapped collective rides a synthetic sched track with
        # its segment spans nested inside it
        for pid in range(8):
            coll = [e for e in evs if e["ph"] == "X"
                    and e.get("cat") == "coll" and e["pid"] == pid
                    and e["name"] == "iallreduce"]
            assert len(coll) == 1 and coll[0]["tid"].startswith("sched-")
            c = coll[0]
            segs = [e for e in evs if e["ph"] == "X"
                    and e.get("cat") == "seg" and e["pid"] == pid
                    and e["tid"] == c["tid"]]
            assert segs
            assert all(c["ts"] <= s["ts"] + 1e-3 and
                       s["ts"] + s["dur"] <= c["ts"] + c["dur"] + 1e-3
                       for s in segs)

        # runtime counters came along: wire totals and engine gauges
        for rank in jt.ranks:
            ctr = jt.counters(rank)
            assert ctr["chan.tx_bytes"] > 0 and ctr["chan.rx_bytes"] > 0
            assert ctr["engine.completed"] == 1
            assert ctr["engine.pending"] == 0

        # second, untraced job: disabled path leaves no trace behind
        assert pool.run(closure) is not None
        assert pool.last_trace is None


@pytest.mark.cluster
@pytest.mark.timeout(120)
def test_rank_health_rtt_and_sigstop():
    """``pool.rank_health()``: every rank reports a measured heartbeat
    RTT, and a SIGSTOPped executor's last-seen age grows while the
    others stay fresh (the wedged-process signal), recovering on
    SIGCONT."""
    from repro.core.cluster import ExecutorPool

    with ExecutorPool(3, timeout=60.0, hb_interval=0.05,
                      hb_timeout=30.0) as pool:
        deadline = time.time() + 10.0
        while time.time() < deadline:
            health = pool.rank_health()
            if all(h["rtt"] is not None for h in health):
                break
            time.sleep(0.05)
        health = pool.rank_health()
        assert all(h["alive"] and not h["conn_dead"] for h in health)
        assert all(h["rtt"] is not None and h["rtt"] < 5.0
                   for h in health)

        victim = pool.pids[1]
        os.kill(victim, signal.SIGSTOP)
        try:
            time.sleep(0.6)
            health = {h["rank"]: h for h in pool.rank_health()}
            assert health[1]["last_seen_age"] > 0.4     # heartbeats froze
            assert health[0]["last_seen_age"] < 0.4     # peers keep beating
            assert health[2]["last_seen_age"] < 0.4
            assert health[1]["alive"]       # stopped, not dead
        finally:
            os.kill(victim, signal.SIGCONT)
        deadline = time.time() + 10.0
        while time.time() < deadline:       # recovers once resumed
            if {h["rank"]: h for h in pool.rank_health()}[1][
                    "last_seen_age"] < 0.3:
                break
            time.sleep(0.05)
        assert {h["rank"]: h for h in pool.rank_health()}[1][
            "last_seen_age"] < 0.3


@pytest.mark.cluster
@pytest.mark.timeout(120)
def test_streaming_flush_surfaces_partial_trace_mid_job(
        tmp_path, monkeypatch):
    """Mid-job trace recovery: executors stream incremental trace
    frames every ``MPIGNITE_TRACE_FLUSH`` seconds, so when one rank is
    SIGSTOPped mid-job the driver's ``pool.last_trace`` already holds
    the *other* ranks' spans while the job is still wedged -- the
    post-mortem view a dead job used to take to the grave."""
    from repro.core.cluster import ExecutorPool

    monkeypatch.setenv("MPIGNITE_TRACE_FLUSH", "0.2")
    stop_flag = str(tmp_path / "parked")
    go_flag = str(tmp_path / "go")

    def closure(comm):
        r = comm.get_rank()
        x = comm.allreduce(np.arange(64, dtype=np.int64), np.add)
        if r == 1:
            open(stop_flag, "w").close()
            while not os.path.exists(go_flag):
                time.sleep(0.02)
        comm.barrier()
        return int(x.sum())

    with ExecutorPool(3, timeout=90.0, hb_interval=0.05,
                      hb_timeout=60.0) as pool:
        result: dict = {}

        def run():
            result["out"] = pool.run(closure, trace=True, timeout=90.0)

        t = threading.Thread(target=run)
        t.start()
        deadline = time.time() + 30.0
        while not os.path.exists(stop_flag) and time.time() < deadline:
            time.sleep(0.02)
        assert os.path.exists(stop_flag), "rank 1 never parked"
        victim = pool.pids[1]
        os.kill(victim, signal.SIGSTOP)
        try:
            # ranks 0 and 2 are parked in the barrier; their flush
            # threads keep streaming. Poll until their allreduce spans
            # surface on the driver while the job is still running.
            got_ranks: set = set()
            deadline = time.time() + 20.0
            while time.time() < deadline:
                jt = pool.last_trace
                if jt is not None:
                    got_ranks = {row["rank"] for row in jt.collectives()
                                 if row["op"] == "allreduce"}
                    if {0, 2} <= got_ranks:
                        break
                time.sleep(0.05)
            assert t.is_alive(), "job finished before the partial check"
            assert {0, 2} <= got_ranks, got_ranks
        finally:
            os.kill(victim, signal.SIGCONT)
        open(go_flag, "w").close()
        t.join(timeout=60.0)
        assert not t.is_alive()
        assert result["out"] == [int(np.arange(64).sum()) * 3] * 3
        # the end-of-job flush completes the picture: all three ranks
        rows = pool.last_trace.collectives()
        assert {row["rank"] for row in rows
                if row["op"] == "allreduce"} == {0, 1, 2}
