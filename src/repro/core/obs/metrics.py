"""Always-on runtime metrics + the measured-vs-analytic cost cross-check.

Two halves:

- :class:`ChannelStats` -- cheap per-channel tx/rx byte and frame
  counters kept by the cluster wire layer regardless of tracing (integer
  adds; no allocation on the hot path).
- :func:`cross_check_collectives` -- compares the payload bytes a traced
  collective *actually* sent against ``groups.collective_cost``'s
  analytic prediction. The analytic model is what the byte-model rows of
  ``benchmarks/run.py`` are built on, so a drift here means either the
  model or the schedule is wrong.

Cross-check rules (the "documented overhead allowance" in the README):

- Measured bytes are *payload* bytes counted where the schedule hands a
  message to the transport (``matching.payload_nbytes``), so wire
  framing/HMAC/pickle overhead never enters; the slack covers the small
  meta messages segmented schedules lead with and the rounding of
  near-equal chunking (``chunk_bounds``).
- Each (op, backend) pair is checked at the scope where the
  implementation and the model actually describe the same quantity
  (``_CHECKS``):

  * ``allreduce/segmented`` -- per rank. The segmented reduce-scatter +
    all-gather schedule is exactly the model's bandwidth-optimal ring:
    every rank moves ``2*S*(p-1)/p`` bytes.
  * ``allreduce/linear``, ``broadcast/linear`` -- group total. The relay
    concentrates traffic at the root (root moves O(p*S), leaves S), and
    the model's ``bytes_per_device`` equals the *total* relay volume.
  * ``broadcast/ring|segmented`` -- group total. The pass-along ring
    moves S per hop over p-1 hops; the model's ``(p-1)*S`` counts the
    same bytes summed over the ring (per-device in SPMD, where every
    device participates in each ppermute hop).

- Combinations *not* in the table are skipped, deliberately: the
  whole-buffer ring allreduce circulates full payloads ((p-1)*S per
  rank) and is not the chunked algorithm the ring model prices -- the
  segmented upgrade is what realizes that model on the message runtime.
- ``barrier`` and 0-byte payloads are skipped (pure latency, no byte
  model).
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field

from ..groups import collective_cost

#: (base op, backend) -> comparison scope. Scopes: "per-rank" compares
#: every rank's sent bytes against ``bytes_per_device``; "group-total"
#: compares the sum over one call's spans (all ranks) against it.
_CHECKS = {
    ("allreduce", "segmented"): "per-rank",
    ("allreduce", "linear"): "group-total",
    ("broadcast", "linear"): "group-total",
    ("broadcast", "ring"): "group-total",
    ("broadcast", "segmented"): "group-total",
}

_I_OPS = ("allreduce", "broadcast", "allgather", "reducescatter",
          "alltoall", "barrier", "bcast", "gather", "scatter", "reduce",
          "scan")


@dataclass
class ChannelStats:
    """Tx/rx totals for one executor's wire channels (control plane +
    every peer link). Updated from socket read/write paths; all fields
    monotonic."""
    tx_frames: int = 0
    tx_bytes: int = 0
    rx_frames: int = 0
    rx_bytes: int = 0
    #: frames/bytes that traveled the shared-memory rings instead of a
    #: socket. These are *subsets* of the totals above (an shm frame is
    #: byte-identical to its TCP form and counts in both), so the
    #: measured-vs-analytic byte cross-check holds regardless of which
    #: transport the broker picked.
    shm_tx_frames: int = 0
    shm_tx_bytes: int = 0
    shm_rx_frames: int = 0
    shm_rx_bytes: int = 0
    #: per-peer-rank breakdown; the driver appears as rank -1.
    per_peer: dict = field(default_factory=dict)
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False)

    def _peer(self, peer: int) -> dict:
        p = self.per_peer.get(peer)
        if p is None:
            p = self.per_peer[peer] = {"tx_frames": 0, "tx_bytes": 0,
                                       "rx_frames": 0, "rx_bytes": 0,
                                       "shm_tx_bytes": 0,
                                       "shm_rx_bytes": 0}
        return p

    def on_tx(self, peer: int, nbytes: int, shm: bool = False) -> None:
        with self._lock:
            self.tx_frames += 1
            self.tx_bytes += nbytes
            p = self._peer(peer)
            p["tx_frames"] += 1
            p["tx_bytes"] += nbytes
            if shm:
                self.shm_tx_frames += 1
                self.shm_tx_bytes += nbytes
                p["shm_tx_bytes"] += nbytes

    def on_rx(self, peer: int, nbytes: int, shm: bool = False) -> None:
        with self._lock:
            self.rx_frames += 1
            self.rx_bytes += nbytes
            p = self._peer(peer)
            p["rx_frames"] += 1
            p["rx_bytes"] += nbytes
            if shm:
                self.shm_rx_frames += 1
                self.shm_rx_bytes += nbytes
                p["shm_rx_bytes"] += nbytes

    def summary(self) -> dict:
        with self._lock:
            return {"tx_frames": self.tx_frames, "tx_bytes": self.tx_bytes,
                    "rx_frames": self.rx_frames, "rx_bytes": self.rx_bytes,
                    "shm_tx_frames": self.shm_tx_frames,
                    "shm_tx_bytes": self.shm_tx_bytes,
                    "shm_rx_frames": self.shm_rx_frames,
                    "shm_rx_bytes": self.shm_rx_bytes,
                    "peers": {k: dict(v) for k, v in self.per_peer.items()}}


@dataclass
class AcceptanceStats:
    """Speculative-decoding acceptance accounting for one engine.

    The draft model proposes ``gamma`` tokens per slot per spec round;
    the target accepts a prefix. This tracks the aggregate ratio (the
    number every capacity model of speculative decoding turns on) plus
    a live per-request breakdown so a finished ``Generation`` can carry
    its own acceptance ratio. Per-request entries are popped when the
    request finishes, so memory stays bounded by in-flight requests,
    not by requests served.
    """
    proposed: int = 0
    accepted: int = 0
    rounds: int = 0
    #: uid -> [proposed, accepted] for requests still in flight
    live: dict = field(default_factory=dict)

    def record(self, uid: int, proposed: int, accepted: int) -> None:
        self.proposed += proposed
        self.accepted += accepted
        self.rounds += 1
        ent = self.live.setdefault(uid, [0, 0])
        ent[0] += proposed
        ent[1] += accepted

    def pop_request(self, uid: int) -> float | None:
        """Finish one request: drop its live entry, return its mean
        acceptance ratio (None when it never ran a spec round)."""
        ent = self.live.pop(uid, None)
        if ent is None or ent[0] == 0:
            return None
        return ent[1] / ent[0]

    @property
    def ratio(self) -> float:
        """Aggregate accepted/proposed over the engine's lifetime."""
        return self.accepted / max(self.proposed, 1)

    def summary(self) -> dict:
        return {"proposed": self.proposed, "accepted": self.accepted,
                "rounds": self.rounds, "ratio": self.ratio}

    def publish(self, tracer, prefix: str = "serve.spec") -> None:
        """Drop the aggregate into a Tracer's free-form counters so the
        ratio lands in the job's traced snapshot (JobTrace.counters)."""
        if tracer is None:
            return
        tracer.counters[f"{prefix}.proposed"] = self.proposed
        tracer.counters[f"{prefix}.accepted"] = self.accepted
        tracer.counters[f"{prefix}.rounds"] = self.rounds
        tracer.counters[f"{prefix}.accept_ratio"] = round(self.ratio, 4)


def base_op(op: str) -> str:
    """``iallreduce`` -> ``allreduce`` etc.; the byte model is identical,
    only the overlap flag differs."""
    return op[1:] if op.startswith("i") and op[1:] in _I_OPS else op


def cross_check_collectives(rows: list[dict], rel_tol: float = 0.25,
                            abs_tol: int = 4096) -> list[dict]:
    """Compare traced collective spans against the analytic byte model.

    ``rows`` come from ``JobTrace.collectives()``. Returns one verdict
    dict per checked site with ``ok``, ``measured``, ``expected`` and
    the comparison scope; callers assert ``all(v["ok"] for v in
    verdicts)``. Ops/backends outside the documented ``_CHECKS`` table
    are ignored (see module docstring for why).
    """
    verdicts: list[dict] = []

    def tol(expected: int) -> float:
        return max(abs_tol, rel_tol * expected)

    sites: dict[tuple, list[dict]] = {}
    for r in rows:
        base = base_op(r["op"])
        scope = _CHECKS.get((base, r["backend"]))
        if scope is None or r["nbytes"] <= 0 or r["p"] <= 1:
            continue
        sites.setdefault((base, r["backend"], r["p"], r["nbytes"], scope),
                         []).append(r)

    for (base, backend, p, nbytes, scope), group in sorted(
            sites.items(), key=lambda kv: str(kv[0])):
        expected = collective_cost(base, backend, nbytes, p).bytes_per_device
        if scope == "group-total":
            # the group may hold several identical calls (every rank
            # contributes one span per call) -- normalize per call.
            calls = max(1, round(len(group) / p))
            measured = sum(r["sent_bytes"] for r in group) / calls
            verdicts.append({
                "op": base, "backend": backend, "p": p, "nbytes": nbytes,
                "scope": scope, "calls": calls,
                "measured": int(measured), "expected": expected,
                "ok": abs(measured - expected) <= tol(expected)})
        else:
            for r in group:
                verdicts.append({
                    "op": base, "backend": backend, "p": p,
                    "nbytes": nbytes, "rank": r["rank"],
                    "scope": scope, "calls": 1,
                    "measured": r["sent_bytes"], "expected": expected,
                    "ok": abs(r["sent_bytes"] - expected) <= tol(expected)})
    return verdicts


def format_cross_check(verdicts: list[dict]) -> str:
    lines = [f"{'op':<14}{'backend':<11}{'p':>3}{'scope':>13}"
             f"{'measured':>12}{'expected':>12}  ok"]
    for v in verdicts:
        lines.append(
            f"{v['op']:<14}{v['backend']:<11}{v['p']:>3}{v['scope']:>13}"
            f"{v['measured']:>12}{v['expected']:>12}  "
            f"{'yes' if v['ok'] else 'NO'}")
    return "\n".join(lines)
