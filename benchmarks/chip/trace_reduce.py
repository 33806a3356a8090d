"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: per device, the union of busy intervals, time per
operation and per compiled program, and the collective time during
which no other operation ran; and the host's ``bench.*`` spans, on the
same clock, to say what the host was doing in each idle gap.

The file is read with ``jax.profiler.ProfileData``; nothing else.
"""
from __future__ import annotations

import re
from collections import defaultdict

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
#: control flow that encloses other operations on the same line; kept
#: for the busy union, left out of the time per operation
CONTAINERS = ("while", "conditional", "call")
#: HLO opcodes that move data between devices; instruction names come
#: from the program (``psum_invariant.7`` is an all-reduce), so an
#: operation is told by its opcode, never by its name
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all|send|recv)"
                        r"(-start|-done)?$")
#: the opcode: the first lower-case word followed by "(" after " = "
#: (layouts in the type, such as ``T(8,128)``, are upper case)
OPCODE = re.compile(r"(?<![\w.\-])([a-z][a-z0-9\-]*)\(")


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def opcode(event_name: str) -> str:
    """``%psum.7 = bf16[8]{0} all-reduce(bf16[8]{0} %x), ...`` ->
    ``all-reduce``; an event with no HLO text gives its name."""
    if " = " not in event_name:
        return event_name
    m = OPCODE.search(event_name.split(" = ", 1)[1])
    return m.group(1) if m else event_name


def is_collective(event_name: str) -> bool:
    return bool(COLLECTIVE.match(opcode(event_name)))


def merge(iv: np.ndarray) -> np.ndarray:
    """Union of [start, end) intervals, as sorted disjoint intervals."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    stops = ends[np.append(idx[1:] - 1, len(iv) - 1)]
    return np.stack([starts, stops], axis=1)


def measure(merged: np.ndarray) -> float:
    return float(np.sum(merged[:, 1] - merged[:, 0])) if len(merged) else 0.0


def minus(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the union ``a`` not covered by the union ``b`` (both
    merged)."""
    if len(a) == 0:
        return 0.0
    return measure(a) - measure(merge(np.concatenate(
        [_clip(b, s, e) for s, e in a] or [np.zeros((0, 2))])))


def _clip(b: np.ndarray, s: float, e: float) -> np.ndarray:
    lo = np.searchsorted(b[:, 1], s, side="right")
    hi = np.searchsorted(b[:, 0], e, side="left")
    part = b[lo:hi].copy()
    if len(part):
        part[:, 0] = np.maximum(part[:, 0], s)
        part[:, 1] = np.minimum(part[:, 1], e)
    return part


def gaps(busy: np.ndarray, start: float, end: float) -> np.ndarray:
    """Idle intervals of a merged busy set inside [start, end)."""
    edges = np.concatenate([[start], busy.ravel(), [end]]).reshape(-1, 2)
    edges[:, 0] = np.clip(edges[:, 0], start, end)
    edges[:, 1] = np.clip(edges[:, 1], start, end)
    return edges[edges[:, 1] > edges[:, 0]]


def reduce_events(devices: dict, spans: list, start: float,
                  end: float) -> dict:
    """``devices``: {id: {"ops": [(event name, start, end)], "async":
    [...], "modules": [...]}}, ops and async ops named by their HLO text;
    ``spans``: [(name, start, end)] host spans; times in ns. Returns the
    summary the metric readers and the breakdown use. Busy time is the
    union of the operations on the compute stream; collective time also
    counts the asynchronous collectives that run beside it."""
    out = {"window_ns": end - start, "devices": {}}
    span_iv = sorted(spans, key=lambda s: s[1])

    def clip(events):
        return [(n, max(s, start), min(e, end)) for n, s, e in events
                if e > start and s < end]

    def iv(events):
        return np.array([(s, e) for _, s, e in events], float).reshape(-1, 2)

    for dev, ev in sorted(devices.items()):
        ops = clip(ev["ops"])
        busy = merge(iv(ops))
        coll = [o for o in ops + clip(ev.get("async", []))
                if is_collective(o[0])]
        comp = [o for o in ops if not is_collective(o[0])]
        per_op = defaultdict(float)
        for n, s, e in ops:
            name = op_name(n)
            if name.split(".")[0] not in CONTAINERS:
                per_op[name] += e - s
        per_mod = defaultdict(lambda: [0, 0.0])
        for n, s, e in ev["modules"]:
            per_mod[n][0] += 1
            per_mod[n][1] += e - s
        idle = gaps(busy, start, end)
        out["devices"][dev] = {
            "busy_ns": measure(busy),
            "ops_ns": dict(per_op),
            "modules": {k: tuple(v) for k, v in per_mod.items()},
            "collective_ns": measure(merge(iv(coll))),
            "exposed_collective_ns": minus(merge(iv(coll)), merge(iv(comp))),
            "idle_by_host": _attribute(idle, span_iv),
        }
    return out


def _attribute(idle: np.ndarray, spans: list, depth: int = 16) -> dict:
    """Idle ns by what the host was doing at each gap's midpoint: the
    innermost (latest-starting) ``bench.*`` span around it, looked for
    among the ``depth`` spans that started last before it."""
    by = defaultdict(float)
    starts = np.array([s for _, s, _ in spans], float)
    for s, e in idle:
        mid = (s + e) / 2
        name = "host: no benchmark span"
        j = int(np.searchsorted(starts, mid, side="right")) - 1
        for k in range(j, max(j - depth, -1), -1):
            if spans[k][2] >= mid:
                name = spans[k][0]
                break
        by[name] += e - s
    return dict(by)


def read_xplane(path: str) -> tuple[dict, list, float, float]:
    """Device op and module events, host spans, and the span of all
    device and span events (ns)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    lo, hi = np.inf, -np.inf
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ev = {"ops": [], "async": [], "modules": []}
            lines = {OPS_LINE: "ops", ASYNC_LINE: "async",
                     MODULES_LINE: "modules"}
            for line in plane.lines:
                key = lines.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    s = float(e.start_ns)
                    ev[key].append((e.name, s, s + float(e.duration_ns)))
            devices[int(m.group(1))] = ev
            for _, s, e in ev["ops"]:
                lo, hi = min(lo, s), max(hi, e)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = float(e.start_ns)
                        spans.append((e.name, s, s + float(e.duration_ns)))
    for _, s, e in spans:
        lo, hi = min(lo, s), max(hi, e)
    return devices, spans, lo, hi


def reduce_file(path: str, window_ns: float | None = None,
                chips: int | None = None) -> dict:
    """Reduce one trace. The window runs from the first recorded event
    for ``window_ns`` (the host's measure of the traced window), or to
    the last event when that is not given. ``chips`` keeps devices
    0..chips-1."""
    devices, spans, lo, hi = read_xplane(path)
    if chips is not None:
        devices = {d: v for d, v in devices.items() if d < chips}
    if not np.isfinite(lo):
        lo, hi = 0.0, 0.0
    end = lo + window_ns if window_ns else hi
    return reduce_events(devices, spans, lo, end)


def breakdown(summary: dict, top: int = 10) -> dict:
    """Top device operations and longest idle causes, in seconds,
    averaged over the devices."""
    devs = list(summary["devices"].values())
    n = max(len(devs), 1)
    ops, idle = defaultdict(float), defaultdict(float)
    for d in devs:
        for k, v in d["ops_ns"].items():
            ops[k] += v / n / 1e9
        for k, v in d["idle_by_host"].items():
            idle[k] += v / n / 1e9
    key = lambda kv: -kv[1]
    return {"device_ops": [[k, v] for k, v in sorted(ops.items(),
                                                     key=key)[:top]],
            "idle_gaps": [[k, v] for k, v in sorted(idle.items(),
                                                    key=key)[:top]]}


def busy_s(summary: dict) -> float:
    devs = list(summary["devices"].values())
    return sum(d["busy_ns"] for d in devs) / max(len(devs), 1) / 1e9
