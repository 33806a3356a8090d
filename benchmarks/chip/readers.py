"""What the per-layer metric readers share: lookups in a run's record.

A reader returns None where its run recorded nothing to read, and the
harness then leaves the metric out of the result line."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float):
    return float(np.percentile(values, q)) if len(values) else None


def program_device_ns(record: dict, prefix: str):
    """(executions, device ns) of the compiled programs whose name starts
    with ``prefix``, on the first traced device; None when absent."""
    trace = record.get("trace")
    if not trace or not trace["devices"]:
        return None
    mods = trace["devices"][min(trace["devices"])]["modules"]
    hits = [v for k, v in mods.items() if k.startswith(prefix)]
    if not hits:
        return None
    return sum(n for n, _ in hits), sum(t for _, t in hits)


def idle_share(record: dict):
    """Per cent of the traced window in which no operation ran, mean over
    the devices."""
    trace = record.get("trace")
    if not trace or not trace["devices"] or not trace["window_ns"]:
        return None
    busy = [d["busy_ns"] for d in trace["devices"].values()]
    if not any(busy):
        return None
    return 100.0 * (1.0 - np.mean(busy) / trace["window_ns"])


def peak_gb(record: dict):
    b = record.get("memory_peak_bytes")
    return None if b is None else b / 1e9
