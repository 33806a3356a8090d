"""Scheduler: 95th percentile of the time from a request's due time to
the start of its prefill call (host clock)."""
from benchmarks.chip.readers import percentile


def read(record):
    return percentile(record.get("admit_wait_ms", []), 95)
