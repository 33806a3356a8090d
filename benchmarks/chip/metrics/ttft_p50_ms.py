"""Whole serving loop: median time to first token over the requests due
in the window, from when each was due (host clock)."""
from benchmarks.chip.readers import percentile


def read(record):
    return percentile(record.get("ttft_ms", []), 50)
