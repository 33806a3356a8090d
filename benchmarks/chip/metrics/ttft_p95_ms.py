"""Whole serving loop: 95th percentile of the time to first token over
the requests due in the window, from when each was due (host clock).
Over one window's requests it is the TTFT of one of the few largest
prompts, so it stands here beside the median that the bound holds."""
from benchmarks.chip.readers import percentile


def read(record):
    return percentile(record.get("ttft_ms", []), 95)
