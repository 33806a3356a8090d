"""Load generator: 95th percentile of how late each request was
submitted after it was due (host clock)."""
from benchmarks.chip.readers import percentile


def read(record):
    return percentile(record.get("gen_lag_ms", []), 95)
