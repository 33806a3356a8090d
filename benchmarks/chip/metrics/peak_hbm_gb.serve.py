"""Device: peak bytes in use (``memory_stats``), in GB."""
from benchmarks.chip.readers import peak_gb


def read(record):
    return peak_gb(record)
