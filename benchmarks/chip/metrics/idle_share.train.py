"""Device: per cent of the traced training window with no operation,
mean over the mesh's devices."""
from benchmarks.chip.readers import idle_share


def read(record):
    return idle_share(record)
