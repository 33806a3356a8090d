"""Device: per cent of the traced serving window with no operation."""
from benchmarks.chip.readers import idle_share


def read(record):
    return idle_share(record)
