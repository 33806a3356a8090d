"""Device: peak bytes in use on the fullest device of the mesh, in GB."""
from benchmarks.chip.readers import peak_gb


def read(record):
    return peak_gb(record)
