"""Model step: device time of the decode program per execution, from
the trace."""
from benchmarks.chip.readers import program_device_ns


def read(record):
    got = program_device_ns(record, "jit_decode")
    return None if got is None else got[1] / got[0] / 1e6
