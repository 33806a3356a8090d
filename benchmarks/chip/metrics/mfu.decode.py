"""Whole decode step against the chip's bf16 peak: 2*N FLOPs for every
active slot's token over the decode program's device time."""
from benchmarks.chip import counts
from benchmarks.chip.readers import program_device_ns


def read(record):
    got = program_device_ns(record, "jit_decode")
    if got is None or not record.get("active"):
        return None
    flops = counts.infer_flops(record["model"], record["active"])
    return 100.0 * flops / (record["peaks"]["bf16_flops_per_s"] * got[1] / 1e9)
