"""Model step: device time of the prefill programs per thousand prompt
tokens prefilled in the window."""
from benchmarks.chip.readers import program_device_ns


def read(record):
    got = program_device_ns(record, "jit_prefill")
    if got is None or not record.get("prompt_tokens"):
        return None
    return got[1] / 1e6 / record["prompt_tokens"] * 1000.0
