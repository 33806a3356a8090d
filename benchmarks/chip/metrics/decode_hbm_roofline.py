"""Whole decode step against the chip's HBM bandwidth: the bytes the
algorithm needs (every weight once, the live keys and values of each
active slot, the new token's keys and values) over what the chip could
move in the decode program's device time. Live positions, not the
cache's allocated length, so a step that stops reading dead cache shows
as a rise."""
from benchmarks.chip import counts
from benchmarks.chip.readers import program_device_ns


def read(record):
    got = program_device_ns(record, "jit_decode")
    if got is None or not record.get("decode_steps"):
        return None
    need = counts.decode_bytes(record["model"], record["decode_steps"],
                               record["live"], record["active"])
    return 100.0 * need / (record["peaks"]["hbm_bytes_per_s"] * got[1] / 1e9)
