"""Latent decode step against the chip's HBM bandwidth: the bytes the
algorithm needs (every non-expert weight once a step, each held expert's
weights in every layer and step that routed it a row, the cached latents
of each active slot's live positions and its new row) over what the chip
could move in the decode program's device time."""
from benchmarks.chip import counts_latent
from benchmarks.chip.readers import program_device_ns


def read(record):
    got = program_device_ns(record, "jit_decode")
    touched = (record.get("counters") or {}).get("decode.moe.experts_touched")
    if got is None or touched is None or not record.get("decode_steps"):
        return None
    need = counts_latent.decode_bytes(record["model"], record["decode_steps"],
                                      touched, record["live"],
                                      record["active"])
    return 100.0 * need / (record["peaks"]["hbm_bytes_per_s"] * got[1] / 1e9)
