"""Whole training step against the mesh's bf16 peak: 6*N FLOPs for every
token of the steps that finished in the window, over the window times
the chips; recomputation is not counted."""
from benchmarks.chip import counts


def read(record):
    if not record.get("tokens"):
        return None
    flops = counts.train_flops(record["model"], record["tokens"])
    return 100.0 * flops / (record["window_s"] * record["chips"]
                            * record["peaks"]["bf16_flops_per_s"])
