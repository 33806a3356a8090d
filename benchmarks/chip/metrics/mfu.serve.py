"""Whole serving loop against the chip's bf16 peak: 2*N FLOPs for every
prompt token prefilled and every token decoded in the window, over the
window."""
from benchmarks.chip import counts


def read(record):
    tokens = record.get("prompt_tokens", 0) + record.get("active", 0)
    if not tokens:
        return None
    flops = counts.infer_flops(record["model"], tokens)
    return 100.0 * flops / (record["window_s"]
                            * record["peaks"]["bf16_flops_per_s"])
