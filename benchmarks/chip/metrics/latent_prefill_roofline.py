"""Latent prefill against the chip's bf16 peak: the operations the
algorithm needs for the prompts prefilled in the window (non-expert
weights per token, the head per prompt, each routed row through its
expert, and causal attention's quadratic part) over the prefill
programs' device time."""
from benchmarks.chip import counts_latent
from benchmarks.chip.readers import program_device_ns


def read(record):
    got = program_device_ns(record, "jit_prefill")
    routed = (record.get("counters") or {}).get("prefill.moe.routed_rows")
    if got is None or routed is None or not record.get("prompt_tokens"):
        return None
    flops = counts_latent.prefill_flops(record["model"],
                                        record["prompt_tokens"],
                                        record["prefills"], routed,
                                        record["prompt_pairs"])
    return 100.0 * flops / (record["peaks"]["bf16_flops_per_s"] * got[1] / 1e9)
