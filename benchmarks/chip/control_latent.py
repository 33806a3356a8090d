#!/usr/bin/env python3
"""Readings that set the latent-attention serving cell's correctness
limit, in one process on the cell's chip. The benchmark's own runs never
run this. For each seed, a short window at the cell's own load, drained
and sampled as a run samples; the program's readings (the mean and the
widest gap of a served token below the float32 reference's best) and,
for the first ``--control-seeds`` seeds, the ``w8a16`` control's (the
same gap for the token the reference with int8 weights and bfloat16
activations puts first). One JSON line a seed.

    python3 benchmarks/chip/control_latent.py --workload <cell> \
        --seeds 1 2 3 [--seconds 15] [--control-seeds 3]
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from benchmarks.chip import spec
    from benchmarks.chip.drivers import serve_latent
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    cell = spec.load_cell(args.workload, ROOT)
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    for out in serve_latent.readings(cell, args.seeds, args.seconds,
                                     args.control_seeds):
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
