#!/usr/bin/env python3
"""Rate sweep of a serving cell, in one process: build and warm the
engine once, then offer the cell's mix at each rate for ``--seconds``
and drain. One JSON line per rate. The knee is the highest rate whose
backlog drains within a step or two of the window's close; the cell's
mix runs at about four fifths of it.

    python3 benchmarks/chip/sweep.py --workload <serving cell> \
        --seed <n> --seconds 20 --rates 2 3 4 5 6 8
"""
from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from benchmarks.chip import runtime, spec, traffic
    from benchmarks.chip.drivers import serve
    from benchmarks.chip.readers import percentile
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    cell = spec.load_cell(args.workload, ROOT)
    tracer = runtime.Tracer(False, "")
    compiles = runtime.CompileCounter()
    eng, wrap = serve.build(cell, args.seed, tracer)
    for k, rate in enumerate(args.rates):
        mix = dict(cell.traffic, arrival=dict(cell.traffic["arrival"],
                                              rate_per_s=rate))
        reqs = traffic.open_loop(mix, args.seed + k, args.seconds,
                                 cell.config["model"]["vocab"])
        w = serve.window(eng, wrap, reqs, args.seconds, tracer, compiles)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs),
            "finished": len(w.done), "drained_at_s": w.drained_at,
            "ttft_p95_ms": percentile(w.ttft_ms(), 95), **w.metrics(),
            "occupancy": w.occupancy_sum / max(w.occupancy_steps, 1),
            "compiles_in_window": w.compiles[0]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
