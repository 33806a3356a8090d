#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, in one process on the
cell's chips. The benchmark's own runs never run this.

Serving cells: for each seed, a short window at the cell's own load,
drained and sampled as a run samples; then the program's readings (the
mean and the widest gap of a served token below the float32
reference's best) and,
for the first ``--control-seeds`` seeds, the control's (the same gap for
the token that the reference with int8 weights and bfloat16 activations,
``w8a16``, puts first).

Training cells: for each seed, the numbers a run compares, read between
the program's first steps and the float32 reference (one build of the
step serves every seed); and for the first ``--control-seeds`` seeds the
same numbers between the reference in the control's precision (int8
weights and activations, ``w8a8``) and the sound reference, and between a fault a training cell can have (half of
the batch left out, which on the 2-way data axis is also what leaving
out the gradient exchange between data shards does) and the sound
reference. A step that returns its state unchanged reads 1 by
construction and needs no run.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3 \
        [--seconds 15] [--control-seeds 4]
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def serve_readings(cell, seeds, seconds, n_control):
    from benchmarks.chip import runtime, traffic
    from benchmarks.chip.drivers import serve

    tracer = runtime.Tracer(False, "")
    compiles = runtime.CompileCounter()
    for k, seed in enumerate(seeds):
        t0 = time.perf_counter()
        eng, wrap = serve.build(cell, seed, tracer)
        reqs = traffic.open_loop(cell.traffic, seed, seconds,
                                 cell.config["model"]["vocab"])
        w = serve.window(eng, wrap, reqs, seconds, tracer, compiles)
        sample = serve.sample_requests(
            w.done, seed, cell.config["check"]["sample_tokens"])
        eng.params = eng.caches = None
        del eng, wrap
        gc.collect()
        out = {"seed": seed, "finished": len(w.done), "requests": len(reqs),
               "program": serve.reference_gaps(cell, seed, sample)}
        if k < n_control:
            out["control"] = serve.reference_gaps(cell, seed, sample,
                                                  quant="w8a16")
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)


def train_readings(cell, seeds, n_control):
    import contextlib

    from benchmarks.chip.drivers import train

    check = cell.config["check"]
    half = int(cell.traffic["global_batch"]) // 2
    b = train.build(cell)
    devices = list(b.mesh.devices.flat)
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        state, prog = train.first_steps(b, cell, seed,
                                        lambda _: contextlib.nullcontext())
        del state
        gc.collect()
        sound = train.reference_run(cell, seed, devices, check["steps"])
        out = {"seed": seed, "program_losses": prog["losses"],
               "reference_losses": sound["losses"],
               "program": train.readings(prog, sound)}
        if i < n_control:
            for name, kw in (("control", {"quant": "w8a8"}),
                             ("half_batch", {"rows": half})):
                var = train.reference_run(cell, seed, devices, check["steps"],
                                          **kw)
                out[name] = train.readings(var, sound)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--control-seeds", type=int, default=4)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from benchmarks.chip import spec
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    cell = spec.load_cell(args.workload, ROOT)
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    if cell.config["job"] == "serve":
        serve_readings(cell, args.seeds, args.seconds, args.control_seeds)
    else:
        train_readings(cell, args.seeds, args.control_seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
