#!/usr/bin/env python3
"""One benchmark run of one cell, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Runs in this one process, which holds every chip the cell uses. Set-up
(weights, compiles or cache loads, warm-up) is timed from process start
to the window's first instant; the window then runs for ``--seconds``;
what it produced is checked against ``reference.py``. With ``--trace 0``
the result line holds the cell's end-to-end metrics; with ``--trace 1``
the window runs under the profiler and the line holds its per-layer
metrics, read by ``metrics/<name>.py``.

Without a TPU, or with fewer devices than the cell asks for, it exits 2
and prints no result. The last line of standard output is the result's
JSON object; the numbers compared for ``correct`` are also the last
lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                          # noqa: E402
import json                                              # noqa: E402
import os                                                # noqa: E402
import shutil                                            # noqa: E402
import sys                                               # noqa: E402
import tempfile                                          # noqa: E402
import types                                             # noqa: E402
from pathlib import Path                                 # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def execute(cell, seed: int, seconds: float, trace: bool, devices,
            peaks: dict, t_start: float = T_START) -> dict:
    """Run the cell's driver and turn what it returns into the result
    object. Device checks are the caller's."""
    from benchmarks.chip import runtime, spec, trace_reduce

    ctx = types.SimpleNamespace(
        compiles=runtime.CompileCounter(), log=log,
        tracer=runtime.Tracer(trace, tempfile.mkdtemp(prefix="bench_trace")))
    driver = spec.load_driver(cell.dirs, cell.config["job"])
    try:
        res = driver(cell, seed, seconds, ctx)
        setup_s = res["setup_end"] - t_start
        c = res["compiles"]
        log(f"compiles: set-up {c['setup']} (persistent-cache hits "
            f"{c['setup_hits']}), window {c['window']}, after the window "
            f"{c['after']}")
        dev = devices[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": res["memory_peak_bytes"]}
        out = {"correct": bool(res["correct"]),
               "attempted": res["attempted"], "failed": res["failed"]}
        if not trace:
            vals = dict(res["metrics"], setup_s=setup_s)
            out["metrics"] = {m["name"]: {"value": vals[m["name"]],
                                          "unit": m["unit"]}
                              for m in cell.end_to_end}
        else:
            path = ctx.tracer.trace_file()
            window_s = ctx.tracer.window_s
            summary = trace_reduce.reduce_file(path, window_s * 1e9,
                                               chips=cell.chips)
            log(f"trace: {os.path.getsize(path)} bytes, "
                f"{len(summary['devices'])} device(s)")
            record = dict(res["record"], trace=summary, peaks=peaks,
                          memory_peak_bytes=res["memory_peak_bytes"],
                          chips=cell.chips)
            metrics = {}
            for m in cell.per_layer:
                v = spec.load_reader(cell.dirs, m["name"])(record)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            out["metrics"] = metrics
            device["busy_s"] = trace_reduce.busy_s(summary)
            device["window_s"] = window_s
            out["breakdown"] = trace_reduce.breakdown(summary)
        out["device"] = device
        out["checks"] = res["checks"]
        return out
    finally:
        shutil.rmtree(ctx.tracer.log_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import spec
    cell = spec.load_cell(args.workload, ROOT)

    import jax
    from repro.launch.cache import use_compile_cache
    cache_dir = use_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU: JAX found platform {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peaks = spec.peaks_for(devices[0].device_kind)
    log(f"{cell.name} seed {args.seed}: {len(devices)} x "
        f"{devices[0].device_kind}, jax {jax.__version__}, compile cache "
        f"{cache_dir}")
    out = execute(cell, args.seed, args.seconds, bool(args.trace), devices,
                  peaks)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
