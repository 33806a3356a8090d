"""Cells defined only in a temporary directory, at smoke widths, for the
CPU tests: a ``BENCHMARK.json``, a configuration file, a traffic mix and a
metric reader of their own, nothing edited under ``benchmarks/chip``."""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CHIP = ROOT / "benchmarks" / "chip"
#: the registry's smoke widths, with a vocabulary that no mesh axis of the
#: tests pads (the program pads it to 32 rows a model shard)
SMOKE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             d_ff=128, vocab=128, window=32)

#: a per-layer metric that exists only in the temporary directory
NEW_READER = '''
def read(record):
    return float(record["requests"])
'''


def serve_cell(tmp: Path, rate: float = 20.0) -> Path:
    """A serving cell ``smoke.chat`` under ``tmp``; returns ``tmp``."""
    conf = json.loads((CHIP / "configs" / "danube1p8b-serve.json").read_text())
    conf["model"].update(SMOKE)
    conf["reduced"] = sorted(SMOKE)
    conf["deployment"].update(slots=4, s_max=64)
    # every finished request is checked; at smoke widths a sound run on
    # the CPU reads a mean gap of 1.6e-5, a decode that keeps its old
    # caches 1.6e-3, half of the slots given the other half's logits 0.026
    conf["check"].update(sample_tokens=1000, logit_gap_mean_limit=3e-4)
    mix = {"generator": "open_loop",
           "arrival": {"kind": "poisson", "rate_per_s": rate},
           "prompt_len": {"kind": "choice", "values": [8, 16, 24],
                          "weights": [0.4, 0.4, 0.2]},
           "output_len": {"kind": "lognormal", "median": 6, "sigma": 0.6,
                          "min": 2, "max": 12}}
    metrics = ["gen_lag_ms_p95", "slot_occupancy", "admit_wait_ms_p95",
               "mfu.serve", "ttft_p95_ms", "requests_seen"]
    _write(tmp, "smoke-serve", conf, "smoke.chat", "smoke_chat", mix, 1,
           ["serve_tokens_per_s", "ttft_p50_ms"], metrics)
    return tmp


def train_cell(tmp: Path) -> Path:
    """A 2x2 training cell ``smoke.train`` under ``tmp`` (four devices)."""
    conf = json.loads(
        (CHIP / "configs" / "danube1p8b-train-2x2.json").read_text())
    conf["model"].update(SMOKE)
    conf["reduced"] = sorted(SMOKE)
    conf["deployment"].update(microbatches=2)
    conf["optimizer"].update(lr_peak=2e-3, warmup_steps=1, total_steps=50)
    # the numbers the real cell compares; sound runs on the CPU read
    # about 1e-3 for each, half of the batch 0.42 for the gradient
    conf["check"].update(grad_gap_limit=0.02, change_gap_limit=0.02)
    mix = {"generator": "token_batch", "global_batch": 4, "seq": 64}
    _write(tmp, "smoke-train", conf, "smoke.train", "smoke_tr", mix, 4,
           ["train_tokens_per_s"], ["mfu.train"])
    return tmp


def _write(tmp, cname, conf, wname, tname, mix, chips, e2e, per_layer):
    chip = tmp / "bench"
    (chip / "traffic").mkdir(parents=True, exist_ok=True)
    (chip / "metrics").mkdir(exist_ok=True)
    (chip / "traffic" / f"{tname}.json").write_text(json.dumps(mix))
    (chip / "metrics" / "requests_seen.py").write_text(NEW_READER)
    (tmp / "cfg.json").write_text(json.dumps(conf))
    units = {"ttft_p50_ms": "ms"}
    bench = {
        "paths": ["bench"],
        "configs": [{"name": cname, "file": "cfg.json"}],
        "workloads": [{"name": wname, "config": cname, "traffic": tname,
                       "chips": chips}],
        "end_to_end": [{"name": n, "unit": units.get(n, "tokens/s"),
                        "workloads": [wname]} for n in e2e]
        + [{"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": n, "unit": "x", "workloads": [wname]}
                      for n in per_layer]}
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))


def execute(root: Path, workload: str, seed: int, seconds: float,
            trace: bool = False) -> dict:
    """One run of the cell through the harness, past its look for a chip."""
    import importlib.util
    import time

    import jax

    from benchmarks.chip import spec
    mod_spec = importlib.util.spec_from_file_location(
        "chip_run_entry", CHIP / "run.py")
    run = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(run)
    cell = spec.load_cell(workload, root)
    return run.execute(cell, seed, seconds, trace, jax.devices(),
                       spec.peaks_for("TPU v5 lite"), time.perf_counter())
