"""The command line without a chip, and whole runs on the CPU past the
look for a chip: a cell defined only in a temporary directory, and the
timed path broken underneath so that ``correct`` comes out false."""
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

from chip_cells import CHIP, ROOT, execute, serve_cell, train_cell

CMD = [sys.executable, "benchmarks/chip/run.py", "--workload",
       "danube1p8b.chat.steady", "--seed", "2147483653", "--seconds", "1",
       "--trace", "0"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


def _no_result(out: str) -> bool:
    return not any(line.strip().startswith("{") for line in out.splitlines())


def test_no_tpu_exits_nonzero_without_a_result():
    p = subprocess.run(CMD, cwd=ROOT, env=_env(), capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(CMD, cwd=tmp_path, env=_env(), capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_cell_from_a_temporary_directory_runs(tmp_path):
    root = serve_cell(tmp_path)
    out = execute(root, "smoke.chat", 2**31 + 11, 1.5)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 30
    assert set(out["metrics"]) == {"serve_tokens_per_s", "ttft_p50_ms",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    json.dumps(out)
    traced = execute(root, "smoke.chat", 2**31 + 11, 1.5, trace=True)
    # the reader that lives only in the temporary directory was found
    assert traced["metrics"]["requests_seen"]["value"] == 30.0
    assert {"gen_lag_ms_p95", "slot_occupancy", "admit_wait_ms_p95",
            "mfu.serve", "ttft_p95_ms"} <= set(traced["metrics"])
    assert "breakdown" in traced and "window_s" in traced["device"]


def test_altered_token_is_not_correct(tmp_path, monkeypatch):
    import repro.launch.serve as S
    real = S.build_engine

    def broken(*a, **k):
        eng = real(*a, **k)
        decode = eng.decode_fn

        def altered(params, caches, tokens, pos):
            logits, caches = decode(params, caches, tokens, pos)
            return logits.at[:, 7].add(100.0), caches
        eng.decode_fn = altered
        return eng
    monkeypatch.setattr(S, "build_engine", broken)
    out = execute(serve_cell(tmp_path), "smoke.chat", 5, 1.0)
    assert out["correct"] is False
    assert out["checks"]["logit_gap_mean"]["value"] > \
        out["checks"]["logit_gap_mean"]["limit"]


def _state_unchanged(decode):
    def step(params, caches, tokens, pos):
        logits, _ = decode(params, caches, tokens, pos)
        return logits, caches
    return step


def _half_batch(decode):
    def step(params, caches, tokens, pos):
        logits, caches = decode(params, caches, tokens, pos)
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:h]]), caches
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_serving_faults(tmp_path, monkeypatch, fault):
    """The decode step returning its caches unchanged, and half of the
    slots given the other half's logits: each is caught."""
    import repro.launch.serve as S
    real = S.build_engine

    def broken(*a, **k):
        eng = real(*a, **k)
        eng.decode_fn = fault(eng.decode_fn)
        return eng
    monkeypatch.setattr(S, "build_engine", broken)
    # arrivals fast enough to keep every slot busy, so both halves decode
    out = execute(serve_cell(tmp_path, rate=60.0), "smoke.chat", 2**31 + 21,
                  1.0)
    assert out["correct"] is False
    assert out["checks"]["logit_gap_mean"]["value"] > \
        out["checks"]["logit_gap_mean"]["limit"]


FAULTS = r'''
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src", sys.argv[2]]
from pathlib import Path
import jax, jax.numpy as jnp
from chip_cells import execute, train_cell
import repro.launch.train as T
import repro.parallel.ops as O
real_build, real_sync = T.build, O.ShardOps.sync_grads
fault = sys.argv[4]

def build(*a, **k):
    model, opt, step, ps = real_build(*a, **k)
    if fault == "unchanged":
        def step2(p, o, b):
            return p, o, {"loss": jnp.float32(0.0)}
        return model, opt, step2, ps
    if fault == "half_batch":
        def step2(p, o, b):
            t = b["tokens"]
            h = t.shape[0] // 2
            t = jnp.concatenate([t[:h], t[:h]])
            return step(p, o, {"tokens": jax.device_put(t, b["tokens"].sharding)})
        return model, opt, step2, ps
    return model, opt, step, ps

T.build = build
if fault == "no_exchange":
    O.ShardOps.sync_grads = lambda self, g, s, compress=None, ef=None: (g, ef)
out = execute(train_cell(Path(sys.argv[3])), "smoke.train", 2**31 + 3, 1.0)
print(json.dumps({"correct": out["correct"], "checks": out["checks"]}))
'''


@pytest.mark.parametrize("fault", ["none", "unchanged", "half_batch",
                                   "no_exchange"])
def test_training_faults(tmp_path, fault):
    """The 2x2 training cell on four host devices: sound, then with the
    step returning its state unchanged, with half the batch left out
    (the mean taken over the rest), and with the gradient exchange
    between chips left out."""
    env = dict(_env(), XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", FAULTS, str(ROOT), str(CHIP / "tests"),
         str(tmp_path), fault], env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is (fault == "none"), out["checks"]
