"""The limit readings of ``control.py`` at smoke widths on the CPU: the
program's reading, the control's, and for training the half-batch fault,
which reads far above the sound program."""
import json
import os
import subprocess
import sys

from chip_cells import CHIP, ROOT, serve_cell

TRAIN = r'''
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src", sys.argv[2]]
from pathlib import Path
from chip_cells import train_cell
from benchmarks.chip import control, spec
cell = spec.load_cell("smoke.train", train_cell(Path(sys.argv[3])))
cell.traffic["global_batch"] = 8       # half of it still spans 4 devices
control.train_readings(cell, [2**31 + 3], 1)
'''


def test_serving_readings(tmp_path, capsys):
    from benchmarks.chip import control, spec
    cell = spec.load_cell("smoke.chat", serve_cell(tmp_path))
    control.serve_readings(cell, [2**31 + 17], 1.0, 1)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["finished"] == out["requests"] == 20
    limit = cell.config["check"]["logit_gap_mean_limit"]
    assert 0 <= out["program"]["mean"] <= limit
    assert out["program"]["tokens"] == out["control"]["tokens"] > 0
    assert out["control"]["widest"] >= out["control"]["mean"] >= 0


def test_training_readings(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", TRAIN, str(ROOT), str(CHIP / "tests"),
         str(tmp_path)], env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    names = {"loss_gap", "grad_gap", "change_gap"}
    assert set(out["program"]) == set(out["control"]) == names
    # half of the batch moves the gradient's norm by a quarter or more
    assert out["half_batch"]["grad_gap"] > 100 * out["program"]["grad_gap"]
