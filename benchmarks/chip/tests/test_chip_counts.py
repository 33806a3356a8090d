"""Operation and byte counts against hand counts at h2o-danube-1.8b
widths, and the peaks table."""
import json

import pytest

from chip_cells import CHIP
from benchmarks.chip import counts, spec

DANUBE = json.loads(
    (CHIP / "configs" / "danube1p8b-serve.json").read_text())["model"]


def test_parameters_by_hand():
    # attention: q 2560x2560, k and v 2560x640 each, o 2560x2560
    attn = 2560 * 2560 + 2 * 2560 * 640 + 2560 * 2560
    mlp = 3 * 2560 * 6912            # gate, up, down
    layer = attn + mlp + 2 * 2560    # plus two RMSNorm weights
    assert counts.layer_params(DANUBE) == layer == 69_473_280
    head = 2560 * 32000
    assert counts.active_params(DANUBE) == 24 * layer + 2560 + head
    # with the embedding table, the program's own count of every
    # parameter (chip_smoke.py on the chip)
    assert counts.active_params(DANUBE) + 32000 * 2560 == 1_831_201_280


def test_flops_by_hand():
    n = counts.active_params(DANUBE)
    assert counts.train_flops(DANUBE, 32768) == 6 * n * 32768
    assert counts.infer_flops(DANUBE, 1) == 2 * n
    # about 1.05e10 FLOP per trained token
    assert 1.04e10 < counts.train_flops(DANUBE, 1) < 1.06e10


def test_decode_bytes_by_hand():
    kv = 24 * 2 * 8 * 80 * 2         # layers x (k, v) x heads x dh x bf16
    assert counts.kv_bytes_per_position(DANUBE) == kv == 61_440
    w = 2 * counts.active_params(DANUBE)
    # two slots at positions 99 and 4999: 100 live positions, and the
    # window's 4096 for the second
    live = (counts.live_positions(99, 4096)
            + counts.live_positions(4999, 4096))
    assert live == 100 + 4096
    assert counts.decode_bytes(DANUBE, 1, live, 2) == w + kv * live + kv * 2
    assert counts.decode_bytes(DANUBE, 3, live, 2) == 3 * w + kv * (live + 2)


def test_peaks_refuse_an_unknown_device_kind():
    p = spec.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        spec.peaks_for("cpu")
