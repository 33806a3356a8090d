"""The trace reduction: busy union, idle share, exposed collective time
and idle attribution, on hand-made overlapping intervals and on a trace
recorded on a v5e."""
import glob

import numpy as np
import pytest

from chip_cells import CHIP
from benchmarks.chip import trace_reduce as T
from benchmarks.chip.readers import idle_share

RECORDED = sorted(glob.glob(str(CHIP / "tests" / "data" / "*.xplane.pb")))


def test_union_of_overlapping_intervals():
    iv = np.array([[5, 8], [0, 2], [1, 3], [7, 9], [10, 11]], float)
    np.testing.assert_array_equal(T.merge(iv),
                                  [[0, 3], [5, 9], [10, 11]])
    assert T.measure(T.merge(iv)) == 3 + 4 + 1


def _hlo(name: str, op: str) -> str:
    return f"%{name} = bf16[8]{{0:T(8,128)(2,1)}} {op}(bf16[8]{{0}} %x)"


def test_idle_share_and_exposed_collectives():
    # window 0..100 ns: compute 0-28 and 25-50, an all-reduce named by the
    # program at 40-70 (overlaps compute for 10 ns), an asynchronous
    # all-gather at 10-20 inside compute, a copy at 80-85 on the async line
    dev = {"ops": [(_hlo("fusion.1", "fusion"), 0, 28),
                   (_hlo("fusion.2", "fusion"), 25, 50),
                   (_hlo("psum_invariant.3", "all-reduce"), 40, 70)],
           "async": [(_hlo("all-gather-start", "all-gather-start"), 10, 20),
                     (_hlo("copy-start", "copy-start"), 80, 85)],
           "modules": [("jit_step(1)", 0, 70)]}
    spans = [("bench.train_step", 0, 60), ("bench.batch_put", 75, 95)]
    s = T.reduce_events({0: dev}, spans, 0.0, 100.0)
    d = s["devices"][0]
    assert d["busy_ns"] == 70                 # the compute stream only
    assert d["collective_ns"] == 40           # 40-70 and 10-20
    assert d["exposed_collective_ns"] == 20   # 50-70 alone
    assert d["modules"] == {"jit_step(1)": (1, 70.0)}
    assert idle_share({"trace": s}) == pytest.approx(30.0)
    # the idle 70-100 gap: its midpoint (85) falls in the batch transfer
    assert d["idle_by_host"] == {"bench.batch_put": 30.0}
    b = T.breakdown(s)
    assert b["device_ops"][0] == ["psum_invariant.3", 30e-9]
    assert b["idle_gaps"] == [["bench.batch_put", 30e-9]]


def test_op_names_opcodes_and_containers():
    text = ("%copy-start.2 = (bf16[2560]{0:T(1024)(128)(2,1)S(1)}, "
            "u32[]{:S(2)}) copy-start(bf16[2560]{0:T(1024)} %p.1)")
    assert T.op_name(text) == "copy-start.2"
    assert T.opcode(text) == "copy-start"
    assert T.opcode(_hlo("psum_invariant.7", "all-reduce")) == "all-reduce"
    assert T.is_collective(_hlo("ag", "all-gather-done"))
    assert not T.is_collective(_hlo("all-reduce.1", "fusion"))
    assert T.opcode("barrier-cores") == "barrier-cores"
    dev = {"ops": [(_hlo("while.5", "while"), 0, 100),
                   (_hlo("fusion.1", "fusion"), 10, 20)], "modules": []}
    d = T.reduce_events({0: dev}, [], 0.0, 100.0)["devices"][0]
    assert d["busy_ns"] == 100 and d["ops_ns"] == {"fusion.1": 10}


def test_ops_are_clipped_to_the_window():
    dev = {"ops": [(_hlo("fusion", "fusion"), -10, 10),
                   (_hlo("fusion", "fusion"), 90, 120)], "modules": []}
    s = T.reduce_events({0: dev}, [], 0.0, 100.0)
    assert s["devices"][0]["busy_ns"] == 20
    assert s["window_ns"] == 100


@pytest.mark.parametrize("path", RECORDED,
                         ids=[p.split("/")[-1] for p in RECORDED])
def test_recorded_chip_trace(path):
    """Traces of a matmul, a psum over every chip and a tanh, recorded on
    one v5e and on a 2x2 v5e host."""
    devices, spans, lo, hi = T.read_xplane(path)
    assert devices and all(v["ops"] for v in devices.values())
    assert {n for n, _, _ in spans} == {"bench.step", "bench.host_wait"}
    s = T.reduce_file(path)
    for d in s["devices"].values():
        assert 0 < d["busy_ns"] <= s["window_ns"]
        assert 0 <= d["exposed_collective_ns"] <= d["collective_ns"]
        # the psum is an all-reduce across chips, and nothing overlaps it
        assert (d["collective_ns"] > 0) is (len(devices) > 1)
        assert d["exposed_collective_ns"] == d["collective_ns"]
        assert sum(d["idle_by_host"].values()) == pytest.approx(
            s["window_ns"] - d["busy_ns"])
    assert 0 <= idle_share({"trace": s}) < 100
