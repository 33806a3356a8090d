"""Training substrate: optimizers, schedule, checkpointing (incl. elastic
restore), failure injection, straggler detection, data pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import MemmapTokens, Prefetcher, SyntheticTokens
from repro.train import checkpoint as CKPT
from repro.train import ft
from repro.train.optim import OptConfig, Optimizer, lr_at


def test_lr_schedule():
    cfg = OptConfig(lr_peak=1e-3, warmup_steps=10, total_steps=100)
    assert float(lr_at(cfg, jnp.int32(0))) < 2e-4
    assert float(lr_at(cfg, jnp.int32(10))) == pytest.approx(1e-3, rel=0.1)
    assert float(lr_at(cfg, jnp.int32(100))) < 1e-5 + 1e-9


@pytest.mark.parametrize("name", ["adamw", "adafactor"])
def test_optimizer_reduces_quadratic(name):
    opt = Optimizer(OptConfig(name=name, lr_peak=0.1, warmup_steps=1,
                              total_steps=200, weight_decay=0.0))
    params = {"w": jnp.ones((8, 16), jnp.bfloat16) * 2.0,
              "b": jnp.ones((16,), jnp.bfloat16)}
    state = opt.init(params)

    def loss_fn(p):
        return jnp.sum(p["w"].astype(jnp.float32) ** 2) + \
            jnp.sum(p["b"].astype(jnp.float32) ** 2)

    l0 = float(loss_fn(params))
    for _ in range(60):
        grads = jax.grad(loss_fn)(params)
        params, state = opt.update(grads, state, params)
    assert float(loss_fn(params)) < 0.2 * l0
    if name == "adafactor":   # factored stats really are factored
        assert state["stats"]["w"]["vr"].shape == (8,)
        assert state["stats"]["w"]["vc"].shape == (16,)
        assert "v" in state["stats"]["b"]


def test_checkpoint_roundtrip(tmp_path):
    state = {"params": {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
                        "b": jnp.linspace(-2, 2, 8, dtype=jnp.bfloat16)},
             "opt": {"step": np.int32(7)}}
    CKPT.save(str(tmp_path), 7, state, {"arch": "x"})
    flat, meta, step = CKPT.load(str(tmp_path))
    assert step == 7 and meta["arch"] == "x"
    rebuilt = CKPT.restore_tree(state, flat)
    np.testing.assert_array_equal(rebuilt["params"]["w"],
                                  state["params"]["w"])
    # bf16 survives the npy round trip (ml_dtypes view serialization)
    assert rebuilt["params"]["b"].dtype.name == "bfloat16"
    np.testing.assert_array_equal(np.asarray(rebuilt["params"]["b"]),
                                  np.asarray(state["params"]["b"]))


def test_checkpoint_retention_and_latest(tmp_path):
    for s in [1, 2, 3, 4, 5]:
        CKPT.save(str(tmp_path), s, {"x": np.zeros(2)}, keep=3)
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(steps) == 3
    assert CKPT.latest_step(str(tmp_path)) == 5


def test_async_checkpointer(tmp_path):
    ck = CKPT.AsyncCheckpointer(str(tmp_path))
    for s in [10, 20]:
        ck.submit(s, {"w": jnp.ones((4,)) * s})
    ck.finish()
    flat, _, step = CKPT.load(str(tmp_path))
    assert step == 20
    np.testing.assert_array_equal(flat["w"], np.ones(4) * 20)


def test_elastic_restore_reshards(tmp_path):
    """Save from a '1-device layout', restore onto a different sharding --
    global shapes are the contract."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",))
    w = np.arange(64, dtype=np.float32).reshape(8, 8)
    CKPT.save(str(tmp_path), 1, {"w": w})
    flat, _, _ = CKPT.load(str(tmp_path))
    out = CKPT.restore_sharded({"w": jnp.zeros((8, 8), jnp.float32)}, flat,
                               mesh, {"w": P("data", None)})
    np.testing.assert_array_equal(np.asarray(out["w"]), w)


def test_failure_injector_fires_once():
    inj = ft.FailureInjector(frozenset([3]))
    inj.check(2)
    with pytest.raises(ft.SimulatedFailure):
        inj.check(3)
    inj.check(3)   # second pass after restart does not re-fire


def test_straggler_detector():
    det = ft.StragglerDetector(alpha=0.5, threshold=3.0, warmup=2)
    flags = [det.observe(i, 1.0) for i in range(6)]
    assert not any(flags)
    assert det.observe(6, 10.0)            # 10x the EWMA
    assert not det.observe(7, 1.0)         # EWMA not poisoned
    assert len(det.events) == 1


def test_supervisor_degrade_cycle():
    pol = ft.RecoveryPolicy(degrade_backend="linear", recovery_steps=4,
                            max_restarts=2)
    sup = ft.SupervisorState()
    be = sup.on_failure(10, pol)
    assert be == "linear"
    assert sup.backend_for(12, "native", pol) == "linear"
    assert sup.backend_for(15, "native", pol) == "native"
    sup.on_failure(20, pol)
    with pytest.raises(RuntimeError):
        sup.on_failure(30, pol)


def test_synthetic_data_deterministic():
    src = SyntheticTokens(vocab=100, seq=16, global_batch=4, seed=3)
    a, b = src.batch(5), src.batch(5)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(src.batch(5), src.batch(6))
    assert a.shape == (4, 16) and a.min() >= 0 and a.max() < 100


def test_memmap_source(tmp_path):
    path = str(tmp_path / "tokens.bin")
    np.arange(10_000, dtype=np.int32).tofile(path)
    src = MemmapTokens(path, vocab=50_000, seq=32, global_batch=4)
    b1, b2 = src.batch(0), src.batch(0)
    np.testing.assert_array_equal(b1, b2)
    assert b1.shape == (4, 32)


def test_prefetcher_orders_batches():
    src = SyntheticTokens(vocab=10, seq=4, global_batch=2, seed=0)
    pf = Prefetcher(lambda s: {"tokens": src.batch(s)}, start_step=3,
                    depth=2)
    steps = [pf.get()[0] for _ in range(4)]
    pf.stop()
    assert steps == [3, 4, 5, 6]


def test_grad_compression_int8_error_feedback():
    """Quantize-allreduce with EF: the *accumulated* update over many steps
    converges to the true sum (error telescopes)."""
    from repro.train.compress import quantize_int8
    rng = np.random.default_rng(0)
    g = rng.standard_normal(256).astype(np.float32)
    ef = np.zeros_like(g)
    acc_q, acc_true = np.zeros_like(g), np.zeros_like(g)
    for step in range(50):
        gf = g + ef
        q, s = quantize_int8(jnp.asarray(gf))
        sent = np.asarray(q, np.float32) * float(s)
        ef = gf - sent
        acc_q += sent
        acc_true += g
    # relative error of the accumulated signal is tiny vs one-shot error
    rel = np.linalg.norm(acc_q - acc_true) / np.linalg.norm(acc_true)
    assert rel < 1e-3
