"""The section-Perf levers: correctness of microbatching, the int8
quantizer, lean Adafactor, and the serving (fsdp=False) layout."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models.model import Model
from repro.parallel import axes as A
from repro.parallel.ops import ParallelConfig, make_ops
from repro.train.optim import OptConfig, Optimizer

AXES1 = A.MeshAxes(1, 1, 1)
KEY = jax.random.PRNGKey(0)


def _setup(pcfg, dtype=jnp.float32):
    cfg = dataclasses.replace(get_config("stablelm-3b", smoke=True),
                              dtype=dtype)
    model = Model(cfg, AXES1, pcfg)
    params = model.init(KEY, dtype=dtype)
    batch = {"tokens": np.asarray(
        jax.random.randint(KEY, (4, 32), 0, cfg.vocab))}
    return cfg, model, params, batch


def test_microbatch_grads_match_full_batch():
    """mb=4 accumulated grads == single-batch grads (linearity of the
    mean over equal-sized microbatches)."""
    pcfg = ParallelConfig(sequence_parallel=False, remat="none")
    cfg, model, params, batch = _setup(pcfg)
    ops = make_ops(AXES1, pcfg)

    def gfull(p):
        return jax.grad(lambda q: model.loss(ops, q, batch)[0])(p)

    m = 4
    mb = {"tokens": batch["tokens"].reshape(m, 1, 32)}

    def gacc(p):
        def one(i):
            b = {"tokens": mb["tokens"][i]}
            return jax.grad(lambda q: model.loss(ops, q, b)[0])(p)
        acc = jax.tree.map(jnp.zeros_like, p)
        for i in range(m):
            acc = jax.tree.map(lambda a, g: a + g / m, acc, one(i))
        return acc

    ga, gb = gfull(params), gacc(params)
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-3)


def test_train_step_microbatches_match_one_batch():
    """``make_train_step`` with 4 microbatches (a scan that accumulates
    the bfloat16 gradients in float32) takes the step one batch takes:
    the same loss and gradient norm, the same first moments, and the
    same updated weights. Adam's first step moves a weight by lr times
    the sign of its gradient, so a weight whose gradient is within
    rounding of zero may move either way; every other weight must land
    on the same value."""
    from repro.launch.mesh import make_test_mesh
    from repro.train.step import init_opt_state, make_train_step
    mesh = make_test_mesh(data=1, model=1)
    cfg = get_config("stablelm-3b", smoke=True)
    assert cfg.dtype == jnp.bfloat16
    tokens = np.asarray(jax.random.randint(KEY, (4, 32), 0, cfg.vocab))
    out = {}
    for m in (1, 4):
        pcfg = ParallelConfig(sequence_parallel=False, remat="none",
                              microbatches=m)
        model = Model(cfg, A.MeshAxes.from_mesh(mesh), pcfg)
        opt = Optimizer(OptConfig(lr_peak=1e-3, warmup_steps=1,
                                  total_steps=10, weight_decay=0.0))
        step, _ = make_train_step(model, opt, mesh, 4)
        params = model.init(KEY)
        state = init_opt_state(model, opt, params)
        with jax.set_mesh(mesh):
            out[m] = step(params, state, {"tokens": tokens})
    (p1, s1, met1), (p4, s4, met4) = out[1], out[4]
    for k in ("loss", "gnorm"):
        np.testing.assert_allclose(float(met4[k]), float(met1[k]),
                                   rtol=1e-3)
    for w1, w4, g1, g4 in zip(jax.tree.leaves(p1), jax.tree.leaves(p4),
                              jax.tree.leaves(s1["m"]),
                              jax.tree.leaves(s4["m"])):
        g1, g4 = np.asarray(g1), np.asarray(g4)
        scale = float(np.abs(g1).max())
        np.testing.assert_allclose(g4, g1, rtol=1e-2, atol=1e-2 * scale)
        sure = np.abs(g1) > 1e-2 * scale
        np.testing.assert_allclose(np.asarray(w4, np.float32)[sure],
                                   np.asarray(w1, np.float32)[sure],
                                   rtol=1e-2, atol=1e-6)


def test_lean_adafactor_state_has_no_master():
    opt = Optimizer(OptConfig(name="adafactor", master=False, lr_peak=0.05,
                              warmup_steps=1, total_steps=100,
                              weight_decay=0.0))
    params = {"w": jnp.full((8, 16), 2.0, jnp.bfloat16)}
    state = opt.init(params)
    assert "master" not in state
    ps = opt.state_pspecs_from(
        {"w": __import__("repro.models.common", fromlist=["ParamSpec"])
         .ParamSpec((8, 16), P())})
    assert "master" not in ps

    def loss_fn(p):
        return jnp.sum(p["w"].astype(jnp.float32) ** 2)
    l0 = float(loss_fn(params))
    for _ in range(40):
        g = jax.grad(loss_fn)(params)
        params, state = opt.update(g, state, params)
    assert float(loss_fn(params)) < 0.5 * l0


def test_int8_quantizer_round_trip_error():
    """The int8 quantizer the cross-pod gradient compression sends
    through: its round trip keeps the relative RMS error under 1%."""
    from repro.train.compress import quantize_int8
    w = jax.random.normal(KEY, (256, 128), jnp.float32) * 0.02
    q, s = quantize_int8(w)
    deq = q.astype(jnp.float32) * s
    rel = float(jnp.linalg.norm(deq - w) / jnp.linalg.norm(w))
    assert rel < 0.01, rel


def test_serving_layout_strips_data_axis():
    pcfg = ParallelConfig(sequence_parallel=False, remat="none",
                          fsdp=False)
    axes = A.MeshAxes(data=4, model=2, pod=1)
    cfg = get_config("qwen3-4b", smoke=True)
    model = Model(cfg, axes, pcfg)
    for spec in jax.tree.leaves(
            model.pspecs, is_leaf=lambda s: isinstance(s, P)):
        flat = [n for e in spec if e is not None
                for n in (e if isinstance(e, tuple) else (e,))]
        assert A.DATA_AXIS not in flat, spec
    # fsdp=True keeps it
    model2 = Model(cfg, axes, pcfg.replace(fsdp=True))
    found = any(
        A.DATA_AXIS in [n for e in spec if e is not None
                        for n in (e if isinstance(e, tuple) else (e,))]
        for spec in jax.tree.leaves(
            model2.pspecs, is_leaf=lambda s: isinstance(s, P)))
    assert found


def test_decode_grouped_attention_matches_repeat():
    """The no-repeat GQA decode einsum equals explicit KV repetition."""
    from repro.models.attention import attn_decode
    B, S, Hq, Hkv, D = 2, 64, 8, 2, 32
    q = jax.random.normal(KEY, (B, 1, Hq, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, Hkv, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, Hkv, D))
    kv_len = jnp.asarray([40, 64])
    out = attn_decode(q, k, v, kv_len=kv_len)
    out_rep = attn_decode(q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2),
                          kv_len=kv_len)
    np.testing.assert_allclose(out, out_rep, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
def test_decode_new_row_folded_in_matches_written_row(dtype, tol):
    """Attending to a cache that does not hold this step's rows yet, with
    them folded in (``new=``), equals writing them at their slot first:
    a slot still filling (kv_len < S, a stale row at the slot), one whose
    ring wrapped (kv_len == S), and one at the last row. Only the order
    of the softmax sums differs, hence a tolerance of the dtype's."""
    from repro.models.attention import attn_decode
    B, S, Hq, Hkv, D = 3, 16, 8, 2, 32
    keys = jax.random.split(KEY, 5)
    q = jax.random.normal(keys[0], (B, 1, Hq, D)).astype(dtype)
    k = jax.random.normal(keys[1], (B, S, Hkv, D)).astype(dtype)
    v = jax.random.normal(keys[2], (B, S, Hkv, D)).astype(dtype)
    k_new = jax.random.normal(keys[3], (B, Hkv, D)).astype(dtype)
    v_new = jax.random.normal(keys[4], (B, Hkv, D)).astype(dtype)
    pos = jnp.asarray([5, 21, 15])                 # absolute positions
    slot, kv_len = pos % S, jnp.minimum(pos + 1, S)
    rows = jnp.arange(B)
    want = attn_decode(q, k.at[rows, slot].set(k_new),
                       v.at[rows, slot].set(v_new), kv_len=kv_len)
    got = attn_decode(q, k, v, kv_len=kv_len, new=(k_new, v_new, slot))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
