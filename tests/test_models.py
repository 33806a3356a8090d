"""Per-architecture smoke + decode-parity tests (single device, reduced
configs; the published-width serving programs are compiled for a v5e in
tests/test_tpu_compile.py)."""
import dataclasses
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.launch.serve import serving_model, serving_steps
from repro.models.model import Model
from repro.models.common import gqa_layout, tree_shapes
from repro.parallel import axes as A
from repro.parallel.ops import ParallelConfig, make_ops

AXES1 = A.MeshAxes(1, 1, 1)
PCFG = ParallelConfig(path="mpignite", sequence_parallel=False, remat="none")
KEY = jax.random.PRNGKey(0)


def make_batch(cfg, B, S, key=KEY):
    batch = {}
    if cfg.input_mode == "frames":
        batch["frames"] = jax.random.normal(key, (B, S, cfg.d_model),
                                            jnp.bfloat16)
        batch["labels"] = jax.random.randint(key, (B, S), 0, cfg.vocab)
    else:
        batch["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab)
    if cfg.cross_attn_every:
        batch["image_emb"] = jax.random.normal(
            key, (B, cfg.n_image_tokens, cfg.vision_d), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_loss(arch):
    """One forward/loss on the reduced config: output shapes + no NaNs."""
    cfg = get_config(arch, smoke=True)
    model = Model(cfg, AXES1, PCFG)
    params = model.init(KEY)
    ops = make_ops(AXES1, PCFG)
    loss, metrics = model.loss(ops, params, make_batch(cfg, 2, 32))
    assert np.isfinite(float(loss))
    assert 0.0 < float(loss) < 2 * np.log(cfg.vocab)
    assert float(metrics["n_valid"]) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step_decreases_loss(arch):
    """A few optimizer steps on one repeated batch must reduce the loss."""
    from repro.train.optim import OptConfig, Optimizer
    cfg = get_config(arch, smoke=True)
    model = Model(cfg, AXES1, PCFG)
    params = model.init(KEY)
    ops = make_ops(AXES1, PCFG)
    opt = Optimizer(OptConfig(lr_peak=3e-3, warmup_steps=1, total_steps=50,
                              weight_decay=0.0))
    state = opt.init(params)
    batch = make_batch(cfg, 2, 16)

    @jax.jit
    def step(params, state):
        (loss, _), grads = jax.value_and_grad(
            lambda p: model.loss(ops, p, batch), has_aux=True)(params)
        params, state = opt.update(grads, state, params)
        return params, state, loss

    losses = []
    for _ in range(6):
        params, state, loss = step(params, state)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0] - 0.05, losses


DECODE_ARCHS = [a for a in ARCHS if a != "hubert-xlarge"]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_matches_forward(arch):
    """Teacher-forced prefill+decode logits must match the full forward
    pass at every position (the cache path is consistent with training)."""
    cfg = get_config(arch, smoke=True)
    # capacity routing drops depend on the token-batch size; pin capacity
    # high so prefill/decode dispatch identically to the full forward
    cfg = dataclasses.replace(cfg, dtype=jnp.float32, capacity_factor=8.0)
    model = Model(cfg, AXES1, PCFG)
    params = model.init(KEY, dtype=jnp.float32)
    ops = make_ops(AXES1, PCFG)
    B, S, n_pre = 2, 24, 16
    batch = make_batch(cfg, B, S)
    tokens = batch["tokens"]

    # reference: full-sequence forward logits
    x, img = model._embed_in(ops, params, batch)
    rope = model._rope(jnp.arange(S))
    h, _, _ = model.forward(ops, params, x, rope, img, "train")
    from repro.models.layers import rmsnorm, logits_only
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    full_logits = logits_only(ops, params["head"], h, model.v_pad, cfg.vocab)

    # prefill on the first n_pre tokens, then teacher-forced decode
    pre = dict(batch)
    pre["tokens"] = tokens[:, :n_pre]
    logits, caches = model.prefill(ops, params, pre, s_max=S + 4)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full_logits[:, n_pre - 1]),
                               atol=2e-3, rtol=2e-3)
    for t in range(n_pre, S):
        tok = tokens[:, t:t + 1]
        pos = jnp.full((B,), t, jnp.int32)
        logits, caches = model.decode(ops, params, caches, tok, pos)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full_logits[:, t]),
            atol=3e-3, rtol=3e-3,
            err_msg=f"{arch}: decode diverges at position {t}")


def _random_cache(model, batch, s_max, dtype, key=KEY):
    shapes = tree_shapes(model.cache_specs(batch, s_max), dtype=dtype)
    leaves, treedef = jax.tree.flatten(shapes)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype)
        for s, k in zip(leaves, keys)])


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "deepseek-moe-16b"])
def test_donated_decode_wraps_the_ring_like_the_plain_loop(arch):
    """``serving_steps``' decode, which donates its cache, run 20 steps
    over an 8-row ring from three different offsets (each slot wraps
    twice): its logits and caches equal a plain un-donated
    ``Model.decode`` loop's, the cache it was given is consumed, and each
    step changes only the row it writes in each slot."""
    cfg = dataclasses.replace(get_config(arch, smoke=True), window=8)
    model = serving_model(cfg)
    params = model.init(KEY)
    s_max, B = 8, 3
    _, donated = serving_steps(model, s_max)
    ops = make_ops(model.axes, model.pcfg)
    plain = jax.jit(lambda p, c, t, pos: model.decode(ops, p, c, t, pos))
    c_plain = _random_cache(model, B, s_max, cfg.dtype)
    c_don = jax.tree.map(jnp.copy, c_plain)
    pos0 = np.array([5, 0, 3], np.int32)
    toks = np.random.default_rng(0).integers(0, cfg.vocab, (20, B, 1))
    host = lambda tree: jax.tree.map(lambda a: np.asarray(jnp.copy(a)), tree)
    attn = {seg.name for seg in model.schedule
            if seg.kind in ("attn_mlp", "attn_moe")}
    assert attn
    for t in range(20):
        pos = jnp.asarray(pos0 + t)
        tok = jnp.asarray(toks[t], jnp.int32)
        # host views of device copies: a host view of a CPU array pins its
        # buffer, and a pinned buffer is copied rather than donated
        before = host(c_don)
        given = jax.tree.leaves(c_don)
        want, c_plain = plain(params, c_plain, tok, pos)
        # an expert model's serving decode returns its counters third
        got, c_don, *_ = donated(params, c_don, tok, pos)
        assert all(a.is_deleted() for a in given)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        after = host(c_don)
        jax.tree.map(np.testing.assert_array_equal, after, host(c_plain))
        written = np.zeros((B, s_max), bool)
        written[np.arange(B), (pos0 + t) % s_max] = True
        for name in attn:
            for leaf in ("k", "v"):
                old, new = before[name][leaf], after[name][leaf]
                np.testing.assert_array_equal(new[:, ~written],
                                              old[:, ~written])
                assert not np.array_equal(new[:, written], old[:, written])


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_serving_decode_aliases_its_cache(arch):
    """The lowered serving decode donates every cache leaf to the cache
    it returns: each is marked as aliasing an output."""
    cfg = get_config(arch, smoke=True)
    model = serving_model(cfg)
    _, decode = serving_steps(model, 16)
    B = 2
    params = jax.eval_shape(model.init, KEY)
    caches = tree_shapes(model.cache_specs(B, 16), dtype=cfg.dtype)
    text = decode.lower(params, caches,
                        jax.ShapeDtypeStruct((B, 1), jnp.int32),
                        jax.ShapeDtypeStruct((B,), jnp.int32)).as_text()
    aliased = re.findall(r"tf\.aliasing_output = (\d+)", text)
    n_cache = len(jax.tree.leaves(caches))
    # outputs: logits first, then the cache leaves in order
    assert sorted(map(int, aliased)) == list(range(1, n_cache + 1))


def test_gqa_layout_invariants():
    for (nq, nkv, tp) in [(32, 8, 16), (56, 8, 16), (16, 16, 16),
                          (4, 4, 16), (32, 32, 16), (7, 1, 1), (32, 8, 1)]:
        lay = gqa_layout(nq, nkv, tp)
        assert lay.n_q_pad % tp == 0
        assert lay.kv_eff % tp == 0
        assert lay.n_q_pad >= nq
        assert lay.q_real_mask().sum() == nq
        assert lay.n_q_pad == lay.kv_eff * lay.gq
        src = lay.kv_source()
        assert src.max() < nkv
        # every real q slot's kv head matches the true GQA grouping
        gq0 = nq // nkv
        mask = lay.q_real_mask()
        real_seen = {}
        for slot in range(lay.n_q_pad):
            if not mask[slot]:
                continue
            kv = src[slot // lay.gq]
            real_seen.setdefault(kv, 0)
            real_seen[kv] += 1
        assert all(v == gq0 for v in real_seen.values())


def test_head_padding_zeroes_are_inert():
    """arctic-smoke has 7 q heads / 1 kv head: padded slots must not
    change the output (zero columns in wq, zero rows in wo)."""
    cfg = get_config("arctic-480b", smoke=True)
    model = Model(cfg, AXES1, PCFG)
    params = model.init(KEY)
    wq = params["blocks"]["seg0"]["wq"]
    lay = model.layout
    mask = np.repeat(lay.q_real_mask(), cfg.dh)
    dead = np.asarray(wq)[..., ~mask]
    assert np.all(dead == 0)


def test_n_params_counts():
    cfg = get_config("qwen3-4b")
    model = Model(cfg, AXES1, PCFG)
    n = model.n_params()
    assert 3.5e9 < n < 5.5e9, n        # qwen3-4b-ish
    cfg = get_config("arctic-480b")
    model = Model(cfg, A.MeshAxes(16, 16, 1),
                  ParallelConfig(path="mpignite"))
    n = model.n_params()
    assert 4.3e11 < n < 5.3e11, n      # ~480B total
    na = model.n_params(active_only=True)
    assert na < 0.1 * n                # top-2 of 128 experts + dense


def test_launch_and_configs_import_sets_no_xla_flags():
    """Importing every module of ``repro.launch`` and ``repro.configs``
    sets no ``XLA_FLAGS`` and starts no backend, so a process may import
    them before it forces a host device count or picks a device."""
    code = ("import importlib, os, pkgutil\n"
            "import repro.configs, repro.launch\n"
            "for pkg in (repro.configs, repro.launch):\n"
            "    for m in pkgutil.iter_modules(pkg.__path__,\n"
            "                                  pkg.__name__ + '.'):\n"
            "        importlib.import_module(m.name)\n"
            "assert 'XLA_FLAGS' not in os.environ, os.environ['XLA_FLAGS']\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   env={**env, "JAX_PLATFORMS": "cpu"})
