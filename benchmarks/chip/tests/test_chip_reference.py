"""The plain float32 reference against the program at smoke widths on
the CPU: the same weights from the seed, the same logits through prefill
and cached decode, the same loss and gradients."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_cells import SMOKE
from benchmarks.chip import reference as R

M = dict(SMOKE, rope_theta=10000.0, rope_pct=1.0, norm_eps=1e-5,
         act="swiglu", qk_norm=False, init_std=0.02, dtype="float32")


def _weights(seed=2**31 + 9):
    return jax.jit(lambda k: R.make_weights(M, k))(
        R.weights_key(seed))


@pytest.fixture(scope="module")
def program():
    from repro.configs import get_config
    from repro.launch.serve import serving_model, serving_steps
    cfg = dataclasses.replace(get_config("h2o-danube-1.8b", smoke=True),
                              vocab=M["vocab"], dtype=jnp.float32)
    model = serving_model(cfg)
    params = model.init(jax.random.PRNGKey(2**31 + 9))
    return model, params, serving_steps(model, 64)


def test_weights_from_the_seed_match_the_programs(program):
    _, params, _ = program
    w = _weights()
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(w)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-7)


def test_logits_through_prefill_and_decode(program):
    model, params, (prefill, decode) = program
    w = _weights()
    toks = np.random.default_rng(1).integers(0, 128, 40).astype(np.int32)
    ref = np.asarray(R.logits(w, jnp.asarray(toks), M))
    got, caches = prefill(params, {"tokens": jnp.asarray(toks[:30])[None]})
    np.testing.assert_allclose(got[0], ref[29], atol=2e-5)
    # cached decode past the 32-position window: the ring buffer wraps
    for p in range(30, 40):
        got, caches = decode(params, caches, jnp.asarray([[toks[p]]]),
                             jnp.asarray([p], jnp.int32))
        np.testing.assert_allclose(got[0], ref[p], atol=2e-5)


def test_loss_and_gradients_match_model_loss(program):
    model, params, _ = program
    from repro.parallel.ops import make_ops
    ops = make_ops(model.axes, model.pcfg)
    toks = np.random.default_rng(2).integers(0, 128, (2, 24)).astype(np.int32)
    (want, _), gw = jax.value_and_grad(
        lambda p: model.loss(ops, p, {"tokens": jnp.asarray(toks)}),
        has_aux=True)(params)
    w = jax.tree.map(jnp.asarray, params)
    got, gg = jax.value_and_grad(R.mean_nll)(w, jnp.asarray(toks), M)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gw), jax.tree.leaves(gg)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)


@pytest.mark.parametrize("quant", ["w8a16", "w8a8"])
def test_control_departs_from_the_reference(program, quant):
    w = _weights()
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 128, 24),
                       jnp.int32)
    ref = R.logits(w, toks, M)
    ctl = R.logits(w, toks, M, quant=quant)
    gap = float(jnp.max(jnp.abs(ref - ctl)))
    assert 1e-4 < gap < 1.0
