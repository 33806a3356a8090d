"""MoE dispatch invariants (single device), the capacity path's drops,
the dropless path, and the expert share."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import moe as MOE
from repro.models import transformer as T
from repro.models.common import tree_instantiate
from repro.parallel import axes as A
from repro.parallel.ops import ParallelConfig, make_ops

AXES1 = A.MeshAxes(1, 1, 1)
PCFG = ParallelConfig(sequence_parallel=False, remat="none")
KEY = jax.random.PRNGKey(0)


def setup(T=64, d=32, E=8, k=2, cf=8.0, **kw):
    cfg = dataclasses.replace(
        get_config("deepseek-moe-16b", smoke=True),
        d_model=d, n_experts=E, top_k=k, moe_d_ff=16,
        capacity_factor=cf, dtype=jnp.float32, **kw)
    specs = MOE.moe_param_specs(cfg)
    p = tree_instantiate(specs, KEY, 0.02, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (T, d), jnp.float32)
    return cfg, p, x


def dense_mixture(p, x, topv, topi, held):
    """Per token, the weighted sum over its top-k experts that are held,
    computed directly."""
    want = np.zeros_like(np.asarray(x))
    for t in range(x.shape[0]):
        for j in range(topi.shape[1]):
            e = int(topi[t, j])
            if e >= held:
                continue
            h = jax.nn.silu(x[t] @ p["wg"][e]) * (x[t] @ p["wu"][e])
            want[t] += float(topv[t, j]) * np.asarray(h @ p["wd"][e])
    return want


def test_moe_aux_loss_bounds():
    cfg, p, x = setup()
    ops = make_ops(AXES1, PCFG)
    _, aux, _ = MOE.moe_ffn(ops, p, x, cfg)
    # switch aux is ~1.0 at perfect balance, <= E at total collapse
    assert 0.9 < float(aux) <= cfg.n_experts


def test_moe_no_drops_at_high_capacity_matches_dense_gate():
    """With capacity >= T*k no token is dropped: output equals the dense
    per-token mixture computed directly."""
    cfg, p, x = setup(cf=16.0)
    ops = make_ops(AXES1, PCFG)
    out, _, _ = MOE.moe_ffn(ops, p, x, cfg)

    logits = x @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    topv, topi = jax.lax.top_k(probs, cfg.top_k)
    topv = topv / topv.sum(-1, keepdims=True)
    want = dense_mixture(p, x, topv, topi, cfg.n_experts)
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4, rtol=1e-4)


def test_moe_capacity_drops_tokens():
    """The capacity dispatch (what the sharded all-to-all runs) drops
    the overflow, and counts it."""
    cfg, p, x = setup(cf=0.25)
    ops = make_ops(AXES1, PCFG)
    _, topv, topi = MOE.route(ops, p, x, cfg)
    out, counts = MOE.capacity_experts(ops, p, x, topv, topi, cfg)
    # some tokens must be zero (dropped entirely)
    norms = np.linalg.norm(np.asarray(out), axis=-1)
    assert (norms < 1e-12).any()
    assert int(counts["dropped_rows"]) > 0


def test_moe_deterministic():
    cfg, p, x = setup()
    ops = make_ops(AXES1, PCFG)
    a, _, _ = MOE.moe_ffn(ops, p, x, cfg)
    b, _, _ = MOE.moe_ffn(ops, p, x, cfg)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_capacity_helper():
    assert MOE.capacity(4096, 6, 64, 1.25) % 4 == 0
    assert MOE.capacity(1, 1, 64, 1.0) == 4   # floor


@pytest.mark.parametrize("T", [16, 64])
def test_skewed_routing_drops_nothing(T):
    """Every token's router prefers expert 0: at a decode batch (16) and
    beyond, the one-chip layer runs every routed row, where the capacity
    dispatch (4 rows an expert at T=16) drops most of expert 0's."""
    cfg, p, x = setup(T=T, cf=1.0)
    x = jnp.abs(x)             # positive activations score expert 0 high
    p = dict(p, router=p["router"].at[:, 0].set(1.0))
    ops = make_ops(AXES1, PCFG)
    _, topv, topi = MOE.route(ops, p, x, cfg)
    assert bool(jnp.all(topi[:, 0] == 0))
    out, _, counts = MOE.moe_ffn(ops, p, x, cfg)
    assert int(counts["dropped_rows"]) == 0
    assert int(counts["routed_rows"]) == int(counts["expert_rows"]) == T * 2
    np.testing.assert_allclose(
        np.asarray(out), dense_mixture(p, x, topv, topi, cfg.n_experts),
        atol=1e-5, rtol=1e-4)
    _, cap = MOE.capacity_experts(ops, p, x, topv, topi, cfg)
    assert int(cap["dropped_rows"]) >= T - MOE.capacity(T, 2, 8, 1.0)


def test_norm_topk_prob_false_keeps_the_softmax_weights():
    cfg, p, x = setup(norm_topk_prob=False)
    ops = make_ops(AXES1, PCFG)
    probs, topv, topi = MOE.route(ops, p, x, cfg)
    want_v, want_i = jax.lax.top_k(jax.nn.softmax(x @ p["router"], -1),
                                   cfg.top_k)
    np.testing.assert_allclose(topv, want_v, rtol=1e-6)
    np.testing.assert_array_equal(topi, want_i)
    assert float(jnp.max(topv.sum(-1))) < 1.0
    _, renorm, _ = MOE.route(ops, p, x, dataclasses.replace(
        cfg, norm_topk_prob=True))
    np.testing.assert_allclose(renorm.sum(-1), 1.0, rtol=1e-6)
    out, _, _ = MOE.moe_ffn(ops, p, x, cfg)
    np.testing.assert_allclose(
        np.asarray(out), dense_mixture(p, x, want_v, want_i, cfg.n_experts),
        atol=1e-5, rtol=1e-4)


def test_expert_shares_sum_to_the_uncut_layer():
    """An 8-way expert-parallel group at a small size: 16 routed experts
    top-4 and a shared expert; share r holds experts [2r, 2r + 2) (the
    block [0, 2) of a router whose outputs are rotated by 2r). The eight
    shares' MoE blocks, with the shared expert counted once, add up to
    the uncut block."""
    cfg = dataclasses.replace(
        get_config("deepseek-v2-lite", smoke=True), n_experts=16, top_k=4,
        dtype=jnp.float32)
    ops = make_ops(AXES1, PCFG)
    full = tree_instantiate(T.layer_specs(cfg, None, "attn_moe"), KEY, 0.02,
                            jnp.float32)
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 8, cfg.d_model))
    y_full, _, c_full = T.block_moe(ops, full, x, cfg)
    share_cfg = dataclasses.replace(cfg, experts_held=2)
    shared = T.block_mlp(ops, {"ln2": full["ln2"], **full["par"]}, x,
                         cfg) - x
    total = jnp.zeros_like(x)
    routed = 0
    for r in range(8):
        moe = {"router": jnp.roll(full["moe"]["router"], -2 * r, axis=1),
               **{w: full["moe"][w][2 * r:2 * r + 2]
                  for w in ("wg", "wu", "wd")}}
        y, _, c = T.block_moe(ops, dict(full, moe=moe), x, share_cfg)
        total = total + (y - x)
        routed += int(c["routed_rows"])
        assert int(c["dropped_rows"]) == 0
    np.testing.assert_allclose(total - 7 * shared, y_full - x, atol=1e-6)
    assert routed == int(c_full["routed_rows"]) == 16 * 4
