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


def _stacks(cfg, L, key=KEY):
    """L layers' held experts, stacked as the layer scan holds them."""
    specs = MOE.moe_param_specs(cfg)
    layers = [tree_instantiate(specs, jax.random.fold_in(key, l), 0.02,
                               jnp.float32) for l in range(L)]
    return {w: jnp.stack([p[w] for p in layers])
            for w in MOE.EXPERT_WEIGHTS}, [p["router"] for p in layers]


@pytest.mark.parametrize("mode,T,shards", [("decode", 4, 1),
                                           ("prefill", 48, 1),
                                           ("decode", 4, 2)])
def test_whole_stack_equals_the_sliced_layer(mode, T, shards):
    """Three expert layers of 4 held experts (of 8, top-2): each layer's
    grouped matmuls over the whole (L, n, ...) stack, given the layer's
    index, equal the same call on that layer's slice, bit for bit. At the
    decode size some layers route no row to some held experts. With two
    shards the model axis is named by ``vmap``: each shard holds its 2
    of the 4 experts, the shard=True path of a decode on a mesh."""
    L = 3
    cfg, _, _ = setup(T=T, experts_held=4)
    stacks, routers = _stacks(cfg, L)
    x = jax.random.normal(jax.random.fold_in(KEY, 9), (T, cfg.d_model))
    ops = make_ops(A.MeshAxes(1, shards, 1), PCFG)
    n = 4 // shards

    def both(local, l):
        _, topv, topi = MOE.route(ops, {"router": routers[l]}, x, cfg)
        whole = MOE.dropless_experts(ops, local, x, topv, topi, cfg,
                                     shard=shards > 1, layer=jnp.int32(l))
        sliced = MOE.dropless_experts(ops, {w: v[l] for w, v in
                                            local.items()},
                                      x, topv, topi, cfg, shard=shards > 1)
        return whole, sliced

    touched = []
    for l in range(L):
        if shards == 1:
            (out, counts), (want, want_counts) = both(stacks, l)
        else:
            local = {w: jnp.stack(jnp.split(v, shards, axis=1))
                     for w, v in stacks.items()}       # (shards, L, n, ...)
            (out, counts), (want, want_counts) = jax.vmap(
                lambda s, l=l: both(s, l), axis_name=A.MODEL_AXIS)(local)
            assert local["wg"].shape[2] == n
            out, want = out[0], want[0]
            counts = {k: v[0] for k, v in counts.items()}
            want_counts = {k: v[0] for k, v in want_counts.items()}
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
        assert {k: int(v) for k, v in counts.items()} == \
            {k: int(v) for k, v in want_counts.items()}
        assert int(counts["dropped_rows"]) == 0
        touched.append(int(counts["experts_touched"]))
    if mode == "decode":
        assert min(touched) < 4, touched      # an expert left without rows


def _moe_model(**kw):
    """deepseek-moe-16b at smoke widths with three expert layers (after
    its dense first layer), 4 of 8 routed experts held, float32."""
    from repro.models.model import Model
    cfg = dataclasses.replace(
        get_config("deepseek-moe-16b", smoke=True), n_layers=4,
        experts_held=4, vocab=128, dtype=jnp.float32, **kw)
    model = Model(cfg, AXES1, PCFG)
    return cfg, model, model.init(KEY, dtype=jnp.float32)


def _spy(monkeypatch):
    """Records, for each call of ``dropless_experts``, its ``layer`` and
    the rank of the weights it was handed."""
    calls, real = [], MOE.dropless_experts

    def spy(ops, p, *a, layer=None, **k):
        calls.append((layer is not None, p["wg"].ndim))
        return real(ops, p, *a, layer=layer, **k)
    monkeypatch.setattr(MOE, "dropless_experts", spy)
    return calls


@pytest.mark.parametrize("mode", ["prefill", "decode"])
def test_serving_reads_the_expert_stacks_whole(monkeypatch, mode):
    """Prefill and decode hand every expert layer the whole stack and its
    index, and give the same logits, caches and counts, bit for bit, as
    the same scans with the experts sliced out layer by layer."""
    from repro.models.model import Model
    cfg, model, params = _moe_model()
    ops = make_ops(AXES1, PCFG)
    B, S, s_max = 2, 8, 16
    tokens = jax.random.randint(jax.random.fold_in(KEY, 3), (B, S), 0,
                                cfg.vocab)

    def run():
        logits, caches, n = model.prefill(ops, params, {"tokens": tokens},
                                          s_max=s_max, counts=True)
        if mode == "decode":
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            logits, caches, n = model.decode(
                ops, params, caches, tok, jnp.full((B,), S, jnp.int32),
                counts=True)
        return logits, caches, n

    calls = _spy(monkeypatch)
    got = run()
    assert calls and all(c == (True, 4) for c in calls), calls
    monkeypatch.setattr(Model, "_expert_stacks", lambda *a: None)
    calls.clear()
    want = run()
    assert calls and all(c == (False, 3) for c in calls), calls
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_train_keeps_the_sliced_experts(monkeypatch):
    """Training slices each layer's experts out of the stack, and its
    loss and gradients equal those of the layers run one by one on their
    own slices."""
    from repro.models.layers import logits_and_xent, rmsnorm
    cfg, model, params = _moe_model()
    ops = make_ops(AXES1, PCFG)
    B, S = 2, 8
    tokens = jax.random.randint(jax.random.fold_in(KEY, 4), (B, S), 0,
                                cfg.vocab)

    def unrolled(params):
        x, _ = model._embed_in(ops, params, {"tokens": tokens})
        rope = model._rope(jnp.arange(S))
        aux = 0.0
        for seg in model.schedule:
            for l in range(seg.count):
                p = jax.tree.map(lambda a: a[l], params["blocks"][seg.name])
                x, _ = T.block_attn(ops, p, x, cfg, rope, mode="train")
                if seg.kind == "attn_moe":
                    x, a, _ = T.block_moe(ops, p, x, cfg)
                    aux = aux + a
                else:
                    x = T.block_mlp(ops, p, x, cfg)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        nll, n_valid = logits_and_xent(
            ops, params["head"], x[:, :-1], tokens[:, 1:],
            jnp.ones((B, S - 1), jnp.float32), model.v_pad, cfg.vocab)
        return nll / n_valid + cfg.router_aux_coef * aux

    calls = _spy(monkeypatch)
    loss, grads = jax.value_and_grad(
        lambda p: model.loss(ops, p, {"tokens": tokens})[0])(params)
    assert calls and all(c == (False, 3) for c in calls), calls
    want, want_grads = jax.value_and_grad(unrolled)(params)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("sizes", [(5, 0, 7, 3), (0, 0, 9, 0), (0, 0, 0, 0),
                                   (6, 6, 6, 6), (0, 4, 0, 2)])
@pytest.mark.parametrize("block_n", [256, 128])
def test_stack_gmm_equals_the_ragged_dot_over_the_stack(sizes, block_n):
    """The few-rows kernel (interpret mode) over each layer of a stack of
    3 layers x 4 experts equals ``ragged_dot`` over the whole stack with
    sizes zero outside the layer: rows of a group times its expert, the
    rows past the groups zero, empty groups and a layer with no rows
    included, in one column block or two."""
    L, n, K, N, M = 3, 4, 64, 256, 24
    w = jax.random.normal(KEY, (L * n, K, N), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (M, K), jnp.float32)
    s = jnp.array(sizes, jnp.int32)
    for layer in range(L):
        want = MOE._stack_ragged_dot(x, w, s, layer * n)
        got = MOE.stack_gmm(x, w, s, layer * n, block_n=block_n,
                            interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got)[sum(sizes):], 0.0)
