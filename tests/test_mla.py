"""Latent attention (MLA) and the expert share against the plain float32
reference of DeepSeek-V2-Lite (``benchmarks/chip/reference_latent.py``)
at smoke widths on the CPU: the same weights from the seed, the same
logits after prefill and through cached decode; a bfloat16-matmul
reference fails the same comparison; the absorbed decode agrees with the
expanded form; YaRN's frequencies and score scale by hand."""
import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import reference_latent as R          # noqa: E402
from repro.configs import get_config                       # noqa: E402
from repro.launch.serve import serving_model, serving_steps  # noqa: E402
from repro.models import transformer as T                  # noqa: E402
from repro.models.layers import (apply_rope, rmsnorm,  # noqa: E402
                                 rope_angles, yarn_inv_freq, yarn_mscale)
from repro.parallel.ops import make_ops                    # noqa: E402

SEED = 2**31 + 17
#: smoke widths with a vocabulary the program does not pad, 4 of the 8
#: routed experts held, as one chip of a 2-way expert-parallel group
CFG = dataclasses.replace(get_config("deepseek-v2-lite", smoke=True),
                          vocab=128, experts_held=4, dtype=jnp.float32)
Y = CFG.rope_yarn
M = dict(n_layers=CFG.n_layers, d_model=CFG.d_model, n_heads=CFG.n_heads,
         d_ff=CFG.d_ff, vocab=CFG.vocab, kv_lora_rank=CFG.kv_lora_rank,
         qk_nope_head_dim=CFG.qk_nope_head_dim,
         qk_rope_head_dim=CFG.qk_rope_head_dim, v_head_dim=CFG.v_head_dim,
         rope_theta=CFG.rope_theta,
         rope_yarn=dict(factor=Y.factor, original_max=Y.original_max,
                        beta_fast=Y.beta_fast, beta_slow=Y.beta_slow,
                        mscale=Y.mscale, mscale_all_dim=Y.mscale_all_dim),
         n_experts=CFG.n_experts, experts_held=CFG.experts_held,
         top_k=CFG.top_k, n_shared_experts=CFG.n_shared_experts,
         moe_d_ff=CFG.moe_d_ff, first_dense_layers=CFG.first_dense_layers,
         norm_topk_prob=CFG.norm_topk_prob, routed_scaling_factor=1.0,
         norm_eps=CFG.norm_eps, init_std=CFG.init_std, dtype="float32")
#: program (float32 on the CPU) against the float32 reference: the two
#: compute the same sums in other orders -- absorbed against expanded
#: attention, grouped against dense expert matmuls, online against plain
#: softmax -- each a few float32 roundings of logits under 1 in size,
#: which read 1e-7 here. 2e-5 leaves room for another CPU's order; a
#: reference whose matrix products round to bfloat16 departs by 1.8e-3.
ATOL = 2e-5


def _weights():
    return jax.jit(lambda k: R.make_weights(M, k))(R.weights_key(SEED))


@pytest.fixture(scope="module")
def program():
    model = serving_model(CFG)
    params = model.init(jax.random.PRNGKey(SEED))
    return model, params, serving_steps(model, 48)


def _served_logits(program, toks, n_prompt):
    """Prefill ``n_prompt`` tokens, then decode the rest one at a time
    through the latent cache: the logits at positions n_prompt-1 ..."""
    model, params, (prefill, decode) = program
    got, caches, counts = prefill(params,
                                  {"tokens": jnp.asarray(toks[:n_prompt])[None]})
    out = [np.asarray(got[0])]
    assert int(counts["moe.dropped_rows"]) == 0
    for p in range(n_prompt, len(toks)):
        got, caches, counts = decode(params, caches,
                                     jnp.asarray([[toks[p]]]),
                                     jnp.asarray([p], jnp.int32))
        out.append(np.asarray(got[0]))
    return np.stack(out)


def test_weights_from_the_seed_match_the_programs(program):
    _, params, _ = program
    w = _weights()
    got = jax.tree_util.tree_flatten_with_path(params)[0]
    want = jax.tree_util.tree_flatten_with_path(w)[0]
    assert [p for p, _ in got] == [p for p, _ in want]
    for (_, a), (_, b) in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-7)


def test_logits_through_prefill_and_decode(program):
    toks = np.random.default_rng(1).integers(0, 128, 40).astype(np.int32)
    ref = np.asarray(R.logits(_weights(), jnp.asarray(toks), M))
    got = _served_logits(program, toks, 30)          # 1 + 10 decode steps
    np.testing.assert_allclose(got, ref[29:40], atol=ATOL)


def test_bfloat16_matmul_reference_fails_the_comparison(program):
    toks = np.random.default_rng(1).integers(0, 128, 40).astype(np.int32)
    low = np.asarray(R.logits(_weights(), jnp.asarray(toks), M, quant="bf16"))
    got = _served_logits(program, toks, 30)
    assert np.max(np.abs(got - low[29:40])) > 10 * ATOL


def test_absorbed_decode_agrees_with_the_expanded_form(program):
    """One MLA block's decode (absorbed, against the latent cache) and
    the expanded form over the same cache: keys and values up-projected
    from every cached latent, the new row in place of the one it
    overwrites."""
    model, params, _ = program
    ops = make_ops(model.axes, model.pcfg)
    p = jax.tree.map(lambda a: a[0], params["blocks"]["seg1"])
    B, S = 3, 16
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    cache = {"c": jax.random.normal(keys[0], (1, B, S, CFG.kv_lora_rank)),
             "kr": jax.random.normal(keys[1], (1, B, S,
                                               CFG.qk_rope_head_dim))}
    x = jax.random.normal(keys[2], (B, 1, CFG.d_model))
    pos = jnp.asarray([3, 9, 15], jnp.int32)
    rope = model._rope(pos[:, None])
    got, rows = T.block_mla(ops, p, x, CFG, rope, cache, pos, "decode",
                            layer=0)

    dn, dr = CFG.qk_nope_head_dim, CFG.qk_rope_head_dim
    c = cache["c"][0].at[jnp.arange(B), pos].set(rows["c"])
    kr = cache["kr"][0].at[jnp.arange(B), pos].set(rows["kr"])
    h = rmsnorm(x, p["ln1"], CFG.norm_eps)
    q = (h @ p["wq"]).reshape(B, 1, -1, dn + dr)
    cos, sin = rope
    q = jnp.concatenate([q[..., :dn], apply_rope(q[..., dn:], cos, sin)], -1)
    kv = jnp.einsum("bsr,rhe->bshe", c, p["w_ukv"].reshape(
        CFG.kv_lora_rank, CFG.n_heads, -1))
    H = CFG.n_heads
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        kr[:, :, None], (B, S, H, dr))], -1)
    s = jnp.einsum("bqhd,bshd->bhqs", q, k) * R.score_scale(M)
    s = jnp.where((jnp.arange(S) <= pos[:, None])[:, None, None], s,
                  -jnp.inf)
    o = jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(s, -1), kv[..., dn:])
    want = x + o.reshape(B, 1, -1) @ p["wo"]
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_yarn_frequencies_and_scale_by_hand():
    """DeepSeek-V2-Lite: 64 rotary dims (32 pairs), theta 10000, factor
    40 over 4096 positions, beta 32 and 1. Pair i turns 4096 *
    theta^(-2i/64) / (2 pi) times: 32 times at i = 10.47, once at
    i = 22.51, so the ramp runs from pair 10 to pair 23."""
    low = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(1e4))
    high = 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(1e4))
    assert math.floor(low) == 10 and math.ceil(high) == 23
    base = [1e4 ** (-2 * i / 64) for i in range(32)]
    want = []
    for i, b in enumerate(base):
        ramp = min(max((i - 10) / 13, 0.0), 1.0)
        want.append(b * (1 - ramp) + b / 40 * ramp)
    y = get_config("deepseek-v2-lite").rope_yarn
    np.testing.assert_allclose(yarn_inv_freq(64, 1e4, y), want, rtol=1e-6)
    np.testing.assert_allclose(
        R.yarn_freqs(64, 1e4, dict(factor=40, original_max=4096,
                                   beta_fast=32, beta_slow=1)),
        want, rtol=1e-6)
    assert want[10] == base[10] and want[23] == base[23] / 40
    # m = 0.1 * 0.707 * ln 40 + 1 = 1.260804; the score scale is
    # 192^-0.5 * m^2, and the cos/sin factor mscale/mscale_all_dim is 1
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40, 0.707) == pytest.approx(m) == \
        pytest.approx(1.260804, abs=1e-6)
    full = dict(M, qk_nope_head_dim=128, qk_rope_head_dim=64,
                rope_yarn=dict(M["rope_yarn"]))
    assert R.score_scale(full) == pytest.approx(192 ** -0.5 * m * m)
    cos, sin = rope_angles(jnp.arange(5), 64, 1e4, y)
    np.testing.assert_allclose(cos[3], np.cos(3 * np.asarray(want)),
                               atol=1e-6)
    np.testing.assert_allclose(sin[3], np.sin(3 * np.asarray(want)),
                               atol=1e-6)
