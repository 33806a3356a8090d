"""Continuous-batching engine: greedy generations through the slot engine
must equal direct prefill+decode on the same model; slots recycle;
termination (EOS / budget / context cap) is honored at prefill and at
decode; speculative decoding is bit-identical to plain greedy; the
engine's spans match its counters and change no output, and with tracing
off record and allocate nothing."""
import dataclasses
import os
import subprocess
import sys
import tracemalloc
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.obs import Tracer
from repro.core.obs import trace as trace_mod
from repro.launch.cache import CHECKOUT_CACHE
from repro.launch.serve import (LOGIT_GAP_BOUND, LogitWatch, build_engine,
                                decode_prefill_gap)
from repro.models.common import ParamSpec
from repro.models.model import Model
from repro.parallel import axes as A
from repro.parallel.ops import ParallelConfig, make_ops
from repro.serve.cluster import ClusterServer
from repro.serve.engine import OCCUPANCY_TAIL, Engine
from repro.serve.spec import SpecDecoder

AXES1 = A.MeshAxes(1, 1, 1)
PCFG = ParallelConfig(path="mpignite", sequence_parallel=False, remat="none")


def build(arch="qwen3-4b", s_max=48, slots=3, gamma=0, draft="self",
          tracer=None):
    cfg = dataclasses.replace(get_config(arch, smoke=True),
                              dtype=jnp.float32)
    model = Model(cfg, AXES1, PCFG)
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    ops = make_ops(AXES1, PCFG)

    @jax.jit
    def prefill_fn(params, batch):
        return model.prefill(ops, params, batch, s_max=s_max)

    @jax.jit
    def decode_fn(params, caches, tokens, pos):
        return model.decode(ops, params, caches, tokens, pos)

    spec = None
    if gamma:
        if draft == "self":       # draft == target: accepts everything
            dmodel, dparams = model, params
        else:                     # genuinely smaller, disagreeing draft
            dcfg = dataclasses.replace(cfg, n_layers=1,
                                       name=cfg.name + "-draft")
            dmodel = Model(dcfg, AXES1, PCFG)
            dparams = dmodel.init(jax.random.PRNGKey(1), dtype=jnp.float32)
        spec = SpecDecoder(model, ops, dmodel, dparams, s_max=s_max,
                           gamma=gamma)
    eng = Engine(model, params, prefill_fn, decode_fn, max_slots=slots,
                 s_max=s_max, spec=spec, tracer=tracer)
    return cfg, model, params, ops, eng


def reference_generate(model, params, ops, prompt, n_new, s_max):
    batch = {"tokens": jnp.asarray(prompt, jnp.int32)[None]}
    logits, caches = model.prefill(ops, params, batch, s_max=s_max)
    toks = [int(np.argmax(np.asarray(logits)[0]))]
    pos = len(prompt)
    for i in range(n_new - 1):
        logits, caches = model.decode(
            ops, params, caches,
            jnp.asarray([[toks[-1]]], jnp.int32),
            jnp.asarray([pos + i], jnp.int32))
        toks.append(int(np.argmax(np.asarray(logits)[0])))
    return toks


def test_engine_matches_direct_decode():
    cfg, model, params, ops, eng = build()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (5, 9, 7)]
    uids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    out = eng.run()
    for uid, prompt in zip(uids, prompts):
        want = reference_generate(model, params, ops, prompt, 6, eng.s_max)
        assert out[uid] == want, (uid, out[uid], want)


def test_engine_continuous_batching_recycles_slots():
    cfg, model, params, ops, eng = build(slots=2)
    rng = np.random.default_rng(1)
    uids = [eng.submit(rng.integers(0, cfg.vocab, 4).astype(np.int32),
                       max_new_tokens=3 + i) for i in range(5)]
    out = eng.run()
    assert set(out) == set(uids)
    assert [len(out[u]) for u in uids] == [3, 4, 5, 6, 7]
    assert eng.stats.prefills == 5
    assert max(eng.stats.batch_occupancy) == 2   # both slots were used


def test_engine_eos_stops_early():
    cfg, model, params, ops, eng = build()
    prompt = np.arange(5, dtype=np.int32)
    want = reference_generate(model, params, ops, prompt, 8, eng.s_max)
    eos = want[2]
    uid = eng.submit(prompt, max_new_tokens=8, eos_id=eos)
    out = eng.run()
    assert out[uid] == want[:3]   # stops at first appearance of eos


# ---------------------------------------------------------------------------
# Termination at prefill (regression: a first token that is already
# terminal used to occupy a slot, burn a decode step, and over-generate)
# ---------------------------------------------------------------------------

def test_prefill_finish_eos_and_budget_of_one():
    cfg, model, params, ops, eng = build()
    prompt = np.arange(5, dtype=np.int32)
    first = reference_generate(model, params, ops, prompt, 1, eng.s_max)[0]
    u_eos = eng.submit(prompt, max_new_tokens=8, eos_id=first)
    u_one = eng.submit(prompt, max_new_tokens=1)
    out = eng.run()
    assert out[u_eos] == [first]      # exactly one token, not one extra
    assert out[u_one] == [first]
    assert eng.stats.decode_steps == 0          # never touched a slot
    assert eng.stats.prefill_finishes == 2
    assert eng.stats.tokens_out == 2
    assert not out[u_eos].truncated and not out[u_one].truncated
    assert not any(eng.active) and not eng.queue


def test_prefill_finish_frees_slot_for_next_in_queue():
    cfg, model, params, ops, eng = build(slots=1)
    prompt = np.arange(5, dtype=np.int32)
    want = reference_generate(model, params, ops, prompt, 3, eng.s_max)
    u_one = eng.submit(prompt, max_new_tokens=1)    # finishes at prefill
    u_norm = eng.submit(prompt, max_new_tokens=3)
    out = eng.run()
    # the single slot was re-admitted in the same step the first request
    # finished at prefill -- both prefills before any decode progress
    assert eng.stats.prefills == 2
    assert out[u_one] == want[:1]
    assert out[u_norm] == want


# ---------------------------------------------------------------------------
# Context-budget truncation is distinguishable from EOS
# ---------------------------------------------------------------------------

def test_truncated_flag_pins_context_cap():
    cfg, model, params, ops, eng = build(s_max=16)
    prompt = np.arange(5, dtype=np.int32)
    uid = eng.submit(prompt, max_new_tokens=100)
    out = eng.run()
    assert out[uid].truncated is True
    assert len(out[uid]) == 11          # pos 5 -> 15 == s_max - 1
    assert eng.stats.truncations == 1
    # a natural budget finish is NOT flagged
    uid2 = eng.submit(prompt, max_new_tokens=3)
    out2 = eng.run()
    assert out2[uid2].truncated is False and len(out2[uid2]) == 3
    assert eng.stats.truncations == 1


def test_truncated_at_prefill():
    cfg, model, params, ops, eng = build(s_max=16)
    prompt = np.arange(15, dtype=np.int32)      # already at s_max - 1
    uid = eng.submit(prompt, max_new_tokens=8)
    out = eng.run()
    assert out[uid].truncated is True and len(out[uid]) == 1
    assert eng.stats.decode_steps == 0
    assert eng.stats.truncations == 1


# ---------------------------------------------------------------------------
# Toy model with a deliberately ambiguous cache layout: a singleton
# "head" axis BEFORE batch -- (1, B, s_max). The first-size-1-dim
# heuristic widens/splices axis 0 here and silently corrupts other
# slots' caches (jnp clamps the out-of-range batch indices); the
# cache_specs shape-diff must pick axis 1.
# ---------------------------------------------------------------------------

TOY_VOCAB = 11


class ToyModel:
    def __init__(self, s_max):
        self.s_max = s_max

    def cache_specs(self, batch, s_max):
        return {"kv": ParamSpec((1, batch, s_max))}


def toy_fns(s_max):
    def prefill_fn(params, batch):
        toks = batch["tokens"]                      # (1, S)
        S = toks.shape[1]
        c = jnp.zeros((1, 1, s_max), jnp.int32)
        c = c.at[0, 0, :S].set(toks[0] + 1)         # +1: zero means empty
        nxt = (toks.sum() * 7 + S) % TOY_VOCAB
        return jax.nn.one_hot(nxt, TOY_VOCAB)[None], {"kv": c}

    def decode_fn(params, caches, tokens, pos):
        c = caches["kv"]                            # (1, B, s_max)
        B = tokens.shape[0]
        c = c.at[0, jnp.arange(B), pos].set(tokens[:, 0] + 1)
        s = (c[0].sum(axis=1) * 7 + pos + 1) % TOY_VOCAB
        return jax.nn.one_hot(s, TOY_VOCAB), {"kv": c}

    return prefill_fn, decode_fn


def toy_reference(prompt, n_new, s_max):
    store = np.zeros(s_max, np.int64)
    S = len(prompt)
    store[:S] = np.asarray(prompt, np.int64) + 1
    toks = [int((np.asarray(prompt).sum() * 7 + S) % TOY_VOCAB)]
    pos = S
    for _ in range(n_new - 1):
        store[pos] = toks[-1] + 1
        toks.append(int((store.sum() * 7 + pos + 1) % TOY_VOCAB))
        pos += 1
    return toks


def test_batch_axis_detected_from_cache_specs():
    s_max = 24
    pf, df = toy_fns(s_max)
    eng = Engine(ToyModel(s_max), None, pf, df, max_slots=3, s_max=s_max)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, TOY_VOCAB, n).astype(np.int32)
               for n in (4, 6, 5)]
    uids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    out = eng.run()
    for uid, p in zip(uids, prompts):
        assert out[uid] == toy_reference(p, 6, s_max), uid
    # the metadata pinned the real batch axis despite the leading 1
    assert jax.tree_util.tree_leaves(eng._axis_tree) == [1]


def test_batch_axis_explicit_override_without_metadata():
    s_max = 24
    pf, df = toy_fns(s_max)
    # no model => no cache_specs; the ambiguous layout must be pinned
    # explicitly (the heuristic would pick axis 0 and corrupt slots)
    eng = Engine(None, None, pf, df, max_slots=3, s_max=s_max,
                 batch_axes=1)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, TOY_VOCAB, n).astype(np.int32)
               for n in (5, 3, 7)]
    uids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    out = eng.run()
    for uid, p in zip(uids, prompts):
        assert out[uid] == toy_reference(p, 5, s_max), uid


# ---------------------------------------------------------------------------
# O(1) occupancy stats
# ---------------------------------------------------------------------------

def test_occupancy_stats_are_bounded():
    s_max = 32
    pf, df = toy_fns(s_max)
    eng = Engine(ToyModel(s_max), None, pf, df, max_slots=2, s_max=s_max)
    rng = np.random.default_rng(4)
    for _ in range(80):
        eng.submit(rng.integers(0, TOY_VOCAB, 4).astype(np.int32),
                   max_new_tokens=8)
    eng.run()
    assert eng.stats.decode_steps > OCCUPANCY_TAIL
    assert len(eng.stats.batch_occupancy) == OCCUPANCY_TAIL   # bounded
    assert eng.stats.occupancy_steps == eng.stats.decode_steps
    assert 1.0 < eng.stats.mean_occupancy <= 2.0
    assert max(eng.stats.batch_occupancy) == 2    # back-compat surface


# ---------------------------------------------------------------------------
# Speculative decoding: bit-identical to greedy, acceptance telemetry
# ---------------------------------------------------------------------------

def test_spec_decode_identical_draft_accepts_everything():
    cfg, model, params, ops, eng = build(gamma=3, draft="self")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (5, 9, 7)]
    uids = [eng.submit(p, max_new_tokens=10) for p in prompts]
    out = eng.run()
    for uid, p in zip(uids, prompts):
        want = reference_generate(model, params, ops, p, 10, eng.s_max)
        assert out[uid] == want, uid
        assert out[uid].accept_ratio == 1.0
    assert eng.acceptance.ratio == 1.0
    # gamma+1 tokens per verified dispatch: 10 tokens in ceil(9/4)=3
    # target dispatches instead of 9
    assert eng.stats.spec_rounds == 3
    assert eng.stats.decode_steps == 3
    assert eng.acceptance.live == {}       # per-request state popped


def test_spec_decode_small_draft_still_bit_exact():
    cfg, model, params, ops, eng = build(gamma=3, draft="small")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (5, 9, 7)]
    uids = [eng.submit(p, max_new_tokens=10) for p in prompts]
    out = eng.run()
    for uid, p in zip(uids, prompts):
        want = reference_generate(model, params, ops, p, 10, eng.s_max)
        assert out[uid] == want, uid        # rejections change cost only
    assert eng.stats.spec_rounds >= 3
    assert 0.0 <= eng.acceptance.ratio <= 1.0


def test_spec_decode_falls_back_near_context_budget():
    # s_max=16: slots run out of headroom for gamma+1 writes near the
    # end, so the engine must degrade to single-token steps and still
    # truncate exactly where the plain path does
    cfg, model, params, ops, eng = build(s_max=16, gamma=3, draft="self")
    prompt = np.arange(5, dtype=np.int32)
    uid = eng.submit(prompt, max_new_tokens=100)
    out = eng.run()
    cfg2, model2, params2, ops2, plain = build(s_max=16)
    uid2 = plain.submit(prompt, max_new_tokens=100)
    out2 = plain.run()
    assert list(out[uid]) == list(out2[uid2])
    assert out[uid].truncated and len(out[uid]) == 11
    assert eng.stats.spec_rounds > 0                 # spec ran early on
    assert eng.stats.decode_steps > eng.stats.spec_rounds   # then fell back


# ---------------------------------------------------------------------------
# Engine spans: counted like EngineStats, free when off
# ---------------------------------------------------------------------------

def _spans(tr: Tracer, name: str) -> list:
    return [e for e in tr.events() if e[2] == name]


@pytest.mark.parametrize("gamma", [0, 3], ids=["plain", "spec"])
def test_traced_engine_outputs_bit_identical(gamma):
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (5, 9, 7, 6)]
    outs, stats = [], []
    for tracer in (None, Tracer(0, 1, capacity=4096)):
        cfg, model, params, ops, eng = build(gamma=gamma, draft="small",
                                             tracer=tracer)
        uids = [eng.submit(p, max_new_tokens=7) for p in prompts]
        out = eng.run()
        outs.append([list(out[u]) for u in uids])
        stats.append(eng.stats.summary())
    assert outs[0] == outs[1]
    assert stats[0] == stats[1]
    assert tracer.dropped == 0
    assert len(_spans(tracer, "serve.decode")) == eng.stats.decode_steps
    assert len(_spans(tracer, "serve.fetch")) == eng.stats.decode_steps
    assert len(_spans(tracer, "serve.prefill")) == eng.stats.prefills
    assert eng.stats.spec_rounds == (eng.stats.decode_steps if gamma else 0)


def test_span_counts_match_engine_stats():
    s_max = 32
    pf, df = toy_fns(s_max)
    tr = Tracer(0, 1, capacity=4096)
    eng = Engine(ToyModel(s_max), None, pf, df, max_slots=2, s_max=s_max,
                 tracer=tr)
    rng = np.random.default_rng(9)
    # budgets of one finish at prefill: admitted, never spliced
    uids = [eng.submit(rng.integers(0, TOY_VOCAB, 3 + i % 4).astype(np.int32),
                       max_new_tokens=1 if i % 3 == 0 else 4 + i % 5)
            for i in range(9)]
    steps = 0
    while eng.queue or eng.active.any() or eng._prefill_finished:
        eng.step()
        steps += 1
    st = eng.stats
    assert tr.dropped == 0 and st.prefill_finishes == 3
    assert len(_spans(tr, "serve.step")) == steps
    assert len(_spans(tr, "serve.decode")) == st.decode_steps
    assert len(_spans(tr, "serve.fetch")) == st.decode_steps
    assert len(_spans(tr, "serve.emit")) == st.decode_steps
    assert len(_spans(tr, "serve.prefill")) == st.prefills == 9
    assert len(_spans(tr, "serve.splice")) == st.prefills - 3
    queue = {e[6]["uid"]: e for e in _spans(tr, "serve.queue")}
    admit = {e[6]["uid"]: e for e in _spans(tr, "serve.admit")}
    assert sorted(queue) == sorted(admit) == uids
    assert len(_spans(tr, "serve.queue")) == len(_spans(tr, "serve.admit"))
    step_iv = [(e[3], e[3] + e[4]) for e in _spans(tr, "serve.step")]
    admit_iv = [(e[3], e[3] + e[4]) for e in admit.values()]
    for uid, a in admit.items():
        q = queue[uid]
        # the queue span ends where its admission starts, in a step
        assert q[3] + q[4] <= a[3]
        assert a[6]["prompt_len"] > 0
        assert any(s0 <= a[3] and a[3] + a[4] <= s1 for s0, s1 in step_iv)
    for e in _spans(tr, "serve.prefill") + _spans(tr, "serve.splice"):
        assert any(s0 <= e[3] and e[3] + e[4] <= s1 for s0, s1 in admit_iv)
        assert e[1] == "serve"


def test_engine_tracer_defaults_to_the_environment(monkeypatch):
    pf, df = toy_fns(16)
    monkeypatch.delenv(trace_mod.TRACE_ENV, raising=False)
    trace_mod.reset_process_tracer()
    try:
        assert Engine(ToyModel(16), None, pf, df, 2, 16).tracer is None
        monkeypatch.setenv(trace_mod.TRACE_ENV, "1")
        trace_mod.reset_process_tracer()
        eng = Engine(ToyModel(16), None, pf, df, 2, 16)
        assert eng.tracer is trace_mod.process_tracer() is not None
        eng.submit(np.arange(3, dtype=np.int32), max_new_tokens=2)
        eng.run()
        assert len(_spans(eng.tracer, "serve.admit")) == 1
    finally:
        trace_mod.reset_process_tracer()


def test_untraced_engine_step_allocates_nothing_in_trace_module():
    """Tracing off, over a whole run: the trace module's code does no
    work beyond the ``maybe_span`` guard (it enters no other function of
    its own and calls nothing, so it constructs nothing), and holds no
    allocation afterwards (the tracemalloc pin of the message runtime)."""
    s_max = 32
    pf, df = toy_fns(s_max)
    eng = Engine(ToyModel(s_max), None, pf, df, max_slots=2, s_max=s_max,
                 tracer=None)
    rng = np.random.default_rng(10)

    def load():
        for i in range(6):
            eng.submit(rng.integers(0, TOY_VOCAB, 4).astype(np.int32),
                       max_new_tokens=1 if i == 0 else 5)
        eng.run()

    load()                                      # compile, warm code paths
    here = trace_mod.__file__
    entered, called = set(), []

    def audit(frame, event, arg):
        if event == "call" and frame.f_code.co_filename == here:
            entered.add(frame.f_code.co_name)
        caller = frame.f_back if event == "call" else frame
        if (event in ("call", "c_call") and caller is not None
                and caller.f_code.co_filename == here):
            called.append(arg if event == "c_call" else frame.f_code)

    steps0 = eng.stats.decode_steps
    sys.setprofile(audit)
    try:
        load()
    finally:
        sys.setprofile(None)
    assert eng.stats.decode_steps > steps0
    assert entered == {"maybe_span"} and not called, (entered, called)
    tracemalloc.start()
    try:
        load()
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    stats = snap.filter_traces(
        [tracemalloc.Filter(True, here)]).statistics("lineno")
    assert not stats, [str(s) for s in stats]


# ---------------------------------------------------------------------------
# Cluster front-end, local mode: the routing/ack/merge machinery over
# in-process engines (the cluster lane exercises the pooled real thing)
# ---------------------------------------------------------------------------

def test_cluster_server_local_mode_routes_and_drains():
    s_max = 24

    def build_engine(params, replica_id):
        pf, df = toy_fns(s_max)
        return Engine(ToyModel(s_max), None, pf, df, max_slots=2,
                      s_max=s_max)

    srv = ClusterServer(2, build_engine, mode="local", quantum=4)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, TOY_VOCAB, 3 + i % 4).astype(np.int32)
               for i in range(7)]
    uids = [srv.submit(p, max_new_tokens=5 + i % 3)
            for i, p in enumerate(prompts)]
    out = srv.run_until_drained()
    assert set(out) == set(uids)
    for i, (uid, p) in enumerate(zip(uids, prompts)):
        assert list(out[uid]) == toy_reference(p, 5 + i % 3, s_max), uid
        assert srv.latency(uid) is not None
    assert srv.rounds >= 2                  # quantum forced multi-round
    prefills = [srv.replica_stats[s]["stats"]["prefills"]
                for s in sorted(srv.replica_stats)]
    assert sum(prefills) == 7 and all(p > 0 for p in prefills)


# ---------------------------------------------------------------------------
# The serving build that launch.serve and chip_smoke.py share, with the
# cache-consistency check chip_smoke.py runs on the chip
# ---------------------------------------------------------------------------

def test_build_engine_serves_and_first_decode_matches_prefill():
    cfg = get_config("h2o-danube-1.8b", smoke=True)   # bfloat16, as served
    eng = build_engine(cfg, max_slots=4, s_max=64, seed=0)
    assert all(x.dtype == cfg.dtype for x in jax.tree.leaves(eng.params)
               if x.ndim > 1)
    watch = LogitWatch(eng)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (8, 16, 8, 16)]
    uids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    out = eng.run()
    assert [len(out[u]) for u in uids] == [5] * 4
    assert watch.all_finite()
    for slot, (uid, p) in enumerate(zip(uids, prompts)):
        gap = decode_prefill_gap(eng, watch, slot, p, out[uid][0])
        assert gap <= LOGIT_GAP_BOUND, (slot, gap)


def test_compile_cache_dir_from_env_or_checkout(tmp_path):
    where = ("from repro.launch.cache import use_compile_cache\n"
             "print(use_compile_cache())\n")
    compile_one = ("import jax, jax.numpy as jnp\n"
                   "jax.config.update("
                   "'jax_persistent_cache_min_compile_time_secs', 0)\n"
                   "jax.jit(lambda x: x * 3 + 1)(jnp.ones(4))"
                   ".block_until_ready()\n")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"

    def run(code):
        return subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120,
                              check=True).stdout.strip()

    # unset: a fixed path at the root of the checkout
    assert run(where) == str(CHECKOUT_CACHE)
    assert CHECKOUT_CACHE == Path(__file__).resolve().parents[1] / ".jax_cache"
    # set: JAX's own variable wins, and the compile lands there
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    assert run(where + compile_one) == str(tmp_path / "cache")
    assert any((tmp_path / "cache").iterdir())
