#!/usr/bin/env python3
"""Smoke run of the main path on a TPU, in this one process.

Default (one chip): serve h2o-danube-1.8b at its published widths -- all
24 layers, bfloat16, weights drawn from ``--seed`` -- through
``repro.launch.serve.build_engine``, the function the serving entry
point uses: 8 slots, a 4096-token cache (one full attention window), 8
requests of 32 new tokens whose prompts (128 and 512 tokens) are drawn
over the whole vocabulary. Checks that every request returns 32 tokens,
that every logit is finite, and that one request's first decode step
agrees with a fresh prefill (``decode_prefill_gap``).

``--chips 4`` runs only the multi-chip phase, driving four chips from
this process: PeerComm's native/ring/linear collectives on a flat
4-device mesh against numpy and the thread runtime, then the sharded
train step (``mpignite`` and ``gspmd`` paths) on a 2x2 (data, model)
mesh at h2o-danube-1.8b widths, cut to 2 layers.

Any failed check raises, so the script exits non-zero. Only a run whose
checks all passed prints its last line, one JSON object naming the
device. Without a TPU it exits non-zero and names the platform found.

    python chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import os
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402
import numpy as np                                       # noqa: E402

ARCH = "h2o-danube-1.8b"
SLOTS, S_MAX, MAX_NEW = 8, 4096, 32
PROMPT_LENS = (128, 512)
#: per-rank payload of the large collective case: 4 MiB of float32
BIG = 1 << 20
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 8, 256, 3


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileTimes:
    """XLA compile seconds per jitted program, from JAX's own compile
    events (a persistent-cache hit reports its retrieval time)."""

    def __init__(self):
        self.by_name = defaultdict(list)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_event(self, event, duration, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.by_name[fun_name].append(duration)

    def _on_count(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def report(self, names) -> str:
        parts = [f"{n}={[round(d, 3) for d in self.by_name.get(n, [])]}"
                 for n in names]
        other = sum(d for n, ds in self.by_name.items() if n not in names
                    for d in ds)
        return (" ".join(parts) + f" other_total={other:.3f}"
                f" persistent_cache_hits={self.cache_hits}")


# ---------------------------------------------------------------------------
# one chip: serving at published widths
# ---------------------------------------------------------------------------

def serve_phase(seed: int, compiles: CompileTimes) -> None:
    from repro.configs import get_config
    from repro.launch.serve import (LOGIT_GAP_BOUND, LogitWatch,
                                    build_engine, decode_prefill_gap)

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    eng = build_engine(cfg, max_slots=SLOTS, s_max=S_MAX, seed=seed)
    jax.block_until_ready(eng.params)
    n_params = sum(x.size for x in jax.tree.leaves(eng.params))
    log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"dtype {jnp.dtype(cfg.dtype).name}, {n_params} parameters; "
        f"init {time.perf_counter() - t0:.3f} s")

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, PROMPT_LENS[i % 2]).astype(np.int32)
               for i in range(SLOTS)]
    watch = LogitWatch(eng)
    uids = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    t0 = time.perf_counter()
    out = eng.run()
    wall = time.perf_counter() - t0

    lens = [len(out[u]) for u in uids]
    if lens != [MAX_NEW] * SLOTS:
        raise AssertionError(f"token counts {lens}, want {MAX_NEW} each")
    if not watch.all_finite():
        raise AssertionError("non-finite logits")
    # slot 0 holds the first request: all 8 are admitted before decoding
    gap = decode_prefill_gap(eng, watch, 0, prompts[0], out[uids[0]][0])
    log(f"cache consistency: decode vs prefill logit gap {gap:.6f} "
        f"(bound {LOGIT_GAP_BOUND})")
    if not gap <= LOGIT_GAP_BOUND:
        raise AssertionError(f"logit gap {gap} exceeds {LOGIT_GAP_BOUND}")
    log(f"compile seconds (prefill at lengths {PROMPT_LENS} then "
        f"{PROMPT_LENS[0] + 1} for the check; decode at {SLOTS} slots): "
        + compiles.report(("jit(prefill)", "jit(decode)")))
    log(f"informational, one run: {eng.stats.tokens_out} tokens, "
        f"{eng.stats.prefills} prefills, {eng.stats.decode_steps} decode "
        f"steps in {wall:.3f} s wall, first-call compiles included")


# ---------------------------------------------------------------------------
# four chips: PeerComm collectives, then the sharded train step
# ---------------------------------------------------------------------------

def _payload(rank, n: int, xp):
    """Rank's integer-valued float32 payload (exact under any summation
    order); n == 0 is the scalar case."""
    if n == 0:
        return xp.float32(rank + 1)
    return (xp.arange(n) % 1024 + 1000 * rank).astype(xp.float32)


def _a2a_input(x, n: int, xp):
    """alltoall operand: the payload itself, or one scalar per peer."""
    return x + xp.arange(4, dtype=xp.float32) * 10 if n == 0 else x


def _collectives_oracle(n: int):
    xs = [_payload(r, n, np) for r in range(4)]
    a2a = [np.split(np.atleast_1d(_a2a_input(x, n, np)), 4) for x in xs]
    out = []
    for r in range(4):
        group = [g for g in range(4) if g // 2 == r // 2]
        out.append((sum(xs), np.maximum.reduce(xs), xs[1], np.stack(xs),
                    np.concatenate([a2a[src][r] for src in range(4)]),
                    sum(xs[g] for g in group)))
    return out


def collectives_phase() -> None:
    from repro.core import parallelize_func

    for n in (0, BIG):
        def thread_closure(world, n=n):
            r = world.get_rank()
            x = _payload(r, n, np)
            y = np.atleast_1d(_a2a_input(x, n, np))
            half = world.split(r // 2, r)
            return (world.allreduce(x, np.add),
                    world.allreduce(x, np.maximum),
                    world.broadcast(1, x),
                    np.stack(world.allgather(x)),
                    np.concatenate(world.alltoall(np.split(y, 4))),
                    half.allreduce(x, np.add))

        def spmd_closure(world, n=n):
            x = _payload(world.rank(), n, jnp)
            half = world.split([i // 2 for i in range(4)], list(range(4)))
            return (world.allreduce(x, "add"), world.allreduce(x, "max"),
                    world.broadcast(x, root=1), world.allgather(x),
                    world.alltoall(jnp.atleast_1d(_a2a_input(x, n, jnp))),
                    half.allreduce(x, "add"))

        want = _collectives_oracle(n)
        threads = parallelize_func(thread_closure).execute(4)
        _same(threads, want, f"thread runtime, payload {n}")
        for backend in ("native", "ring", "linear"):
            got = parallelize_func(spmd_closure, backend=backend).execute(
                4, mode="spmd")
            _same(got, want, f"spmd {backend}, payload {n}")
            _same(got, threads, f"spmd {backend} vs threads, payload {n}")
            log(f"collectives ok: backend {backend}, "
                f"{'scalar' if n == 0 else f'{4 * n} byte'} payload "
                "(allreduce add/max, broadcast, allgather, alltoall, "
                "split+allreduce)")


def _same(got, want, what: str) -> None:
    names = ("allreduce add", "allreduce max", "broadcast", "allgather",
             "alltoall", "split+allreduce")
    for r, (g, w) in enumerate(zip(got, want)):
        for name, a, b in zip(names, g, w):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                raise AssertionError(f"{what}: {name} differs on rank {r}")


def train_phase(seed: int) -> None:
    from repro.configs import get_config
    from repro.launch.mesh import make_test_mesh
    from repro.launch.train import build, shard_tree
    from repro.parallel.ops import ParallelConfig
    from repro.train.optim import OptConfig
    from repro.train.step import init_opt_state

    cfg = dataclasses.replace(get_config(ARCH), n_layers=TRAIN_LAYERS)
    mesh = make_test_mesh(data=2, model=2)
    opt_cfg = OptConfig(lr_peak=2e-3, warmup_steps=1, total_steps=50,
                        weight_decay=0.0)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + 7), (TRAIN_BATCH, TRAIN_SEQ), 0, cfg.vocab))
    losses, gnorms = {}, {}
    for path in ("mpignite", "gspmd"):
        pcfg = ParallelConfig(path=path, backend="native",
                              sequence_parallel=True, remat="block")
        model, opt, step, ps = build(cfg, mesh, pcfg, opt_cfg, TRAIN_BATCH)
        params = model.init(jax.random.PRNGKey(seed))
        state = shard_tree(init_opt_state(model, opt, params), mesh,
                           ps["opt"])
        params = shard_tree(params, mesh, ps["params"])
        batch = {"tokens": jax.device_put(tokens, jax.sharding.NamedSharding(
            mesh, ps["batch"]["tokens"]))}
        ls, gn = [], []
        with jax.set_mesh(mesh):
            for _ in range(TRAIN_STEPS):
                params, state, metrics = step(params, state, batch)
                ls.append(float(metrics["loss"]))
                gn.append(float(metrics["gnorm"]))
        log(f"train {path}: loss {ls} gnorm {gn}")
        if not (np.all(np.isfinite(ls)) and ls[-1] < ls[0] - 0.02):
            raise AssertionError(f"{path}: loss did not fall: {ls}")
        _report_placement(path, params, ps["params"])
        losses[path], gnorms[path] = ls, gn
    # same weights and batch, so the first step's loss and gradient norm
    # must agree between the explicit-collective and compiler paths. The
    # bounds are those of the float32 CPU check (tests/_dist_checks.py)
    # widened for bfloat16: different reduction orders round differently.
    dl = abs(losses["mpignite"][0] - losses["gspmd"][0])
    dg = abs(gnorms["mpignite"][0] - gnorms["gspmd"][0]) / gnorms["gspmd"][0]
    log(f"mpignite vs gspmd, first step: |loss diff| {dl:.6f}, "
        f"gnorm relative diff {dg:.6f}")
    if dl > 2e-2 or dg > 5e-2:
        raise AssertionError(f"paths disagree: loss {dl}, gnorm {dg}")


def _report_placement(path: str, params, pspecs) -> None:
    """Print where each parameter's shards live; fail unless every leaf
    spans all four devices and every sharded leaf is really split."""
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    specs = jax.tree.leaves(pspecs, is_leaf=lambda s: isinstance(
        s, jax.sharding.PartitionSpec))
    for (kp, leaf), spec in zip(leaves, specs):
        shards = leaf.addressable_shards
        devs = sorted(s.device.id for s in shards)
        split = len({str(s.index) for s in shards})
        log(f"  {path} {jax.tree_util.keystr(kp)} {leaf.shape} {spec} "
            f"shard {shards[0].data.shape} x{split} distinct on devices "
            f"{devs}")
        if len(devs) != 4:
            raise AssertionError(f"{jax.tree_util.keystr(kp)} on {devs}")
        if any(e is not None for e in spec) and split == 1:
            raise AssertionError(f"{jax.tree_util.keystr(kp)} has spec "
                                 f"{spec} but is not split")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache
    cache_dir = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found platform {dev.platform!r} "
              f"({len(devices)} device(s))", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    log(f"jax {jax.__version__}, jaxlib "
        f"{importlib.metadata.version('jaxlib')}, libtpu "
        f"{importlib.metadata.version('libtpu')}")
    log(f"devices: {devices}")
    log(f"compile cache: {cache_dir}")

    compiles = CompileTimes()
    t0 = time.perf_counter()
    if args.chips == 1:
        serve_phase(args.seed, compiles)
    else:
        collectives_phase()
        train_phase(args.seed)
    log(f"phase wall {time.perf_counter() - t0:.3f} s (one call, "
        "informational)")
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        log(f"device {d.id} peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
