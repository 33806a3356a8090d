"""Serving driver: the program's own engine under an open loop.

Set-up builds the engine with ``repro.launch.serve.build_engine`` (the
program's entry point), warms every prompt length of the mix and every
slot, then the window offers the mix's requests at their due times from
this one thread. Tokens are stamped when ``Engine.step`` hands them
back, which is when a caller of the engine could stream them. After the
window the requests due in it are drained (no new arrivals), the
program's state is freed, and a sample of what was served is checked
against ``reference.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import counts, reference, traffic
from benchmarks.chip.readers import percentile
from benchmarks.chip.runtime import peak_bytes

#: a request due in the window may take this long past its close to
#: finish; one that has not is a failure, not a late answer
DRAIN_LIMIT_S = 120.0


@dataclasses.dataclass
class Tracked:
    spec: traffic.Request
    req: object = None           # the engine's Request
    submitted: float = 0.0
    prefill_start: float | None = None
    stamps: list = dataclasses.field(default_factory=list)


class Wrap:
    """The engine's prefill and decode programs, called through this
    object: spans, the start of each request's prefill, and the live
    positions each decode step attends to."""

    def __init__(self, eng, tracer, window: int):
        self.eng = eng
        self.tracer = tracer
        self.window = window
        self._prefill, self._decode = eng.prefill_fn, eng.decode_fn
        eng.prefill_fn, eng.decode_fn = self.prefill, self.decode
        self.waiting: list[Tracked] = []      # submitted, not yet admitted
        self.clock = None                     # window clock, once it runs
        self.reset()

    def reset(self):
        self.prefills = 0
        self.prompt_tokens = 0
        self.decode_steps = 0
        self.live = 0                         # sum over steps and slots
        self.active = 0

    def prefill(self, params, batch):
        if self.clock is not None:
            queued = {id(r) for r in self.eng.queue}
            for t in self.waiting:
                if id(t.req) not in queued:   # popped for this prefill
                    t.prefill_start = self.clock()
                    self.waiting.remove(t)
                    break
        self.prefills += 1
        self.prompt_tokens += int(batch["tokens"].shape[1])
        with self.tracer.span("prefill"):
            return self._prefill(params, batch)

    def decode(self, params, caches, tokens, pos):
        act = self.eng.active
        self.decode_steps += 1
        self.active += int(act.sum())
        self.live += sum(counts.live_positions(int(p), self.window)
                         for p in self.eng.pos[act])
        with self.tracer.span("decode"):
            return self._decode(params, caches, tokens, pos)


#: configuration-file keys (each a ``ModelConfig`` field) and what the
#: program reads them as
FIELDS = {"n_layers": "n_layers", "d_model": "d_model", "n_heads": "n_heads",
          "n_kv_heads": "n_kv_heads", "head_dim": "dh", "d_ff": "d_ff",
          "vocab": "vocab", "window": "window", "rope_theta": "rope_theta",
          "rope_pct": "rope_pct", "norm_eps": "norm_eps",
          "init_std": "init_std", "act": "act", "qk_norm": "qk_norm"}


def program_config(conf: dict):
    """The program's registry entry for the configuration, with the keys
    the file lists under ``reduced`` set to the file's values; every
    other size must agree with the file."""
    from repro.configs import get_config
    cfg = get_config(conf["arch"])
    model = conf["model"]
    cfg = dataclasses.replace(cfg, **{k: model[k] for k in conf["reduced"]})
    got = {k: getattr(cfg, f) for k, f in FIELDS.items()}
    got["dtype"] = jnp.dtype(cfg.dtype).name
    bad = {k: (model[k], got[k]) for k in got if k in model
           and model[k] != got[k]}
    if bad:
        raise ValueError(f"configuration file and program disagree: {bad}")
    return cfg


def build(cell, seed: int, tracer):
    """The engine through the program's entry point, warmed on every
    prompt length of the mix and every slot (the splice into a slot is a
    program of its own); returns the engine and its wrapper."""
    from repro.launch.serve import build_engine

    conf, mix = cell.config, cell.traffic
    model, dep = conf["model"], conf["deployment"]
    cfg = program_config(conf)
    eng = build_engine(cfg, max_slots=dep["slots"], s_max=dep["s_max"],
                       seed=reference.weights_seed(seed))
    jax.block_until_ready(eng.params)
    wrap = Wrap(eng, tracer, model["window"])
    n_warm = max(dep["slots"], len(traffic.prompt_lengths(mix)))
    for p in traffic.warmup_prompts(mix, seed, n_warm, model["vocab"]):
        eng.submit(p, max_new_tokens=2)
    eng.run()
    return eng, wrap


@dataclasses.dataclass
class Window:
    tracked: list
    seconds: float
    counts: dict            # what the wrapper counted inside the window
    occupancy_sum: int
    occupancy_steps: int
    compiles: tuple         # (in the window, in the drain)
    drained_at: float

    @property
    def done(self) -> list:
        return [t for t in self.tracked if t.req is not None and t.req.done]

    def metrics(self) -> dict:
        return {"serve_tokens_per_s": self.tokens() / self.seconds,
                "ttft_p50_ms": percentile(self.ttft_ms(), 50)}

    def ttft_ms(self) -> list[float]:
        return [(t.stamps[0] - t.spec.due) * 1e3 for t in self.done]

    def tokens(self) -> int:
        return sum(1 for t in self.tracked for s in t.stamps
                   if s < self.seconds)


def window(eng, wrap, reqs, seconds: float, tracer, compiles) -> Window:
    """Offer ``reqs`` at their due times for ``seconds``, then drain the
    requests due in the window with no new arrivals."""
    tracked = [Tracked(r) for r in reqs]
    live: list[Tracked] = []
    stats0 = dataclasses.replace(eng.stats)
    wrap.reset()

    def submit(t: Tracked, now: float):
        with tracer.span("submit"):
            eng.submit(t.spec.prompt, max_new_tokens=t.spec.max_new)
        t.req = eng.queue[-1]
        t.submitted = now
        wrap.waiting.append(t)
        live.append(t)

    def step() -> None:
        with tracer.span("engine_step"):
            eng.step()
        now = clock()
        for t in live:
            for _ in range(len(t.req.out_tokens) - len(t.stamps)):
                t.stamps.append(now)
        live[:] = [t for t in live if not t.req.done]

    c0 = compiles.snapshot()[0]
    tracer.start()
    t0 = time.perf_counter()
    clock = lambda: time.perf_counter() - t0
    wrap.clock = clock
    i, n = 0, len(tracked)
    while True:
        now = clock()
        if now >= seconds:
            break
        while i < n and tracked[i].spec.due <= now:
            submit(tracked[i], now)
            i += 1
        if eng.queue or eng.active.any():
            step()
        elif i < n:
            time.sleep(max(0.0, min(tracked[i].spec.due, seconds) - clock()))
    c1 = compiles.snapshot()[0]
    tracer.stop()
    st = eng.stats
    counts_in = dict(prefills=wrap.prefills, prompt_tokens=wrap.prompt_tokens,
                     decode_steps=wrap.decode_steps, live=wrap.live,
                     active=wrap.active)
    occ = (st.occupancy_sum - stats0.occupancy_sum,
           st.occupancy_steps - stats0.occupancy_steps)
    while i < n:                      # due in the window, not yet sent
        submit(tracked[i], clock())
        i += 1
    while live and clock() < seconds + DRAIN_LIMIT_S:
        step()
    wrap.clock = None
    return Window(tracked, seconds, counts_in, occ[0], occ[1],
                  (c1 - c0, compiles.snapshot()[0] - c1), clock())


def run(cell, seed: int, seconds: float, ctx) -> dict:
    conf, mix = cell.config, cell.traffic
    model, dep = conf["model"], conf["deployment"]
    eng, wrap = build(cell, seed, ctx.tracer)
    c_setup, hits_setup = ctx.compiles.snapshot()
    reqs = traffic.open_loop(mix, seed, seconds, model["vocab"])
    ctx.log(f"window: {len(reqs)} requests over {seconds} s, prompt tokens "
            f"{sum(len(r.prompt) for r in reqs)}, output tokens asked "
            f"{sum(r.max_new for r in reqs)}")
    setup_done = time.perf_counter()
    w = window(eng, wrap, reqs, seconds, ctx.tracer, ctx.compiles)
    peak = peak_bytes(jax.local_devices()[:cell.chips])
    done = w.done
    failed = len(w.tracked) - len(done)
    record = {
        "model": model, "slots": dep["slots"], "window_s": seconds,
        "requests": len(w.tracked), "ttft_ms": w.ttft_ms(),
        "gen_lag_ms": [(t.submitted - t.spec.due) * 1e3 for t in w.tracked],
        "admit_wait_ms": [(t.prefill_start - t.spec.due) * 1e3
                          for t in w.tracked if t.prefill_start is not None],
        "occupancy_sum": w.occupancy_sum,
        "occupancy_steps": w.occupancy_steps, **w.counts,
    }
    ctx.log(f"served {len(done)}/{len(w.tracked)} requests; {w.tokens()} "
            f"tokens in the window; {w.counts['decode_steps']} decode steps, "
            f"{w.counts['prefills']} prefills in the window; drained at "
            f"{w.drained_at:.3f} s")

    # free the program's state before the reference takes the device
    sample = sample_requests(done, seed, conf["check"]["sample_tokens"])
    eng.params = eng.caches = None
    del eng, wrap
    gc.collect()
    t0 = time.perf_counter()
    gaps = reference_gaps(cell, seed, sample)
    ctx.log(f"reference: {len(sample)} requests, {gaps['tokens']} served "
            f"tokens, mean gap {gaps['mean']:.6g}, widest {gaps['widest']:.6g}"
            f", {time.perf_counter() - t0:.3f} s")
    checks = {f"logit_gap_{k}": {"value": gaps[k],
                                 "limit": conf["check"][f"logit_gap_{k}_limit"]}
              for k in ("mean", "widest")
              if f"logit_gap_{k}_limit" in conf["check"]}
    checks["unfinished"] = {"value": failed, "limit": 0}
    return {"setup_end": setup_done, "metrics": w.metrics(),
            "record": record, "checks": checks,
            "correct": all(c["value"] <= c["limit"]
                           for c in checks.values()),
            "attempted": len(w.tracked), "failed": failed,
            "memory_peak_bytes": peak,
            "compiles": {"setup": c_setup, "setup_hits": hits_setup,
                         "window": w.compiles[0], "after": w.compiles[1]}}


def sample_requests(done: list[Tracked], seed: int, want_tokens: int):
    """Finished requests to check, drawn from the seed: the one with the
    most served tokens, then others until ``want_tokens`` are covered."""
    order = sorted(done, key=lambda t: -len(t.req.out_tokens))
    if not order:
        return []
    rng = np.random.default_rng([int(seed), 3])
    rest = [order[j] for j in rng.permutation(len(order) - 1) + 1]
    picked, total = [order[0]], len(order[0].req.out_tokens)
    for t in rest:
        if total >= want_tokens:
            break
        picked.append(t)
        total += len(t.req.out_tokens)
    return [(t.spec.prompt, np.asarray(t.req.out_tokens, np.int32))
            for t in picked]


def reference_gaps(cell, seed: int, sample, quant=None) -> dict:
    """How far the served tokens lie below the float32 reference's best
    logit at their positions: the mean over every sampled served token,
    and the widest. With ``quant``, the same for the token the control
    puts first at each of those positions. Sequences are padded to the
    mix's longest prompt plus output, so one program serves every run."""
    model = cell.config["model"]
    w = jax.jit(lambda k: reference.make_weights(model, k))(
        reference.weights_key(seed))
    mix = cell.traffic
    length = (max(traffic.prompt_lengths(mix))
              + int(mix["output_len"].get("max", 0)))
    length = -(-length // reference.Q_BLOCK) * reference.Q_BLOCK

    @jax.jit
    def gap(w, toks, plen, served, n):
        ref = reference.logits(w, toks, model)
        pos = plen - 1 + jnp.arange(served.shape[0])
        if quant is not None:
            ctl = reference.logits(w, toks, model, quant)
            served = jnp.argmax(ctl, axis=-1)[jnp.clip(pos, 0, length - 1)]
        rl = ref[jnp.clip(pos, 0, length - 1)]
        picked = jnp.take_along_axis(rl, served[:, None], 1)[:, 0]
        g = jnp.where(jnp.arange(served.shape[0]) < n,
                      jnp.max(rl, axis=1) - picked, 0.0)
        return jnp.max(g), jnp.sum(g)

    widest, total, count = 0.0, 0.0, 0
    for prompt, served in sample:
        toks = np.zeros(length, np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        toks[:len(seq)] = seq
        padded = np.zeros(length, np.int32)
        padded[:len(served)] = served
        g_max, g_sum = gap(w, toks, np.int32(len(prompt)), padded,
                           np.int32(len(served)))
        widest = max(widest, float(g_max))
        total += float(g_sum)
        count += len(served)
    del w
    return {"mean": total / count if count else float("inf"),
            "widest": widest, "tokens": count}
