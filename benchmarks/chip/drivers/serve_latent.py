"""Serving driver for the latent-attention expert configuration
(DeepSeek-V2-Lite, one chip's expert share): the program's own engine
under an open loop, as ``drivers/serve.py`` runs it, with what this
configuration adds.

- ``program_config`` checks the latent-attention and expert sizes too,
  and that the file's published keys say what its ``model`` block says.
- The window's counts also hold the engine's expert counters
  (``EngineStats.counters``, read where the window opens and closes) and
  the causal (query, key) pairs of the prompts prefilled in it.
- Served tokens are checked against ``reference_latent.py``; and no
  routed row may be dropped (``moe_dropped_rows``), in the window or the
  drain after it.
"""
from __future__ import annotations

import dataclasses
import gc
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import counts_latent, reference_latent, traffic
from benchmarks.chip.drivers.serve import Wrap, sample_requests, window
from benchmarks.chip.runtime import peak_bytes

#: ``model``-block keys (each a ``ModelConfig`` field of that name)
FIELDS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff", "vocab",
          "window", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
          "v_head_dim", "rope_theta", "n_experts", "experts_held", "top_k",
          "n_shared_experts", "moe_d_ff", "first_dense_layers",
          "norm_topk_prob", "norm_eps", "act", "init_std")
#: the file's published keys (the model's config.json) and the ``model``
#: keys that must say the same
PUBLISHED = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads",
             "intermediate_size": "d_ff", "vocab_size": "vocab",
             "kv_lora_rank": "kv_lora_rank",
             "qk_nope_head_dim": "qk_nope_head_dim",
             "qk_rope_head_dim": "qk_rope_head_dim",
             "v_head_dim": "v_head_dim", "rope_theta": "rope_theta",
             "n_routed_experts": "n_experts",
             "num_experts_per_tok": "top_k",
             "n_shared_experts": "n_shared_experts",
             "moe_intermediate_size": "moe_d_ff",
             "first_k_dense_replace": "first_dense_layers",
             "norm_topk_prob": "norm_topk_prob", "rms_norm_eps": "norm_eps",
             "routed_scaling_factor": "routed_scaling_factor",
             "experts_held": "experts_held"}
YARN = {"factor": "factor",
        "original_max_position_embeddings": "original_max",
        "beta_fast": "beta_fast", "beta_slow": "beta_slow",
        "mscale": "mscale", "mscale_all_dim": "mscale_all_dim"}


def program_config(conf: dict):
    """The program's registry entry for the configuration, with the keys
    the file lists under ``reduced`` set to the file's values; every
    other size must agree with the file."""
    from repro.configs import get_config
    model = conf["model"]
    bad = {k: (conf[k], model[m]) for k, m in PUBLISHED.items()
           if conf[k] != model[m]}
    bad.update({f"rope_scaling.{k}": (conf["rope_scaling"][k],
                                      model["rope_yarn"][m])
                for k, m in YARN.items()
                if conf["rope_scaling"][k] != model["rope_yarn"][m]})
    if bad:
        raise ValueError(f"published keys and model block disagree: {bad}")
    if model["routed_scaling_factor"] != 1:
        raise ValueError("the program does not rescale the routed sum")
    cfg = get_config(conf["arch"])
    cfg = dataclasses.replace(cfg, **{k: model[k] for k in conf["reduced"]})
    got = {k: getattr(cfg, k) for k in FIELDS}
    got["dtype"] = jnp.dtype(cfg.dtype).name
    got["rope_yarn"] = dataclasses.asdict(cfg.rope_yarn)
    bad = {k: (model[k], got[k]) for k in got if model[k] != got[k]}
    if bad:
        raise ValueError(f"configuration file and program disagree: {bad}")
    return cfg


class LatentWrap(Wrap):
    """``serve.Wrap``, also counting the causal (query, key) pairs of the
    prompts prefilled since the last ``reset``."""

    def reset(self):
        super().reset()
        self.pairs = 0

    def prefill(self, params, batch):
        self.pairs += counts_latent.causal_pairs(int(batch["tokens"].shape[1]))
        return super().prefill(params, batch)


class Marks:
    """The run's tracer as ``serve.window`` sees it. The window calls
    ``start`` as it opens and ``stop`` as it closes, before its drain;
    these also read the engine's counters and the wrapper's pairs there.

    ``stop`` closes the traced window at once but lets the profiler
    write its trace in a thread beside the drain (``join`` waits for
    it): writing a 51 s window's trace takes about 100 s on the chip,
    and the drain, which ``serve.window`` limits to 120 s from the
    window's close, has this cell's backlog to serve. The trace keeps
    recording the drain until it is written; the reduction reads the
    window only."""

    def __init__(self, tracer, eng, wrap: LatentWrap):
        self.tracer, self.eng, self.wrap = tracer, eng, wrap
        self.opened = self.closed = None
        self._writing = None
        self._failed: list[BaseException] = []

    def span(self, name: str):
        return self.tracer.span(name)

    def _read(self):
        return dict(self.eng.stats.counters), self.wrap.pairs

    def start(self) -> None:
        self.opened = self._read()
        self.tracer.start()

    def stop(self) -> None:
        self.closed = self._read()
        self._writing = threading.Thread(target=self._stop)
        self._writing.start()

    def _stop(self) -> None:
        try:
            self.tracer.stop()
        except BaseException as e:      # re-raised by join
            self._failed.append(e)

    def join(self) -> None:
        if self._writing is not None:
            self._writing.join()
        if self._failed:
            raise self._failed[0]


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


def build(cell, seed: int, tracer):
    """The engine through the program's entry point, warmed on every
    prompt length of the mix and every slot; returns it and its wrapper."""
    from repro.launch.serve import build_engine

    conf, mix = cell.config, cell.traffic
    model, dep = conf["model"], conf["deployment"]
    cfg = program_config(conf)
    eng = build_engine(cfg, max_slots=dep["slots"], s_max=dep["s_max"],
                       seed=reference_latent.weights_seed(seed))
    jax.block_until_ready(eng.params)
    wrap = LatentWrap(eng, tracer, model["window"])
    n_warm = max(dep["slots"], len(traffic.prompt_lengths(mix)))
    for p in traffic.warmup_prompts(mix, seed, n_warm, model["vocab"]):
        eng.submit(p, max_new_tokens=2)
    eng.run()
    return eng, wrap


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
    marks = Marks(ctx.tracer, eng, wrap)
    w = window(eng, wrap, reqs, seconds, marks, ctx.compiles)
    marks.join()
    peak = peak_bytes(jax.local_devices()[:cell.chips])
    done = w.done
    failed = len(w.tracked) - len(done)
    counters = _delta(marks.closed[0], marks.opened[0])
    served = _delta(eng.stats.counters, marks.opened[0])  # and the drain
    dropped = sum(v for k, v in served.items()
                  if k.endswith("moe.dropped_rows"))
    record = {
        "model": model, "slots": dep["slots"], "window_s": seconds,
        "requests": len(w.tracked), "ttft_ms": w.ttft_ms(),
        "gen_lag_ms": [(t.submitted - t.spec.due) * 1e3 for t in w.tracked],
        "admit_wait_ms": [(t.prefill_start - t.spec.due) * 1e3
                          for t in w.tracked if t.prefill_start is not None],
        "occupancy_sum": w.occupancy_sum,
        "occupancy_steps": w.occupancy_steps, **w.counts,
        "prompt_pairs": marks.closed[1] - marks.opened[1],
        "counters": counters,
    }
    ctx.log(f"served {len(done)}/{len(w.tracked)} requests; {w.tokens()} "
            f"tokens in the window; {w.counts['decode_steps']} decode steps, "
            f"{w.counts['prefills']} prefills in the window; drained at "
            f"{w.drained_at:.3f} s; expert counters in the window {counters}")

    # free the program's state before the reference takes the device
    sample = sample_requests(done, seed, conf["check"]["sample_tokens"])
    eng.params = eng.caches = None
    del eng, wrap, marks
    gc.collect()
    t0 = time.perf_counter()
    gaps = reference_gaps(cell, seed, sample)
    ctx.log(f"reference: {len(sample)} requests, {gaps['tokens']} served "
            f"tokens, mean gap {gaps['mean']:.6g}, widest {gaps['widest']:.6g}"
            f", {time.perf_counter() - t0:.3f} s")
    checks = {"logit_gap_mean": {"value": gaps["mean"],
                                 "limit": conf["check"]["logit_gap_mean_limit"]},
              "unfinished": {"value": failed, "limit": 0},
              "moe_dropped_rows": {"value": dropped, "limit": 0}}
    return {"setup_end": setup_done, "metrics": w.metrics(),
            "record": record, "checks": checks,
            "correct": all(c["value"] <= c["limit"]
                           for c in checks.values()),
            "attempted": len(w.tracked), "failed": failed,
            "memory_peak_bytes": peak,
            "compiles": {"setup": c_setup, "setup_hits": hits_setup,
                         "window": w.compiles[0], "after": w.compiles[1]}}


def reference_gaps(cell, seed: int, sample, quant=None) -> dict:
    """How far the served tokens lie below the float32 reference's best
    logit at their positions: the mean over every sampled served token,
    and the widest. With ``quant``, the same for the token the control
    puts first at each of those positions. Sequences are padded to the
    mix's longest prompt plus output, so one program serves every run;
    logits are formed only at the output's positions."""
    model = cell.config["model"]
    w = jax.jit(lambda k: reference_latent.make_weights(model, k))(
        reference_latent.weights_key(seed))
    mix = cell.traffic
    n_out = int(mix["output_len"]["max"])
    length = max(traffic.prompt_lengths(mix)) + n_out
    length = -(-length // reference_latent.Q_BLOCK) * reference_latent.Q_BLOCK

    @jax.jit
    def gap(w, toks, plen, served, n):
        pos = jnp.clip(plen - 1 + jnp.arange(n_out), 0, length - 1)
        ref = reference_latent.logits_at(w, toks, pos, model)
        if quant is not None:
            ctl = reference_latent.logits_at(w, toks, pos, model, quant)
            served = jnp.argmax(ctl, axis=-1)
        picked = jnp.take_along_axis(ref, served[:, None], 1)[:, 0]
        g = jnp.where(jnp.arange(n_out) < n, jnp.max(ref, axis=1) - picked,
                      0.0)
        return jnp.max(g), jnp.sum(g)

    widest, total, count = 0.0, 0.0, 0
    for prompt, served in sample:
        toks = np.zeros(length, np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        toks[:len(seq)] = seq
        padded = np.zeros(n_out, np.int32)
        padded[:len(served)] = served
        g_max, g_sum = gap(w, toks, np.int32(len(prompt)), padded,
                           np.int32(len(served)))
        widest = max(widest, float(g_max))
        total += float(g_sum)
        count += len(served)
    del w
    return {"mean": total / count if count else float("inf"),
            "widest": widest, "tokens": count}


def readings(cell, seeds, seconds: float, n_control: int):
    """For each seed, a short window at the cell's own load, drained and
    sampled as a run samples; the program's gaps and, for the first
    ``n_control`` seeds, the ``w8a16`` control's. Yields one dict a seed."""
    from benchmarks.chip import runtime

    tracer = runtime.Tracer(False, "")
    compiles = runtime.CompileCounter()
    for k, seed in enumerate(seeds):
        t0 = time.perf_counter()
        eng, wrap = build(cell, seed, tracer)
        reqs = traffic.open_loop(cell.traffic, seed, seconds,
                                 cell.config["model"]["vocab"])
        w = window(eng, wrap, reqs, seconds, tracer, compiles)
        sample = sample_requests(w.done, seed,
                                 cell.config["check"]["sample_tokens"])
        dropped = sum(v for n, v in eng.stats.counters.items()
                      if n.endswith("moe.dropped_rows"))
        eng.params = eng.caches = None
        del eng, wrap
        gc.collect()
        out = {"seed": seed, "finished": len(w.done), "requests": len(reqs),
               "dropped_rows": dropped,
               "program": reference_gaps(cell, seed, sample)}
        if k < n_control:
            out["control"] = reference_gaps(cell, seed, sample,
                                            quant="w8a16")
        out["seconds"] = time.perf_counter() - t0
        yield out
