"""Serving driver: run the continuous-batching engine against a config.

``build_engine`` is the one place that turns a ``ModelConfig`` into a
served model: the single-device resident-weight layout (``fsdp=False``),
parameters in the config's own dtype, and the jitted prefill and decode
programs the engine runs. ``main`` and the repository's ``chip_smoke.py``
both call it.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b \
        --requests 8 --max-new 12               # smoke widths
    PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
        --no-smoke --slots 8 --s-max 4096       # published widths
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..models.common import ModelConfig
from ..models.model import Model
from ..parallel import axes as A
from ..parallel.ops import ParallelConfig, make_ops
from ..serve.engine import ENV_TRACER, Engine
from .cache import use_compile_cache

#: Bound on the cache-consistency gap (``decode_prefill_gap``): the
#: largest |decode logit - prefill logit| over the largest |prefill
#: logit|. Both programs compute the same function of the same weights;
#: they differ only in reduction order (attention over the slot cache
#: vs. causal attention over the prompt) and in where activations round
#: to the compute dtype. In bfloat16 one rounding moves a value by up to
#: 2^-9 of itself and the residual stream rounds a few times per layer,
#: so the gap grows with depth: on the CPU backend, at h2o-danube-1.8b
#: widths, it read 0.011 at 4 layers and 0.019 at 12 (0 in float32);
#: on one TPU v5e chip at all 24 layers it read 0.024. The bound leaves
#: room for another backend's reduction order. A wrong context -- what
#: a stale slot, a shifted position or a dropped layer amounts to --
#: read above 1.2 at the same widths.
LOGIT_GAP_BOUND = 0.1


def serving_model(cfg: ModelConfig) -> Model:
    """One device, resident weights: no FSDP gather in the decode loop."""
    pcfg = ParallelConfig(sequence_parallel=False, remat="none", fsdp=False)
    return Model(cfg, A.MeshAxes(1, 1, 1), pcfg)


def serving_steps(model: Model, s_max: int):
    """The jitted (prefill, decode) programs the engine runs. Decode
    donates its cache argument: the cache it returns is written into the
    same buffers, so a caller must not read a cache it passed to decode.
    Each returns (logits, caches) and, for a model with expert layers,
    their counts third (``moe.moe_ffn``'s, summed over the layers), which
    the engine fetches with the step's tokens."""
    ops = make_ops(model.axes, model.pcfg)

    def prefill(params, batch):
        out = model.prefill(ops, params, batch, s_max=s_max, counts=True)
        return out if out[2] else out[:2]

    def decode(params, caches, tokens, pos):
        out = model.decode(ops, params, caches, tokens, pos, counts=True)
        return out if out[2] else out[:2]

    return jax.jit(prefill), jax.jit(decode, donate_argnums=(1,))


def build_engine(cfg: ModelConfig, *, max_slots: int, s_max: int,
                 seed: int = 0, tracer=ENV_TRACER) -> Engine:
    """Model, seeded parameters in ``cfg.dtype``, compiled steps, engine.
    ``tracer`` is the engine's (``Engine``'s default: ``$MPIGNITE_TRACE``)."""
    model = serving_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    prefill_fn, decode_fn = serving_steps(model, s_max)
    return Engine(model, params, prefill_fn, decode_fn,
                  max_slots=max_slots, s_max=s_max, tracer=tracer)


class LogitWatch:
    """Wraps an engine's prefill and decode programs: keeps whether every
    logit they returned was finite, and the inputs and logits of the
    first decode step (which ``decode_prefill_gap`` checks)."""

    def __init__(self, eng: Engine):
        self._prefill, self._decode = eng.prefill_fn, eng.decode_fn
        self._finite = []
        self.first_decode = None
        eng.prefill_fn, eng.decode_fn = self.prefill, self.decode

    def prefill(self, params, batch):
        out = self._prefill(params, batch)
        self._finite.append(jnp.isfinite(out[0]).all())
        return out

    def decode(self, params, caches, tokens, pos):
        out = self._decode(params, caches, tokens, pos)
        self._finite.append(jnp.isfinite(out[0]).all())
        if self.first_decode is None:
            # host copies: on the CPU backend the device arrays may share
            # memory with the engine's numpy state, which it then advances
            self.first_decode = (np.array(tokens), np.array(pos), out[0])
        return out

    def all_finite(self) -> bool:
        return bool(self._finite) and all(bool(f) for f in self._finite)


def decode_prefill_gap(eng: Engine, watch: LogitWatch, slot: int,
                       prompt: np.ndarray, first_token: int) -> float:
    """Cache consistency: the logits of ``slot``'s first decode step
    (which read the prompt's keys and values from the engine's batched
    cache) against the last-position logits of a fresh prefill over the
    prompt plus that step's input token. Returns the gap as defined at
    ``LOGIT_GAP_BOUND``."""
    tokens, pos, logits = watch.first_decode
    if int(pos[slot]) != len(prompt) or int(tokens[slot, 0]) != first_token:
        raise AssertionError(
            f"slot {slot}'s first decode step fed token {int(tokens[slot, 0])}"
            f" at position {int(pos[slot])}; expected {first_token} at "
            f"{len(prompt)}")
    ext = np.append(np.asarray(prompt, np.int32), np.int32(first_token))
    want = eng.prefill_fn(eng.params, {"tokens": jnp.asarray(ext)[None]})[0]
    want = np.asarray(want[0], np.float32)
    got = np.asarray(logits[slot], np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--s-max", type=int, default=96)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    eng = build_engine(cfg, max_slots=args.slots, s_max=args.s_max,
                       seed=args.seed)

    rng = np.random.default_rng(args.seed)
    uids = [eng.submit(rng.integers(0, cfg.vocab, 4 + i % 7)
                       .astype(np.int32), max_new_tokens=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    out = eng.run()
    dt = time.time() - t0
    for uid in uids:
        print(f"req {uid}: {out[uid]}")
    s = eng.stats
    print(f"\n{s.tokens_out} tokens in {dt:.2f}s "
          f"({s.tokens_out/dt:.1f} tok/s), {s.prefills} prefills, "
          f"{s.decode_steps} decode steps, mean occupancy "
          f"{s.mean_occupancy:.2f}/{args.slots}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
