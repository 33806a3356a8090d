"""The one traffic generator. A mix is a data file (``traffic/<name>.json``)
whose ``generator`` names one of the functions below and whose other
keys are its parameters.

Every seed gets the same work: the same set of prompt lengths, output
lengths and inter-arrival gaps, drawn as fixed quantiles of the mix's
laws (or, for a law with no closed-form quantile, from a stream that
does not depend on the seed), in one order that does not depend on the
seed either: near a server's capacity its latency tail depends on which
long request lands next to which, so an order drawn per seed would make
seeds differ in work. Only the token ids come from the seed.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

#: stream for the order of the schedule and for laws drawn rather than
#: taken as quantiles: fixed, so that every seed gets the same schedule
FIXED_STREAM = 20240117


@dataclasses.dataclass
class Request:
    due: float              # seconds after the window opens
    prompt: np.ndarray      # (prompt_len,) int32 token ids
    max_new: int            # output tokens asked for (no EOS)


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.Generator(np.random.Philox(key=[int(seed), salt]))


def _mids(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _gaps(arrival: dict, n: int) -> np.ndarray:
    rate = float(arrival["rate_per_s"])
    if arrival["kind"] == "poisson":
        return -np.log1p(-_mids(n)) / rate
    if arrival["kind"] == "gamma":
        k = 1.0 / float(arrival["cv"]) ** 2
        g = np.random.default_rng(FIXED_STREAM).gamma(k, 1.0 / (rate * k), n)
        return np.sort(g)
    raise ValueError(f"unknown arrival kind {arrival['kind']!r}")


def _counts(values: list, weights: list, n: int) -> np.ndarray:
    """``n`` values in the given proportions (largest remainder)."""
    w = np.asarray(weights, float) / sum(weights)
    exact = w * n
    cnt = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - cnt), kind="stable")[:n - cnt.sum()]:
        cnt[i] += 1
    return np.repeat(np.asarray(values, int), cnt)


def _lengths(law: dict, n: int) -> np.ndarray:
    if law["kind"] == "choice":
        return _counts(law["values"], law["weights"], n)
    if law["kind"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(u) for u in _mids(n)])
        x = np.exp(math.log(law["median"]) + law["sigma"] * z)
        return np.clip(np.rint(x), law["min"], law["max"]).astype(int)
    if law["kind"] == "fixed":
        return np.full(n, int(law["value"]))
    raise ValueError(f"unknown length law {law['kind']!r}")


def open_loop(mix: dict, seed: int, seconds: float,
              vocab: int) -> list[Request]:
    """Requests due in a window of ``seconds``, in order of due time.
    The count is the rate times the window; the gaps are scaled to fill
    the window exactly, so the offered rate is the mix's own."""
    n = max(1, round(float(mix["arrival"]["rate_per_s"]) * seconds))
    rng = _rng(seed, 1)
    order = np.random.default_rng(FIXED_STREAM)
    gaps = order.permutation(_gaps(mix["arrival"], n))
    gaps *= seconds / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    plen = order.permutation(_lengths(mix["prompt_len"], n))
    olen = order.permutation(_lengths(mix["output_len"], n))
    return [Request(float(due[i]),
                    rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
                    int(olen[i])) for i in range(n)]


def prompt_lengths(mix: dict) -> list[int]:
    """Every prompt length the mix can send (what set-up warms)."""
    law = mix["prompt_len"]
    if law["kind"] == "choice":
        return sorted(int(v) for v in law["values"])
    if law["kind"] == "fixed":
        return [int(law["value"])]
    raise ValueError(f"prompt law {law['kind']!r} has no finite set")


def warmup_prompts(mix: dict, seed: int, count: int,
                   vocab: int) -> list[np.ndarray]:
    """``count`` prompts cycling through every length of the mix."""
    rng = _rng(seed, 2)
    lens = prompt_lengths(mix)
    return [rng.integers(0, vocab, lens[i % len(lens)], dtype=np.int32)
            for i in range(count)]


def token_batch(mix: dict, seed: int, step: int, vocab: int) -> np.ndarray:
    """Training batch ``step``: (global_batch, seq) token ids, a pure
    function of (seed, step), so every step's rows differ."""
    rng = _rng(seed, 1000 + int(step))
    return rng.integers(0, vocab, (int(mix["global_batch"]),
                                   int(mix["seq"])), dtype=np.int32)
