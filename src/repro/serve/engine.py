"""Slot-based continuous-batching serving engine.

A fixed pool of ``max_slots`` sequence slots shares one decode step
(compiled once for the full batch); requests are admitted from a FIFO
queue as slots free up, prefilled individually, and decoded together
every engine step. Finished sequences (EOS or budget) release their
slot immediately -- the decode batch is always full-width with a
per-slot active mask, which is the standard continuous-batching trick
to keep the compiled shape static.

The engine is deliberately runtime-agnostic: ``prefill_fn``/``decode_fn``
are compiled steps handed in by the caller, and the engine itself holds
no device or mesh layout. The served programs' steps come from
``launch.serve.serving_steps``. ``decode_fn`` may donate the cache it is
given (``serving_steps``' does): the engine keeps only the cache it
returns. Either step may return a dict of device counters after
(logits, caches) -- ``serving_steps`` returns the expert layers' --
which the engine fetches with the step's tokens and adds up in
``EngineStats.counters`` under ``"prefill.<name>"`` and
``"decode.<name>"``. ``serve/cluster.py`` shards replicas of it across
a warm ``ExecutorPool``; ``serve/spec.py`` plugs draft-model speculative
decoding into ``step()``.

Termination contract: a request finishes when its token hits ``eos_id``,
its ``max_new_tokens`` budget is spent, or its position runs out of
cache (``s_max``) -- the last case sets ``Request.truncated`` so callers
can tell a context-capped generation from a naturally finished one.
Finishing can happen *at prefill* (first token is EOS, or the budget is
one): such a request never occupies a slot and is returned by the next
``step()``/``run()``.

Tracing: with a tracer (``Engine(tracer=...)``, or ``$MPIGNITE_TRACE``
through the process tracer) each step records ``serve.*`` spans into
the tracer's ring and onto the host plane of any running
``jax.profiler`` trace: ``serve.step`` around the step; ``serve.admit``
(one admission) holding ``serve.prefill`` and ``serve.splice`` (with a
draft model, the draft's prefill and splice too); ``serve.decode``
(input build and dispatch), ``serve.fetch`` (the host's wait for the
next tokens) and ``serve.emit`` (termination bookkeeping); and, in the
ring only, ``serve.queue`` from ``submit()`` to the start of the
request's admission; and a ``Tracer.counter`` event per step counter
(the step's own count, category ``serve.prefill`` or ``serve.decode``).
Without a tracer each point is an ``is None`` test.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core.obs.metrics import AcceptanceStats
from ..core.obs.trace import maybe_span, process_tracer

#: category of the engine's spans in the tracer's ring
SPAN_CAT = "serve"
#: ``Engine(tracer=...)`` default: the process tracer, which
#: ``$MPIGNITE_TRACE`` turns on
ENV_TRACER = object()

#: bounded debugging window of recent per-step occupancies kept by
#: EngineStats (the running sum/count is what long-lived replicas use)
OCCUPANCY_TAIL = 256


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int = 16
    eos_id: int = -1                # -1: never stops early
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    #: finished because ``pos`` hit the cache budget (``s_max``), not
    #: EOS and not ``max_new_tokens`` -- the caller's signal that the
    #: generation was cut off rather than completed
    truncated: bool = False
    #: ``perf_counter_ns`` at ``submit()`` when the engine traces (0 when
    #: it does not): the start of the request's ``serve.queue`` span
    queued_ns: int = 0


class Generation(list):
    """A finished request's tokens. Compares equal to a plain list (so
    ``out[uid] == expected_tokens`` keeps working) and carries the
    per-request outcome flags alongside."""

    def __init__(self, tokens, uid: int, truncated: bool = False,
                 accept_ratio: float | None = None):
        super().__init__(tokens)
        self.uid = uid
        self.truncated = truncated
        #: mean speculative-decoding acceptance ratio over this
        #: request's spec rounds (None when spec decoding never ran)
        self.accept_ratio = accept_ratio


@dataclasses.dataclass
class EngineStats:
    prefills: int = 0
    decode_steps: int = 0
    tokens_out: int = 0
    #: requests finished by the ``s_max`` cache budget (truncated)
    truncations: int = 0
    #: requests finished at prefill (first token was terminal)
    prefill_finishes: int = 0
    #: engine steps that ran the speculative (propose+verify) path
    spec_rounds: int = 0
    #: running occupancy aggregate -- O(1) however long the engine
    #: lives; ``occupancy_tail`` keeps a bounded recent window for
    #: debugging
    occupancy_sum: int = 0
    occupancy_steps: int = 0
    occupancy_tail: deque = dataclasses.field(
        default_factory=lambda: deque(maxlen=OCCUPANCY_TAIL))
    #: step counters summed over the engine's life, "<phase>.<name>"
    #: (phase: prefill | decode), as the steps returned them
    counters: dict = dataclasses.field(default_factory=dict)

    def record_occupancy(self, n: int) -> None:
        self.occupancy_sum += int(n)
        self.occupancy_steps += 1
        self.occupancy_tail.append(int(n))

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / max(self.occupancy_steps, 1)

    @property
    def batch_occupancy(self) -> list[int]:
        """Recent per-step occupancies (bounded window -- the unbounded
        list it replaces grew forever on serving replicas)."""
        return list(self.occupancy_tail)

    def summary(self) -> dict:
        return {"prefills": self.prefills,
                "decode_steps": self.decode_steps,
                "tokens_out": self.tokens_out,
                "truncations": self.truncations,
                "prefill_finishes": self.prefill_finishes,
                "spec_rounds": self.spec_rounds,
                "mean_occupancy": self.mean_occupancy,
                "counters": dict(self.counters)}


class Engine:
    """``spec`` (optional) is a ``serve.spec.SpecDecoder``: when set and
    every active slot has cache headroom, ``step()`` proposes ``gamma``
    draft tokens per slot and verifies them in one fused target dispatch,
    emitting 1..gamma+1 tokens per slot per step (greedy outputs are
    bit-identical to the non-speculative path by construction).

    ``batch_axes`` optionally pins the cache batch axis (one int for
    every leaf, or a pytree of ints congruent with the cache); when
    omitted the engine derives each leaf's batch axis from the model's
    ``cache_specs`` metadata -- see ``_batch_axis_tree``.

    ``tracer`` is an ``obs.Tracer`` that records the engine's spans (see
    the module docstring), or None for none; the default is the process
    tracer, on when ``$MPIGNITE_TRACE`` asks. ``self.tracer`` is read at
    every call, so it may also be set on a built engine."""

    def __init__(self, model, params, prefill_fn: Callable,
                 decode_fn: Callable, max_slots: int, s_max: int,
                 spec=None, batch_axes=None, tracer=ENV_TRACER):
        self.model = model
        self.params = params
        self.prefill_fn = prefill_fn
        self.decode_fn = decode_fn
        self.max_slots = max_slots
        self.s_max = s_max
        self.queue: deque[Request] = deque()
        self.slots: list[Request | None] = [None] * max_slots
        self.pos = np.zeros((max_slots,), np.int32)      # next position
        self.cur_tok = np.zeros((max_slots,), np.int32)
        self.active = np.zeros((max_slots,), bool)
        self.caches = None                               # batched cache tree
        self.stats = EngineStats()
        self.acceptance = AcceptanceStats()
        self.spec = spec
        self._batch_axes = batch_axes
        self.tracer = process_tracer() if tracer is ENV_TRACER else tracer
        self._axis_tree = None                  # resolved on first prefill
        self._draft_caches = None
        self._draft_axis_tree = None
        #: requests finished at prefill, to be returned by the next
        #: step()/run() -- they never occupied a slot
        self._prefill_finished: list[Request] = []
        #: live per-request spec accounting: uid -> [proposed, accepted]
        self._uid = 0

    # ---- public API --------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               eos_id: int = -1, uid: int | None = None) -> int:
        """Queue one request. ``uid`` lets a front-end (serve/cluster.py)
        assign globally unique ids across replicas; left None, the
        engine numbers requests itself."""
        if uid is None:
            self._uid += 1
            uid = self._uid
        else:
            self._uid = max(self._uid, int(uid))
        queued_ns = 0 if self.tracer is None else self.tracer.now()
        self.queue.append(Request(uid, np.asarray(prompt, np.int32),
                                  max_new_tokens, eos_id,
                                  queued_ns=queued_ns))
        return uid

    def pending(self) -> int:
        """Queued + in-flight + finished-but-uncollected requests --
        the engine's load measure (what least-loaded routing compares)."""
        return (len(self.queue) + int(self.active.sum())
                + len(self._prefill_finished))

    def run(self) -> dict[int, Generation]:
        """Drive to completion; returns {uid: Generation} (a Generation
        compares equal to the plain token list and carries
        ``truncated``/``accept_ratio``)."""
        out: dict[int, Generation] = {}
        while self.queue or any(self.active) or self._prefill_finished:
            for r in self.step():
                out[r.uid] = self._generation(r)
        return out

    def _generation(self, req: Request) -> Generation:
        return Generation(req.out_tokens, req.uid, req.truncated,
                          self.acceptance.pop_request(req.uid))

    # ---- engine step --------------------------------------------------------
    def step(self) -> list[Request]:
        tr = self.tracer
        with maybe_span(tr, "serve.step", SPAN_CAT):
            self._admit(tr)
            finished: list[Request] = list(self._prefill_finished)
            self._prefill_finished.clear()
            if not any(self.active):
                return finished
            if self.spec is not None and self._spec_eligible():
                return finished + self._spec_step(tr)
            with maybe_span(tr, "serve.decode", SPAN_CAT):
                tokens = jnp.asarray(self.cur_tok)[:, None]
                pos = jnp.asarray(self.pos)
                logits, self.caches, *counters = self.decode_fn(
                    self.params, self.caches, tokens, pos)
                if self._draft_caches is not None:
                    # keep the draft cache position-consistent: the draft
                    # decodes the same token at the same position the
                    # target just did, so a later spec round resumes from
                    # an aligned prefix
                    _, self._draft_caches = self.spec.draft_decode(
                        self._draft_caches, tokens, pos)
            self.stats.decode_steps += 1
            self.stats.record_occupancy(int(self.active.sum()))
            with maybe_span(tr, "serve.fetch", SPAN_CAT):
                next_tok, counters = jax.device_get(
                    (jnp.argmax(logits, axis=-1), counters))
                next_tok = np.asarray(next_tok, np.int32)
            with maybe_span(tr, "serve.emit", SPAN_CAT):
                self._count("decode", counters, tr)
                for i, req in enumerate(self.slots):
                    if req is None or not self.active[i]:
                        continue
                    self.pos[i] += 1
                    if self._emit(i, req, int(next_tok[i])):
                        finished.append(req)
            return finished

    def _count(self, phase: str, counters: list, tr) -> None:
        """Add a step's fetched counters (none, or one dict) to the
        stats, and emit them as counter events when tracing."""
        total = self.stats.counters
        for name, v in (counters[0].items() if counters else ()):
            key = f"{phase}.{name}"
            total[key] = total.get(key, 0) + int(v)
            if tr is not None:
                tr.counter(name, int(v), f"{SPAN_CAT}.{phase}")

    def _emit(self, slot: int, req: Request, tok: int) -> bool:
        """Append one generated token; apply the termination contract.
        Returns True (and frees the slot) when the request finished.
        Caller has already advanced ``pos`` past the token that
        *produced* ``tok``."""
        req.out_tokens.append(tok)
        self.stats.tokens_out += 1
        self.cur_tok[slot] = tok
        hit_eos = tok == req.eos_id
        hit_budget = len(req.out_tokens) >= req.max_new_tokens
        hit_ctx = bool(self.pos[slot] >= self.s_max - 1)
        if hit_eos or hit_budget or hit_ctx:
            req.done = True
            req.truncated = hit_ctx and not (hit_eos or hit_budget)
            if req.truncated:
                self.stats.truncations += 1
            self.active[slot] = False
            self.slots[slot] = None
            return True
        return False

    # ---- speculative decoding ----------------------------------------------
    def _spec_eligible(self) -> bool:
        """Every active slot must have cache headroom for gamma+1 writes
        (positions pos..pos+gamma all < s_max); otherwise this step falls
        back to the one-token path so near-budget requests still finish
        correctly."""
        gamma = self.spec.gamma
        act = self.active
        return bool(np.all(self.pos[act] + gamma < self.s_max))

    def _spec_step(self, tr) -> list[Request]:
        """One speculative round: the draft proposes gamma tokens per
        slot, the target verifies them in one fused dispatch, and each
        slot emits its accepted prefix plus the target's correction
        token -- greedy acceptance, so the emitted stream is bit-equal
        to plain decoding."""
        sp = self.spec
        gamma = sp.gamma
        with maybe_span(tr, "serve.decode", SPAN_CAT):
            # inactive rows still flow through the batched scans; pin
            # their inputs to position 0 so the dead rows' writes never
            # clamp
            pos_in = np.where(self.active, self.pos, 0).astype(np.int32)
            tok_in = np.where(self.active, self.cur_tok, 0).astype(np.int32)
            draft_toks, self._draft_caches = sp.propose(
                self._draft_caches, jnp.asarray(tok_in), jnp.asarray(pos_in))
            verified, self.caches = sp.verify(
                self.params, self.caches, jnp.asarray(tok_in), draft_toks,
                jnp.asarray(pos_in))
        self.stats.decode_steps += 1
        self.stats.spec_rounds += 1
        self.stats.record_occupancy(int(self.active.sum()))
        with maybe_span(tr, "serve.fetch", SPAN_CAT):
            d = np.asarray(draft_toks)              # (B, gamma)
            v = np.asarray(verified)                # (B, gamma+1)
        finished: list[Request] = []
        with maybe_span(tr, "serve.emit", SPAN_CAT):
            for i, req in enumerate(self.slots):
                if req is None or not self.active[i]:
                    continue
                # longest prefix where the draft guessed the target's token
                agree = d[i] == v[i, :gamma]
                n_acc = int(np.cumprod(agree).sum())
                self.acceptance.record(req.uid, gamma, n_acc)
                for tok in v[i, :n_acc + 1]:
                    self.pos[i] += 1
                    if self._emit(i, req, int(tok)):
                        finished.append(req)
                        break
        return finished

    # ---- admission + prefill -------------------------------------------------
    def _admit(self, tr):
        for i in range(self.max_slots):
            # a request that finishes at prefill never takes the slot --
            # keep admitting into it until something survives prefill
            while self.slots[i] is None and self.queue:
                req = self.queue.popleft()
                if tr is None:
                    self._prefill_into(i, req, None)
                    continue
                if req.queued_ns:       # 0: queued while untraced
                    tr.complete("serve.queue", SPAN_CAT, req.queued_ns,
                                args={"uid": req.uid})
                with tr.span("serve.admit", SPAN_CAT,
                             {"uid": req.uid, "prompt_len": len(req.prompt)}):
                    self._prefill_into(i, req, tr)

    def _prefill_into(self, slot: int, req: Request, tr):
        """Prefill one request and splice its cache into the batch cache.
        If the prefill token itself is terminal (EOS, a budget of one,
        or a prompt already at the cache limit), the request finishes
        here: it never occupies the slot, never costs a decode step, and
        is returned by the next ``step()``."""
        with maybe_span(tr, "serve.prefill", SPAN_CAT):
            batch = {"tokens": jnp.asarray(req.prompt)[None, :]}
            logits, cache1, *counters = self.prefill_fn(self.params, batch)
            self.stats.prefills += 1
            logits, counters = jax.device_get((logits, counters))
            first = int(np.argmax(logits[0]))
        self._count("prefill", counters, tr)
        req.out_tokens.append(first)
        self.stats.tokens_out += 1
        pos = len(req.prompt)
        hit_eos = first == req.eos_id
        hit_budget = req.max_new_tokens <= 1
        hit_ctx = pos >= self.s_max - 1
        if hit_eos or hit_budget or hit_ctx:
            req.done = True
            req.truncated = hit_ctx and not (hit_eos or hit_budget)
            if req.truncated:
                self.stats.truncations += 1
            self.stats.prefill_finishes += 1
            self._prefill_finished.append(req)
            return
        with maybe_span(tr, "serve.splice", SPAN_CAT):
            if self._axis_tree is None:
                self._axis_tree = self._batch_axis_tree(cache1, self.model)
            if self.caches is None:
                self.caches = jax.tree_util.tree_map(
                    self._widen, cache1, self._axis_tree)
            self.caches = jax.tree_util.tree_map(
                lambda full, one, ax: self._splice(full, one, slot, ax),
                self.caches, cache1, self._axis_tree)
            if self.spec is not None:
                self._prefill_draft(slot, req)
        self.slots[slot] = req
        self.active[slot] = True
        self.pos[slot] = pos
        self.cur_tok[slot] = first

    def _prefill_draft(self, slot: int, req: Request):
        """Mirror the prefill into the draft model's slot cache."""
        dcache1 = self.spec.draft_prefill(req.prompt)
        if self._draft_axis_tree is None:
            self._draft_axis_tree = self._batch_axis_tree(
                dcache1, self.spec.draft_model)
        if self._draft_caches is None:
            self._draft_caches = jax.tree_util.tree_map(
                self._widen, dcache1, self._draft_axis_tree)
        self._draft_caches = jax.tree_util.tree_map(
            lambda full, one, ax: self._splice(full, one, slot, ax),
            self._draft_caches, dcache1, self._draft_axis_tree)

    # ---- cache layout -------------------------------------------------------
    def _batch_axis_tree(self, cache1, model):
        """Per-leaf batch axis of the cache tree.

        The prefill cache carries batch size 1, but a size-1 dim is NOT
        proof of batch-ness: a single-KV-head layout has a legitimate
        size-1 head axis *before* batch, and widening/splicing that axis
        silently corrupts other slots' caches. So the axis is derived
        from ground truth where available: the model's ``cache_specs``
        metadata evaluated at two batch sizes -- the axis whose extent
        follows the batch argument IS the batch axis, whatever size-1
        dims surround it. An explicit ``batch_axes`` constructor arg
        wins; the first-size-1 heuristic survives only as the fallback
        for models without cache metadata."""
        if self._batch_axes is not None:
            if isinstance(self._batch_axes, int):
                return jax.tree_util.tree_map(
                    lambda _: self._batch_axes, cache1)
            return self._batch_axes
        specs = getattr(model, "cache_specs", None)
        if specs is not None:
            try:
                s1, s3 = specs(1, self.s_max), specs(3, self.s_max)
                tree = jax.tree_util.tree_map(
                    lambda a, b, c: _axis_from_specs(a, b, c), s1, s3,
                    cache1)
                return tree
            except Exception:       # noqa: BLE001 -- metadata shape drift
                pass                # falls through to the heuristic
        return jax.tree_util.tree_map_with_path(_first_one_axis, cache1)

    def _widen(self, c, axis: int):
        """(1, ...)-batched single cache -> zeros of full slot width."""
        shape = list(c.shape)
        shape[axis] = self.max_slots
        return jnp.zeros(shape, c.dtype)

    def _splice(self, full, one, slot: int, axis: int):
        idx = [slice(None)] * one.ndim
        idx[axis] = slice(slot, slot + 1)
        return full.at[tuple(idx)].set(one)


def _axis_from_specs(spec1, spec3, leaf) -> int:
    """Batch axis = the dim whose extent tracked the batch argument
    across two ``cache_specs`` evaluations (1 vs 3)."""
    for i, (a, b) in enumerate(zip(spec1.shape, spec3.shape)):
        if a != b:
            return i
    return _first_one_axis((), leaf)


def _first_one_axis(path, c) -> int:
    """Fallback heuristic for metadata-less models: the first size-1
    dim. Ambiguous layouts (several size-1 dims) should pass
    ``batch_axes`` explicitly."""
    for i, s in enumerate(c.shape):
        if s == 1:
            return i
    leaf = jax.tree_util.keystr(path) if path else "<leaf>"
    raise ValueError(
        f"cannot locate batch axis in cache leaf {leaf}: no size-1 "
        f"dimension in shape {c.shape} (prefill caches must keep the "
        "single-request batch dim)")
