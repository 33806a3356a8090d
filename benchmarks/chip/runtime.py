"""Run-time helpers shared by the drivers: compile counting, host spans
and the profiler window, device memory."""
from __future__ import annotations

import contextlib
import glob
import os
import time

import jax

#: every host span the benchmark writes starts with this, so the trace
#: reduction can tell them from the runtime's own host events
SPAN_PREFIX = "bench."


class CompileCounter:
    """XLA compiles and persistent-cache hits, from JAX's own monitoring
    events (a cache hit also reports a backend compile event holding its
    retrieval time)."""

    def __init__(self):
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _on_count(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, int]:
        return self.count, self.cache_hits


class Tracer:
    """Host spans on the profiler's clock, and the traced window.

    Off (``--trace 0``), ``span`` costs one attribute test and nothing is
    recorded. On, each span is a ``jax.profiler.TraceAnnotation`` and
    ``start``/``stop`` bracket the window with the JAX profiler."""

    def __init__(self, on: bool, log_dir: str):
        self.on = on
        self.log_dir = log_dir
        self._t0 = None
        self.window_s = None

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    def start(self) -> None:
        if self.on:
            jax.profiler.start_trace(self.log_dir)
            self._t0 = time.perf_counter()

    def stop(self) -> None:
        if self.on and self._t0 is not None:
            self.window_s = time.perf_counter() - self._t0
            jax.profiler.stop_trace()
            self._t0 = None

    def trace_file(self) -> str | None:
        found = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
