"""Compile accounting from JAX's own monitoring events: how much of the
set-up is compilation and persistent-cache loading, and whether anything
compiled or was traced inside the measured window; and the pauses of
Python's garbage collector, which stall the host that drives the chip."""
from __future__ import annotations

import gc
import time

import jax

_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration")
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


class Monitor:
    """Counts since construction. JAX keeps listeners for the life of the
    process, so make one Monitor per process."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.trace_s = 0.0
        self.cache_load_s = 0.0
        self.hits = 0
        self.misses = 0
        self.gc_s = 0.0
        self.gc_max_s = 0.0
        self._gc_t0 = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)
        gc.callbacks.append(self._gc)

    def _gc(self, phase: str, info: dict):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            d = time.perf_counter() - self._gc_t0
            self.gc_s += d
            self.gc_max_s = max(self.gc_max_s, d)

    def _dur(self, event: str, duration: float, **_):
        if event == _COMPILE:
            self.compiles += 1
            self.compile_s += duration
        elif event in _TRACE:
            self.trace_s += duration
        elif event == _LOAD:
            self.cache_load_s += duration

    def _event(self, event: str, **_):
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1

    def snapshot(self) -> dict:
        """Backend compile time includes the persistent-cache loads, which
        are also given on their own."""
        return {"compiles": self.compiles,
                "compile_s": round(self.compile_s, 3),
                "trace_lower_s": round(self.trace_s, 3),
                "cache_load_s": round(self.cache_load_s, 3),
                "cache_hits": self.hits, "cache_misses": self.misses}
