"""Compile and persistent-cache accounting from JAX's monitoring events.

Compile seconds are the wall time covered by tracing, lowering or XLA
compilation (persistent-cache reads included) on any thread: the union of
the event intervals, so nested traces and compiles on parallel threads
count once. Backend compiles are also counted, so a compile inside a
measured window shows.
"""
from __future__ import annotations

import threading
import time

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Collects compile spans (perf_counter clock) and cache counters."""

    def __init__(self):
        self._lock = threading.Lock()
        self.spans = []              # (start, end, event)
        self.counters = {"pcache_requests": 0, "pcache_hits": 0,
                         "pcache_misses": 0}

    def on_duration(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            end = time.perf_counter()
            with self._lock:
                self.spans.append((end - secs, end, event))

    def on_event(self, event, **_):
        key = {"/jax/compilation_cache/compile_requests_use_cache":
               "pcache_requests",
               "/jax/compilation_cache/cache_hits": "pcache_hits",
               "/jax/compilation_cache/cache_misses": "pcache_misses"}.get(event)
        if key:
            with self._lock:
                self.counters[key] += 1

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def covered(self, t0: float, t1: float) -> float:
        """Seconds of [t0, t1] covered by compile spans."""
        with self._lock:
            spans = sorted((a, b) for a, b, _ in self.spans)
        total, reach = 0.0, t0
        for a, b in spans:
            a, b = max(a, reach), min(b, t1)
            if b > a:
                total += b - a
                reach = b
        return total

    def backend_compiles(self, t0: float, t1: float) -> int:
        """XLA backend compiles that ended inside [t0, t1]."""
        with self._lock:
            return sum(1 for a, b, e in self.spans
                       if e == BACKEND_COMPILE and t0 <= b <= t1)
