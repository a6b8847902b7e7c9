"""Compile accounting for the whole process, from JAX's own events.

JAX reports how long each trace, each lowering to MLIR and each backend
compile (a persistent-cache read included) took, on the thread that did
it, as it ends. One listener, installed once per process by the runtime,
keeps two counters that ``ProgramCache.snapshot_stats()`` exposes:

  backend_compiles  XLA backend compiles (or persistent-cache loads);
  compile_s         seconds covered by trace, lowering or backend
                    compile: the union of the event intervals, so a
                    trace nested in another, or compiles on two threads
                    at once, count once.

Every jit counts, not only the ProgramCache's programs (the store's
slot copies, eager ops). While tracing is on, each event is also
recorded as a ``runtime.compile`` span ``[end - duration, end]`` on the
compiling thread: a first call's compile then nests inside its
``program.<name>`` span and names the device's idle time there.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Tuple

from ..obs import clock
from ..obs.trace import TRACER as _TRACER

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           BACKEND_COMPILE: "backend"}
# merged intervals kept for the union; an older one could only change if
# a single event outlasted this many later, disjoint compiles
_KEEP = 256


class CompileClock:
    """The listener and its counters (thread-safe)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._merged: List[Tuple[float, float]] = []
        self.backend_compiles = 0
        self.compile_s = 0.0

    def on_duration(self, event: str, secs: float, **kw):
        kind = _EVENTS.get(event)
        if kind is None:
            return
        end = clock.now()
        start = end - secs
        with self._lock:
            if event == BACKEND_COMPILE:
                self.backend_compiles += 1
            self._add(start, end)
        if _TRACER.enabled:
            _TRACER.record("runtime.compile", "runtime", start, end,
                           {"event": kind, "fn": kw.get("fun_name", "")})

    def _add(self, lo: float, hi: float):
        """Merge [lo, hi] into the sorted, disjoint intervals (lock held)
        and grow ``compile_s`` by the part no earlier event covered."""
        iv = self._merged
        j = len(iv)
        while j and iv[j - 1][0] > hi:       # entirely after the new one
            j -= 1
        k = j
        while k and iv[k - 1][1] >= lo:      # overlapping or touching
            k -= 1
        old = iv[k:j]
        if old:
            lo, hi = min(lo, old[0][0]), max(hi, old[-1][1])
        self.compile_s += (hi - lo) - sum(b - a for a, b in old)
        iv[k:j] = [(lo, hi)]
        if len(iv) > _KEEP:
            del iv[:len(iv) - _KEEP]

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {"backend_compiles": self.backend_compiles,
                    "compile_s": self.compile_s}


_CLOCK = CompileClock()
_installed = False
_install_lock = threading.Lock()


def install() -> CompileClock:
    """Register the process's one listener with ``jax.monitoring`` (once;
    later calls return the same clock)."""
    global _installed
    with _install_lock:
        if not _installed:
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                _CLOCK.on_duration)
            _installed = True
    return _CLOCK


def snapshot() -> Dict[str, float]:
    return _CLOCK.snapshot()
