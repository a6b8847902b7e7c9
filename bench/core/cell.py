"""One run of one cell: set-up marks, the measured window, the traced
window's reduction, the correctness checks, and the result line.

A mode (``bench/modes/<mode>.py``) drives the program through
``run(cell)`` and reports into the cell:

  cell.mark(name)            end of a set-up part (``weights``, ...)
  cell.start_window()        set-up ends here; the profiler starts when
                             the run is traced
  cell.end_window()          the window ends; the trace is reduced
  cell.read_memory()         the fullest chip's peak bytes, read before
                             the reference runs
  cell.e2e[name] = value     end-to-end metrics (host clock)
  cell.layer[key] = value    raw inputs of the per-layer readers
  cell.check(name, value)    a number compared with the cell's limit
  cell.attempted, cell.failed
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time
from typing import Optional

from . import xtrace

TRACE_DIR = ".bench_trace"


class Cell:
    def __init__(self, resolved, *, seed: int, seconds: float, trace: bool,
                 control, t_proc: float, compile_log, devices):
        self.r = resolved
        self.name = resolved.workload["name"]
        self.spec = resolved.spec
        self.traffic = resolved.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.control = control          # None, or the control's precision
        self.t_proc = t_proc
        self.compiles = compile_log
        self.devices = devices
        self.chips = int(resolved.workload["chips"])
        self.marks = {}
        self.e2e = {}
        self.layer = {}
        self.checks = []
        self.attempted = 0
        self.failed = 0
        self.notes = {}
        self.memory_peak = None
        self.t_w0 = self.t_w1 = None
        self.reduced: Optional[xtrace.Reduced] = None
        self.host_spans = []            # harness spans, perf_counter clock
        self._anchor = None
        self._cc0 = None

    # -- set-up ----------------------------------------------------------
    def mark(self, name: str):
        self.marks[name] = time.perf_counter()

    @property
    def setup_s(self) -> float:
        return self.t_w0 - self.t_proc

    # -- the window ------------------------------------------------------
    def _trace_dir(self) -> str:
        return os.path.join(self.r.root, TRACE_DIR)

    def start_window(self):
        from repro.obs import trace as obs_trace
        from repro.runtime import global_cache
        self._cc0 = global_cache().snapshot_stats()["cold_compiles"]
        if self.trace:
            import jax
            shutil.rmtree(self._trace_dir(), ignore_errors=True)
            obs_trace.clear()
            obs_trace.enable(ring=1 << 20)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._trace_dir(),
                                     profiler_options=opts)
            self._anchor = time.perf_counter()
            with jax.profiler.TraceAnnotation(xtrace.ANCHOR):
                pass
        self.t_w0 = time.perf_counter()

    def end_window(self, t_end: Optional[float] = None):
        """Close the window at ``t_end`` (default: now) and stop the
        profiler; the trace is read later, by ``finish_trace``, so that
        reading it delays no request."""
        from repro.obs import trace as obs_trace
        self.t_w1 = time.perf_counter() if t_end is None else t_end
        if not self.trace:
            self._window_compiles()
            return
        import jax
        jax.profiler.stop_trace()
        spans = obs_trace.snapshot()
        obs_trace.disable()
        self._window_compiles()
        self.layer["program_spans"] = [
            (s["name"], s["t0"], s["t1"]) for s in spans
            if s["t1"] >= self.t_w0 and s["t0"] <= self.t_w1]

    def _window_compiles(self):
        from repro.runtime import global_cache
        self.layer["window_compiles"] = (
            global_cache().snapshot_stats()["cold_compiles"] - self._cc0
            + self.compiles.backend_compiles(self.t_w0, self.t_w1))

    def finish_trace(self):
        """Reduce the traced window (run after the mode has finished)."""
        if not self.trace or self.t_w1 is None:
            return
        paths = glob.glob(os.path.join(self._trace_dir(), "plugins",
                                       "profile", "*", "*.xplane.pb"))
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        devices, host = xtrace.load(paths[0])
        shutil.rmtree(self._trace_dir(), ignore_errors=True)
        a_ns = xtrace.anchor_ns(host)
        if a_ns is None:
            raise RuntimeError("the trace holds no anchor event")

        def to_ns(t):
            return a_ns + (t - self._anchor) * 1e9

        spans_ns = [(n, to_ns(a), to_ns(b))
                    for n, a, b in self.layer["program_spans"]
                    + self.host_spans]
        self.reduced = xtrace.Reduced(devices, host, to_ns(self.t_w0),
                                      to_ns(self.t_w1), spans_ns)

    @property
    def window_s(self) -> float:
        return self.t_w1 - self.t_w0

    # -- memory and checks -----------------------------------------------
    def read_memory(self):
        peaks = []
        for d in self.devices[:self.chips]:
            st = d.memory_stats() or {}
            if "peak_bytes_in_use" in st:
                peaks.append(int(st["peak_bytes_in_use"]))
        self.memory_peak = max(peaks) if peaks else None

    def note_memory(self, key: str):
        st = self.devices[0].memory_stats() or {}
        if "bytes_in_use" in st:
            self.notes[key] = int(st["bytes_in_use"])

    def limit(self, name: str) -> float:
        return float(self.r.limits["checks"][name]["limit"])

    def check(self, name: str, value: float):
        """Compare ``value`` with the cell's limit for ``name``; a number
        the cell's limits file does not list is only noted."""
        if name in self.r.limits["checks"]:
            self.checks.append((name, float(value), self.limit(name)))
        else:
            self.notes[name] = float(value)

    @property
    def correct(self) -> bool:
        import math
        return bool(self.checks) and self.failed == 0 and all(
            math.isfinite(v) and v <= lim for _, v, lim in self.checks)

    # -- output ----------------------------------------------------------
    def setup_line(self) -> dict:
        cc = dict(self.compiles.counters)
        t_prev, parts = self.t_proc, {}
        for k, t in sorted(self.marks.items(), key=lambda kv: kv[1]):
            parts[k + "_s"] = t - t_prev
            t_prev = t
        parts["rest_s"] = self.t_w0 - t_prev
        return {"setup": {"setup_s": self.setup_s, **parts,
                          "compile_s": self.compiles.covered(self.t_proc,
                                                             self.t_w0),
                          **cc}}

    def device_info(self) -> dict:
        d = self.devices[0]
        info = {"platform": d.platform, "kind": d.device_kind,
                "count": len(self.devices),
                "memory_peak_bytes": self.memory_peak}
        if self.trace and self.reduced is not None:
            info["busy_s"] = self.reduced.busy_s()
            info["window_s"] = self.reduced.window_s
        return info

    def result(self, metrics: dict) -> dict:
        out = {"correct": self.correct, "attempted": int(self.attempted),
               "failed": int(self.failed), "metrics": metrics,
               "device": self.device_info()}
        if self.trace and self.reduced is not None:
            out["breakdown"] = {"device_ops": self.reduced.top_ops(10),
                                "idle_gaps": self.reduced.idle_by_span(10)}
        out["checks"] = {n: {"value": v, "limit": lim}
                         for n, v, lim in self.checks}
        return out

    def print_checks(self):
        for n, v, lim in self.checks:
            print(f"check {n} {v!r} limit {lim!r}", file=sys.stderr)
        sys.stderr.flush()


def emit(obj: dict):
    print(json.dumps(obj), flush=True)
