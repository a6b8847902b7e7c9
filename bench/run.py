#!/usr/bin/env python3
"""Run one cell of Push's benchmark on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The workload names a cell of
``BENCHMARK.json``; its configuration, traffic mix, mode, correctness
limits and per-layer metric readers are files found by name (see
``bench/core/registry.py``). With ``--trace 0`` the result line carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a profiler trace of the window and from the program's spans
and counters.

Standard output: one ``{"setup": ...}`` line that splits the set-up
time, then the result line (last). Standard error ends with each number
that decided ``correct``, beside its limit. No TPU, or fewer chips than
the cell asks for: exit code 3 and no result.

The program runs at the matmul precision its configuration states
(``matmul_precision``, JAX's default precision for every thread); the
reference always computes at "highest". ``--control`` computes one step
below (``CONTROL_PRECISION``) to show that the correctness limits catch
it: the training mode runs the program there; the serving mode puts the
reference there, in the service's place. The benchmark's own runs never
use it.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _paths():
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the program one precision below the "
                         "configuration's (correctness control)")
    return ap.parse_args(argv)


def use_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``repro.launch.compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_cache``, a fixed path, so every run of a
    checkout finds what the first one compiled). Every program is
    cached, however fast it compiled, so set-up is the same work each
    run."""
    import jax
    from repro.launch.compile_cache import use_compile_cache as place
    path = place(root)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# the correctness control for each stated matmul precision, the nearest
# below it: "high" (three bf16 passes) below "highest"; below the default
# (one bf16 pass: bf16 operands, fp32 sums) fp8 operands, which the
# serving mode gives the reference put in the service's place
CONTROL_PRECISION = {"highest": "high", "default": "float8_e4m3fn"}


def control_precision(spec: dict) -> str:
    return CONTROL_PRECISION[spec["matmul_precision"]]


def refuse(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 3


def main(argv=None, *, require_chip: bool = True, overrides=None,
         t_proc: float = None, compile_cache: bool = True) -> int:
    """``require_chip=False``, ``overrides`` (``spec``, ``traffic``,
    ``limits`` dicts merged into the resolved files) and
    ``compile_cache=False`` exist for the CPU tests, which drive a small
    run end to end inside the test process."""
    _paths()
    a = parse(argv)
    from bench.core.registry import Resolved, benchmark
    res = Resolved(benchmark(ROOT), a.workload, ROOT)
    for key, val in (overrides or {}).items():
        getattr(res, key).update(val)

    import jax
    devices = jax.devices()
    if require_chip:
        if not devices or devices[0].platform != "tpu":
            found = devices[0].platform if devices else "no device"
            return refuse(f"needs a TPU, JAX found {found}")
        if len(devices) < int(res.workload["chips"]):
            return refuse(f"{a.workload} needs {res.workload['chips']} "
                          f"chips, JAX found {len(devices)}")
        from bench.core.peaks import peaks
        peaks(devices[0].device_kind)       # unknown kinds are an error
    if compile_cache:
        use_compile_cache(ROOT)

    from bench.core.cell import Cell, emit
    from bench.core.compiles import CompileLog
    cell = Cell(res, seed=a.seed, seconds=a.seconds, trace=bool(a.trace),
                control=control_precision(res.spec) if a.control else None,
                t_proc=T_PROC if t_proc is None
                else t_proc, compile_log=CompileLog().install(),
                devices=devices)
    cell.mark("jax_init")
    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision",
                      res.spec["matmul_precision"])
    try:
        res.mode.run(cell)
    finally:
        jax.config.update("jax_default_matmul_precision", prev)
    cell.finish_trace()

    metrics = {}
    if a.trace:
        for m in res.per_layer():
            v = res.reader(m["name"]).read(cell)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in res.end_to_end():
            v = cell.setup_s if m["name"] == "setup_s" \
                else cell.e2e[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    emit(cell.setup_line())
    for k, v in cell.notes.items():
        print(f"note {k} {v}", file=sys.stderr)
    cell.print_checks()
    emit(cell.result(metrics))
    return 0


if __name__ == "__main__":
    sys.exit(main())
