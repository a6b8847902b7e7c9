#!/usr/bin/env python3
"""Read a cell's correctness numbers over many seeds in one process: the
readings its limits are set from.

    python3 bench/tools/calibrate.py --workload vit-mnist.train-ensemble \\
        --seconds 0 --seeds 101,102,... [--control-seeds 201,202,203] \
        [--fault train_half_batch --fault-seeds 301,302,303]

Each seed is a whole run of the cell's mode (weights from the seed,
set-up, a window of ``--seconds`` at the cell's own load, the reference
after it), in one process so that every seed after the first finds its
programs compiled. ``--control-seeds`` then runs the correctness control
(one matmul precision below the configuration's) on those seeds, and
``--fault-seeds`` runs with a fault of ``bench/core/faults.py`` planted.
One JSON line per seed: the numbers compared, the limits they are held
to, and whether the run came out correct.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default=None)
    ap.add_argument("--fault-seeds", default="")
    a = ap.parse_args(argv)
    import jax
    from bench import run as bench_run
    from bench.core import faults
    from bench.core.cell import Cell
    from bench.core.compiles import CompileLog
    from bench.core.registry import Resolved, benchmark
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return bench_run.refuse("needs a TPU")
    bench_run.use_compile_cache(ROOT)
    log = CompileLog().install()
    res = Resolved(benchmark(ROOT), a.workload, ROOT)
    runs = [(int(s), False, None) for s in a.seeds.split(",") if s] + \
        [(int(s), True, None) for s in a.control_seeds.split(",") if s] + \
        [(int(s), False, a.fault) for s in a.fault_seeds.split(",") if s]
    for seed, control, fault in runs:
        t0 = time.perf_counter()
        patches = faults.Patches()
        if fault:
            faults.FAULTS[fault](patches.setattr)
        cell = Cell(res, seed=seed, seconds=a.seconds, trace=False,
                    control=bench_run.control_precision(res.spec)
                    if control else None, t_proc=t0, compile_log=log,
                    devices=devices)
        jax.config.update("jax_default_matmul_precision",
                          res.spec["matmul_precision"])
        try:
            res.mode.run(cell)
            out = {"seed": seed, "control": control, "fault": fault,
                   "correct": cell.correct, "failed": cell.failed,
                   "checks": {n: [v, lim] for n, v, lim in cell.checks},
                   "notes": cell.notes, "e2e": cell.e2e,
                   "seconds": time.perf_counter() - t0}
        except Exception as e:          # a control that crashes has failed
            out = {"seed": seed, "control": control, "fault": fault,
                   "error": repr(e)[:400]}
        patches.undo()
        print(json.dumps(out), flush=True)
        del cell
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
