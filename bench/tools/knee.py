#!/usr/bin/env python3
"""Sweep an open-loop serving cell's rate to find its knee: the highest
fixed rate whose queue does not grow over the window.

    python3 bench/tools/knee.py --workload qwen1.5-0.5b.chat-steady \\
        --seed 7 --seconds 30 --rates 1,2,3

One process: the service is built and warmed once, then each rate runs
one window of the traffic mix. For each rate it prints one JSON line:
requests sent, finished within ``drain_s`` of the window's end, the
waiting queue's depth sampled through the window (first and last
quarters), time to first token and gap between tokens. Run it on the
chip; the chosen rate goes into the traffic file by hand.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    a = ap.parse_args(argv)
    import jax
    from bench import run as bench_run
    from bench.core import traffic
    from bench.core.cell import Cell
    from bench.core.compiles import CompileLog
    from bench.core.registry import Resolved, benchmark
    res = Resolved(benchmark(ROOT), a.workload, ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return bench_run.refuse("needs a TPU")
    bench_run.use_compile_cache(ROOT)
    jax.config.update("jax_default_matmul_precision",
                      res.spec["matmul_precision"])
    mode = res.mode
    cell = Cell(res, seed=a.seed, seconds=a.seconds, trace=False,
                control=None, t_proc=T_PROC,
                compile_log=CompileLog().install(), devices=devices)
    pd, svc, log, keys = mode.build(cell)
    sched = svc.scheduler
    print(json.dumps({"setup_s": time.perf_counter() - T_PROC}), flush=True)
    for rate in (float(r) for r in a.rates.split(",")):
        tr = dict(res.traffic, rate_per_s=rate)
        cell.traffic = tr
        reqs = traffic.requests(tr, seed=a.seed, seconds=a.seconds,
                                vocab=int(res.spec["vocab_size"]))
        depth, stop = [], threading.Event()

        def sample():
            while not stop.is_set():
                depth.append(sched.queue_depth())
                time.sleep(0.25)

        th = threading.Thread(target=sample, daemon=True)
        th.start()
        recs = mode.open_loop(cell, svc, log, reqs)
        stop.set()
        th.join()
        n_win = max(1, int(a.seconds / 0.25))
        q = max(1, n_win // 4)
        ok = [r for r in recs if r.gen is not None]
        ttft = [(r.times[0] - r.due) * 1e3 for r in recs if r.times]
        itl = [(b - c) * 1e3 for r in recs for c, b in zip(r.times,
                                                           r.times[1:])]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(recs), "finished": len(ok),
            "queue_first_quarter": sum(depth[:q]) / q,
            "queue_last_quarter": sum(depth[n_win - q:n_win]) / q,
            "queue_max": max(depth[:n_win]) if depth else None,
            "ttft_p50_ms": mode.pct(ttft, 50),
            "ttft_p95_ms": mode.pct(ttft, 95),
            "itl_p50_ms": mode.pct(itl, 50), "itl_p95_ms": mode.pct(itl, 95),
            "steps": sched.snapshot_stats()["steps"]}), flush=True)
    svc.close()
    pd.cleanup()
    return 0


if __name__ == "__main__":
    sys.exit(main())
