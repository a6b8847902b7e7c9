"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Container-scale sizes (single CPU
core); EXPERIMENTS.md maps each section to the paper artifact and explains
which trends are wall-clock-faithful vs structurally validated.

When the scaling section runs, its rows (particles x emulated-device
throughput for every backend that ran) are also written to
``BENCH_scaling.json`` so the perf trajectory is tracked across PRs; CI's
sharded matrix job runs it under 4 forced host devices with
``--scaling-backend compiled-sharded``.

Serve rows land in ``BENCH_serve.json`` the same way (micro-batched vs
one-request-at-a-time throughput, fused-call latency across batch sizes
and particle counts); CI enforces the >= 3x micro-batching bar via
``bench_serve --require``.

Decode rows land in ``BENCH_decode.json`` (continuous-batching vs
flush-batched tokens/sec, retirement latency percentiles, page-pool
occupancy, plus speculative-vs-plain tok/s with acceptance rate and
cold-compile delta); CI enforces the >= 2x continuous-batching bar via
``bench_decode --require`` and the >= 1.3x speculative bar via
``--require-spec``.

Compile rows land in ``BENCH_runtime.json`` (cold-compile counts and
ProgramCache hit rate across the train -> serve lifecycle); CI enforces
a minimum hit rate via ``bench_compile --require-hit-rate``.

  bench_scaling          Fig. 4 / Fig. 7  (particles x algorithms x devices)
  bench_depth_particles  Table 1          (depth vs particle tradeoff)
  bench_stress           Table 2 / C.3    (particle-cache oversubscription)
  bench_accuracy         Tables 3-4       (multi-SWAG vs standard accuracy)
  bench_kernels          (ours)           Pallas kernels + SVGD impls
  bench_dispatch         (ours)           event-loop vs thread-per-dispatch
  bench_serve            (ours)           posterior-predictive serving layer
  bench_compile          (ours)           ProgramCache compile economics
  bench_lifecycle        (ours)           elastic churn: ops/sec, recompiles,
                                          serve latency under clone/kill
  bench_decode           (ours)           continuous-batching paged decode vs
                                          flush-batched (tok/s, p99, pages)
                                          + speculative BMA decode vs plain
                                          (tok/s, acceptance, cold compiles)
  bench_obs              (ours)           tracing overhead on the dispatch
                                          hot path (CI gates: disabled <=1%,
                                          enabled <=5%)
  bench_precision        (ours)           mixed-precision particles: HBM per
                                          particle, remat-policy temp bytes,
                                          serve latency (CI gate: fp32/bf16
                                          params+opt bytes >= 1.8x)

Obs rows land in ``BENCH_obs.json``; every BENCH_*.json additionally
carries an ``obs`` context block (tracer state + per-program
FLOPs/bytes cost attribution from the global ProgramCache).
"""
import argparse
import functools
import json
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. kernels,stress")
    ap.add_argument("--scaling-backend", default="nel",
                    choices=("nel", "compiled", "compiled-sharded"),
                    help="backend column set for the scaling section")
    ap.add_argument("--scaling-model", type=int, default=1,
                    help="model-axis size for the compiled-sharded scaling "
                         "rows (2D particle x model placement)")
    ap.add_argument("--scaling-json", default="BENCH_scaling.json",
                    help="where to persist the scaling rows")
    ap.add_argument("--serve-json", default="BENCH_serve.json",
                    help="where to persist the serving rows")
    ap.add_argument("--runtime-json", default="BENCH_runtime.json",
                    help="where to persist the compile/cache rows")
    ap.add_argument("--lifecycle-json", default="BENCH_lifecycle.json",
                    help="where to persist the churn rows")
    ap.add_argument("--decode-json", default="BENCH_decode.json",
                    help="where to persist the decode rows")
    ap.add_argument("--obs-json", default="BENCH_obs.json",
                    help="where to persist the tracing-overhead rows")
    ap.add_argument("--precision-json", default="BENCH_precision.json",
                    help="where to persist the mixed-precision rows")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from . import (bench_accuracy, bench_compile, bench_decode,
                   bench_depth_particles, bench_dispatch, bench_kernels,
                   bench_lifecycle, bench_obs, bench_precision,
                   bench_scaling, bench_serve, bench_stress, util)
    table = {
        "scaling": functools.partial(bench_scaling.run,
                                     backend=args.scaling_backend,
                                     model=args.scaling_model),
        "depth_particles": bench_depth_particles.run,
        "stress": bench_stress.run,
        "accuracy": bench_accuracy.run,
        "kernels": bench_kernels.run,
        "dispatch": bench_dispatch.run,
        "serve": bench_serve.run,
        "compile": bench_compile.run,
        "lifecycle": bench_lifecycle.run,
        "decode": functools.partial(bench_decode.run, speculative=True),
        "obs": bench_obs.run,
        "precision": bench_precision.run,
    }
    only = set(args.only.split(",")) if args.only else set(table)
    print("name,us_per_call,derived")
    for name, fn in table.items():
        if name in only:
            print(f"# --- {name} ---", flush=True)
            fn()
    if "scaling" in only:
        rows = [r for r in util.ROWS if r["name"].startswith("scaling/")]
        with open(args.scaling_json, "w") as f:
            json.dump({**util.device_info(),
                       "backend": args.scaling_backend,
                       "model_axis": args.scaling_model,
                       "rows": rows, "obs": util.obs_context()}, f, indent=1)
        print(f"# wrote {len(rows)} scaling rows -> {args.scaling_json}",
              flush=True)
    if "serve" in only:
        rows = [r for r in util.ROWS if r["name"].startswith("serve/")]
        with open(args.serve_json, "w") as f:
            json.dump({**util.device_info(), "rows": rows,
                       "obs": util.obs_context()}, f, indent=1)
        print(f"# wrote {len(rows)} serve rows -> {args.serve_json}",
              flush=True)
    if "compile" in only:
        from repro.runtime import global_cache
        rows = [r for r in util.ROWS if r["name"].startswith("compile/")]
        with open(args.runtime_json, "w") as f:
            json.dump({**util.device_info(),
                       "cache": global_cache().snapshot_stats(),
                       "rows": rows, "obs": util.obs_context()}, f, indent=1)
        print(f"# wrote {len(rows)} compile rows -> {args.runtime_json}",
              flush=True)
    if "lifecycle" in only:
        rows = [r for r in util.ROWS if r["name"].startswith("lifecycle/")]
        with open(args.lifecycle_json, "w") as f:
            json.dump({**util.device_info(), "rows": rows,
                       "obs": util.obs_context()}, f, indent=1)
        print(f"# wrote {len(rows)} lifecycle rows -> {args.lifecycle_json}",
              flush=True)
    if "decode" in only:
        rows = [r for r in util.ROWS if r["name"].startswith("decode/")]
        with open(args.decode_json, "w") as f:
            json.dump({**util.device_info(), "rows": rows,
                       "obs": util.obs_context()}, f, indent=1)
        print(f"# wrote {len(rows)} decode rows -> {args.decode_json}",
              flush=True)
    if "obs" in only:
        rows = [r for r in util.ROWS if r["name"].startswith("obs/")]
        with open(args.obs_json, "w") as f:
            json.dump({**util.device_info(), "rows": rows,
                       "obs": util.obs_context()}, f, indent=1)
        print(f"# wrote {len(rows)} obs rows -> {args.obs_json}",
              flush=True)
    if "precision" in only:
        rows = [r for r in util.ROWS if r["name"].startswith("precision/")]
        with open(args.precision_json, "w") as f:
            json.dump({**util.device_info(), "rows": rows,
                       "obs": util.obs_context()}, f, indent=1)
        print(f"# wrote {len(rows)} precision rows -> {args.precision_json}",
              flush=True)


if __name__ == '__main__':
    main()
