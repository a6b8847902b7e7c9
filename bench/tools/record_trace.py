#!/usr/bin/env python3
"""Record the small TPU trace that the trace reduction's CPU test reads
(``bench/data/small_trace.xplane.pb``).

    python3 bench/tools/record_trace.py [--out DIR]

On one TPU chip: three rounds of a jitted matmul followed by the Pallas
paged decode kernel at a small shape, each round then a 30 ms host sleep
inside a ``host.sleep`` annotation, after the harness's anchor event.
What the test may expect is printed as JSON (sleep lengths on the
harness's clock) and written beside the trace.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

SLEEP_S = 0.03


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(ROOT, "bench", "data"),
                    help="directory for the trace and its JSON")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from bench.core import xtrace
    from repro.kernels import ops
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 3
    B, H, hd, NP, PS, NPMAX = 4, 16, 64, 8, 16, 2
    k = jax.random.normal(jax.random.PRNGKey(0), (NP, PS, H, hd))
    q = jax.random.normal(jax.random.PRNGKey(1), (B, 1, H, hd))
    bt = jnp.arange(B * NPMAX, dtype=jnp.int32).reshape(B, NPMAX) % NP
    sl = jnp.array([20, 5, -1, 31], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(2), (512, 512))
    mm = jax.jit(lambda a: a @ a)

    def round_():
        jax.block_until_ready(mm(x))
        jax.block_until_ready(ops.paged_decode_attention(q, k, k, bt, sl))

    round_()
    out = os.path.join(ROOT, ".bench_trace_record")
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out)
    anchor = time.perf_counter()
    with jax.profiler.TraceAnnotation(xtrace.ANCHOR):
        pass
    sleeps = []
    for _ in range(3):
        round_()
        with jax.profiler.TraceAnnotation("host.sleep"):
            a = time.perf_counter()
            time.sleep(SLEEP_S)
            sleeps.append([a - anchor, time.perf_counter() - anchor])
    end = time.perf_counter() - anchor
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    os.makedirs(args.out, exist_ok=True)
    dst = os.path.join(args.out, "small_trace.xplane.pb")
    shutil.copy(path, dst)
    shutil.rmtree(out, ignore_errors=True)
    meta = {"sleeps_s": sleeps, "window_end_s": end,
            "device_kind": jax.devices()[0].device_kind}
    with open(os.path.splitext(os.path.splitext(dst)[0])[0] + ".json",
              "w") as f:
        json.dump(meta, f, indent=1)
    print(json.dumps(meta), os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
