"""Shared benchmark utilities: timing, CSV emission, tiny workloads.

All benchmarks print ``name,us_per_call,derived`` CSV rows (one per
measurement) so run.py can aggregate. Container-scale defaults: this box
has ONE physical CPU core — multi-"device" rows use forced host devices in
subprocesses, which exercises placement/communication code paths but NOT
real parallel speedup; EXPERIMENTS.md discusses how each paper trend is
validated structurally (collective bytes, dispatch counts) instead of by
wall clock where the wall clock cannot be faithful.
"""
from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import ParticleModule
from repro.models import api


def timeit(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall time per call in microseconds (blocks on jax results)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


# Every emitted row is also collected here so run.py can persist sections
# as JSON artifacts (BENCH_scaling.json) for perf-trajectory tracking.
ROWS: list = []


def emit(name: str, us: float, derived: str = ""):
    ROWS.append({"name": name, "us_per_call": round(us, 1),
                 "derived": derived})
    print(f"{name},{us:.1f},{derived}", flush=True)


def device_info() -> dict:
    """The device every BENCH_*.json names: platform, kind and count."""
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "devices": len(jax.devices())}


def obs_context() -> dict:
    """Observability context attached to every BENCH_*.json artifact:
    tracer state plus per-program cost attribution for whatever
    the global ProgramCache compiled during the run (compute=True pays
    one analysis compile per entry — fine post-benchmark, off any timed
    path)."""
    from repro.obs import summary
    from repro.runtime import global_cache
    return {"obs": summary(),
            "program_costs": global_cache().program_costs(compute=True)}


def tiny_module(arch: str = "vit-mnist", n_units: int = 2,
                d_model: int = 64) -> ParticleModule:
    cfg = configs.get(arch).smoke().replace(n_units=n_units, d_model=d_model,
                                            n_heads=4, n_kv_heads=4,
                                            head_dim=16, d_ff=128)
    return ParticleModule(
        init=lambda rng: api.init_params(rng, cfg),
        loss=lambda p, b: api.loss_fn(p, b, cfg),
        forward=lambda p, b: api.forward(p, b, cfg)[0],
        cfg=cfg)
