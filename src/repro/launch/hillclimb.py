"""Hillclimb driver (EXPERIMENTS.md §Perf): measure the three roofline
terms for one (arch x shape) with optional plan overrides, and attribute
the top collectives to source ops.

Usage:
  PYTHONPATH=src python -m repro.launch.hillclimb --arch llama3-405b \
      --shape train_4k [--microbatches 8] [--top-collectives]
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse
import dataclasses
import json

import jax

from .. import configs as configs_mod
from ..configs import INPUT_SHAPES
from .mesh import make_production_mesh
from .plans import plan_for
from . import hlo_cost as hc
from . import steps as steps_mod
from .roofline import DRYRUN_DEVICE_KIND, peaks


def measure(arch: str, shape_name: str, *, microbatches=None, particles=None,
            top: bool = False, multi_pod: bool = False, bdl: str = "ensemble"):
    cfg = configs_mod.get(arch)
    shape = INPUT_SHAPES[shape_name]
    plan = plan_for(cfg, shape)
    if microbatches is not None:
        plan = dataclasses.replace(plan, microbatches=microbatches)
    if particles is not None:
        plan = dataclasses.replace(plan, particles=particles)
    mesh = make_production_mesh(multi_pod=multi_pod)
    donate = {"train": (0, 1), "decode": (2,), "prefill": ()}[shape.kind]
    if shape.kind == "train" and bdl == "svgd":
        donate = (0,)
    with jax.set_mesh(mesh):
        step, args, sh = steps_mod.build(cfg, shape, plan, mesh, bdl=bdl)
        c = jax.jit(step, in_shardings=sh,
                    donate_argnums=donate).lower(*args).compile()
    txt = c.as_text()
    cost = hc.cost(txt)
    coll = sum(cost["coll"].values())
    m = c.memory_analysis()
    pk = peaks(DRYRUN_DEVICE_KIND)
    rec = {
        "arch": arch, "shape": shape_name, "plan": dataclasses.asdict(plan),
        "t_compute_s": cost["flops"] / pk["flops"],
        "t_memory_s": cost["bytes"] / pk["hbm_bw"],
        "t_collective_s": coll / pk["link_bw"],
        "coll_tb": {k: round(v / 1e12, 3) for k, v in cost["coll"].items()},
        "hbm_temp_gb": m.temp_size_in_bytes / 1e9,
        "hbm_args_gb": m.argument_size_in_bytes / 1e9,
    }
    print(json.dumps({k: v for k, v in rec.items() if k != "plan"}, indent=1))
    if top:
        print("top collectives (bytes x trips):")
        for kind, tot, trips, b, name in hc.top_collectives(txt):
            print(f"  {kind:18s} {tot/1e12:7.2f}TB x{trips:6d} "
                  f"each {b/1e6:9.1f}MB  {name[:100]}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--particles", type=int, default=None)
    ap.add_argument("--top-collectives", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--bdl", default="ensemble")
    a = ap.parse_args()
    measure(a.arch, a.shape, microbatches=a.microbatches,
            particles=a.particles, top=a.top_collectives,
            multi_pod=a.multi_pod, bdl=a.bdl)


if __name__ == "__main__":
    main()
