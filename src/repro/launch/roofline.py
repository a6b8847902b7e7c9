"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch x shape) on the single-pod mesh, derive the three roofline terms
from the compiled per-device program:

  compute term    = HLO_FLOPs_per_device / peak FLOP/s            [s]
  memory term     = HLO_bytes_per_device / peak HBM bytes/s       [s]
  collective term = collective_bytes_per_device / link bytes/s    [s]

The peaks come from ``PEAKS``, one table keyed by jax's ``device_kind``;
a device that is not in it is an error, never a default. The dry run
targets the v5e production mesh, so ``DRYRUN_DEVICE_KIND`` names that
entry. cost_analysis() is taken from the SPMD-partitioned (per-device)
module, so all three terms are per-device quantities; MODEL_FLOPS is
scaled to per-device for the usefulness ratio.

Usage:  PYTHONPATH=src python -m repro.launch.roofline --runs runs/dryrun
        (writes a markdown table to stdout + runs/roofline.json)
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re

# Per-chip peaks keyed by ``jax.devices()[0].device_kind``.
# TPU v5e — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip interconnect over 4 links
# (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}
DRYRUN_DEVICE_KIND = "TPU v5 lite"
CHIPS = 256              # single-pod mesh


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates recorded for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None

_COUNT_CACHE = {}


def param_counts(arch: str):
    """(total_params, active_params) for one particle (MoE: top-k active)."""
    if arch in _COUNT_CACHE:
        return _COUNT_CACHE[arch]
    import jax
    from .. import configs
    from ..models import api
    from ..sharding import rules
    cfg = configs.get(arch)
    tree = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0), cfg))
    total = active = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        p = rules.normalize_path(path)
        if re.search(r"moe/(wi|wg|wo)$", p) and cfg.n_experts:
            active += n * cfg.top_k // cfg.n_experts
        else:
            active += n
    _COUNT_CACHE[arch] = (total, active)
    return total, active


def model_flops(rec, shapes):
    """Theoretical useful FLOPs per device: 6*N_active*tokens (train),
    2*N_active*tokens (prefill), 2*N_active*B (decode), x particles."""
    total, active = param_counts(rec["arch"])
    shp = shapes[rec["shape"]]
    P = rec.get("particles", 1)
    if shp.kind == "train":
        f = 6 * active * shp.global_batch * shp.seq_len
    elif shp.kind == "prefill":
        f = 2 * active * shp.global_batch * shp.seq_len
    else:
        f = 2 * active * shp.global_batch
    return f * P / CHIPS


def analyze(runs_dir: str, mesh: str = "single"):
    from ..configs import INPUT_SHAPES
    pk = peaks(DRYRUN_DEVICE_KIND)
    rows = []
    for f in sorted(glob.glob(os.path.join(runs_dir, f"*__{mesh}.json"))):
        r = json.load(open(f))
        if r["status"] != "ok":
            rows.append({**r, "dominant": "-"})
            continue
        coll = sum(r["collective_bytes_per_device"].values())
        t_c = r["flops_per_device"] / pk["flops"]
        t_m = r["bytes_per_device"] / pk["hbm_bw"]
        t_n = coll / pk["link_bw"]
        dom = max(("compute", t_c), ("memory", t_m), ("collective", t_n),
                  key=lambda kv: kv[1])[0]
        mf = model_flops(r, INPUT_SHAPES)
        rows.append({
            "arch": r["arch"], "shape": r["shape"], "status": "ok",
            "particles": r["particles"], "mode": r["mode"],
            "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_n,
            "dominant": dom,
            "model_flops_per_device": mf,
            "useful_ratio": mf / max(r["flops_per_device"], 1.0),
            "hbm_args_gb": (r.get("memory") or {}).get(
                "argument_size_in_bytes", 0) / 1e9,
            "hbm_temp_gb": (r.get("memory") or {}).get(
                "temp_size_in_bytes", 0) / 1e9,
            "collectives_gb": {k: v / 1e9 for k, v in
                               r["collective_bytes_per_device"].items()},
        })
    return rows


NOTES = {
    "compute": "at the compute roofline — push MFU via tiling/fusion, or cut "
               "redundant FLOPs (causal block pruning, less remat recompute)",
    "memory": "HBM-bound — raise arithmetic intensity (fuse elementwise "
              "chains, wider tiles, bf16 activations)",
    "collective": "ICI-bound — reshard to cut all-gathers, overlap "
                  "collectives with compute, or change the parallelism axis",
}


def markdown(rows):
    out = ["| arch | shape | P | mode | compute s | memory s | collective s | "
           "dominant | useful FLOP ratio | note |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if r.get("status") != "ok":
            out.append(f"| {r['arch']} | {r['shape']} | - | - | - | - | - | "
                       f"skip | - | {r.get('reason', r.get('error', ''))[:60]} |")
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['particles']} | {r['mode']} "
            f"| {r['t_compute_s']:.3f} | {r['t_memory_s']:.3f} "
            f"| {r['t_collective_s']:.3f} | **{r['dominant']}** "
            f"| {r['useful_ratio']:.2f} | {NOTES[r['dominant']]} |")
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", default="runs/dryrun")
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--json-out", default="runs/roofline.json")
    a = ap.parse_args()
    rows = analyze(a.runs, a.mesh)
    print(markdown(rows))
    with open(a.json_out, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
