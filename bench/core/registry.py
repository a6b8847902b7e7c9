"""Find everything a cell needs by the names in ``BENCHMARK.json``.

  configuration  the ``file`` of its entry (sizes), and beside it a
                 ``.py`` of the same stem: its plain reference, weights
                 from the seed, and how the program's config is built;
  traffic        ``bench/traffic/<traffic>.json``;
  mode           ``bench/modes/<mode>.py``, the mode named by the traffic
                 file (how a window drives the program);
  limits         ``bench/cells/<workload>.json``, the correctness limits
                 of one cell with the readings they were set from;
  metric reader  ``bench/metrics/<metric name>.py``, with ``read(cell)``
                 returning a number or None; where there is none, the
                 reader of the quantity, ``bench/metrics/<name up to its
                 first dot>.py``, which serves every cell kind alike
                 (``device_idle.py`` reads ``device_idle.train``, ...).

Adding a cell, a traffic mix, a mode or a metric adds files; no file that
is already here needs an edit.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

_MODULES = {}


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_module(path: str):
    """Import a file by its path (its name need not be an identifier)."""
    path = os.path.abspath(path)
    mod = _MODULES.get(path)
    if mod is None:
        if not os.path.isfile(path):
            raise FileNotFoundError(path)
        name = "bench_file_" + re.sub(r"\W", "_", os.path.relpath(path, ROOT))
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return mod


class Resolved:
    """Every file one workload of ``BENCHMARK.json`` resolves to."""

    def __init__(self, bench: dict, workload: str, root: str = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        self.bench = bench
        self.root = root
        self.workload = cells[workload]
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config_path = os.path.join(root, self.config_entry["file"])
        self.spec = load_json(self.config_path)
        self.reference_path = os.path.splitext(self.config_path)[0] + ".py"
        self.traffic_path = os.path.join(BENCH, "traffic",
                                         self.workload["traffic"] + ".json")
        self.traffic = load_json(self.traffic_path)
        self.mode_path = os.path.join(BENCH, "modes",
                                      self.traffic["mode"] + ".py")
        self.limits_path = os.path.join(BENCH, "cells", workload + ".json")
        self.limits = load_json(self.limits_path)

    @property
    def reference(self):
        return load_module(self.reference_path)

    @property
    def mode(self):
        return load_module(self.mode_path)

    def end_to_end(self):
        """End-to-end metrics this cell reports."""
        name = self.workload["name"]
        return [m for m in self.bench["end_to_end"]
                if name in m.get("workloads", [name])]

    def per_layer(self):
        """Per-layer metrics this cell reports: those that list it, or
        that list no cells and move an end-to-end metric it reports."""
        name = self.workload["name"]
        e2e = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.bench["per_layer"]:
            if "workloads" in m:
                if name in m["workloads"]:
                    out.append(m)
            elif m["moves"] in e2e:
                out.append(m)
        return out

    @staticmethod
    def reader(metric_name: str):
        d = os.path.join(BENCH, "metrics")
        own = os.path.join(d, metric_name + ".py")
        if os.path.isfile(own):
            return load_module(own)
        return load_module(os.path.join(d, metric_name.split(".")[0]
                                        + ".py"))
