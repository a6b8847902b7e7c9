"""The benchmark harness: every cell resolves by name, traffic follows
the seed, a CPU is refused, and the result line keeps the contract."""
import json
import os
import re
import subprocess
import sys

import pytest

from benchlib import ROOT, SMALL, run_small

from bench.core import traffic
from bench.core.registry import Resolved, benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return benchmark(ROOT)


def test_benchmark_file_keeps_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][:2] == ["python3", "bench/run.py"]
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p)) and not p.startswith("/")
    assert 1 <= bench["run_seconds"] <= 51
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert m["moves"] in e2e and "\n" not in m["layer"]
        for w in m.get("workloads", []):
            cells = e2e[m["moves"]].get("workloads")
            assert cells is None or w in cells, (m["name"], w)
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 2)


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_every_cell_resolves(bench, workload):
    assert workload in {w["name"] for w in bench["workloads"]}
    r = Resolved(bench, workload, ROOT)
    assert os.path.isfile(r.reference_path) and os.path.isfile(r.mode_path)
    assert r.reference.init_params and r.mode.run
    assert r.limits["checks"]
    reported = {m["name"] for m in r.end_to_end()}
    assert "setup_s" in reported and len(reported) >= 2
    layer = r.per_layer()
    assert layer
    for m in layer:
        assert m["moves"] in reported
        assert callable(r.reader(m["name"]).read)


def test_a_quantity_reader_serves_every_cell_kind(bench):
    idle = [m["name"] for m in bench["per_layer"]
            if m["name"].startswith("device_idle.")]
    assert len(idle) >= 2
    assert len({id(Resolved.reader(n)) for n in idle}) == 1
    with pytest.raises(FileNotFoundError):
        Resolved.reader("no_such_metric.train")


def test_traffic_follows_the_seed(bench):
    for w in bench["workloads"]:
        r = Resolved(bench, w["name"], ROOT)
        if r.traffic["mode"] != "serve":
            continue
        vocab = r.spec["vocab_size"]
        a = traffic.requests(r.traffic, seed=2 ** 31 + 7, seconds=30,
                             vocab=vocab)
        b = traffic.requests(r.traffic, seed=2 ** 31 + 7, seconds=30,
                             vocab=vocab)
        c = traffic.requests(r.traffic, seed=2 ** 31 + 8, seconds=30,
                             vocab=vocab)
        key = [(q.due, q.prompt, q.max_new) for q in a]
        assert key == [(q.due, q.prompt, q.max_new) for q in b]
        assert key != [(q.due, q.prompt, q.max_new) for q in c]
        # another seed: the same work in another order
        assert sorted(len(q.prompt) for q in a) == \
            sorted(len(q.prompt) for q in c)
        assert sorted(q.max_new for q in a) == sorted(q.max_new for q in c)
        if r.traffic["loop"] == "open":
            assert len(a) == round(r.traffic["rate_per_s"] * 30)
            assert all(0 <= q.due < 30 for q in a)


def test_a_cpu_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "vit-mnist.train-ensemble", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_result_line_has_the_contract_keys(workload, capsys):
    rc, out = run_small(workload, capsys)
    assert rc == 0
    setup, last = json.loads(out[-2]), json.loads(out[-1])
    assert "setup" in setup and setup["setup"]["setup_s"] > 0
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert last["correct"] is True and last["attempted"] > 0
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    r = Resolved(benchmark(ROOT), workload, ROOT)
    assert set(last["metrics"]) == {m["name"] for m in r.end_to_end()}
    for v in last["metrics"].values():
        assert set(v) == {"value", "unit"} and v["value"] > 0
    assert set(last["checks"]) == set(r.limits["checks"])
