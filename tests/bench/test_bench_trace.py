"""The reduction from a profiler trace to device metrics: against
hand-built event lists, and against a small trace recorded on a TPU v5e
chip by ``bench/tools/record_trace.py``."""
import json
import os

import pytest

from benchlib import ROOT

from bench.core import xtrace

DATA = os.path.join(ROOT, "bench", "data")


def test_union_busy_and_gaps():
    ops = [(0, 10), (5, 20), (30, 40), (40, 45), (100, 120)]
    assert xtrace.union(ops) == [(0, 20), (30, 45), (100, 120)]
    assert xtrace.busy_ns(ops, 0, 200) == 55
    assert xtrace.busy_ns(ops, 10, 35) == 15
    assert xtrace.gaps(ops, 0, 200) == [(20, 30), (45, 100), (120, 200)]
    assert xtrace.gaps(ops, 35, 110) == [(45, 100)]


def test_self_time_counts_a_loop_and_its_body_once():
    ops = [("%while.3 = (..) while(..)", 0, 100, ""),
           ("%fusion.1 = f32[2] fusion(..)", 10, 40, ""),
           ("%paged_decode_attention.6 = f32[..] custom-call(..)", 50, 80,
            ""),
           ("%copy.2 = f32[4] copy(..)", 150, 160, "")]
    st = xtrace.self_times(ops, 0, 200)
    assert st == {"while.3": 40, "fusion.1": 30,
                  "paged_decode_attention.6": 30, "copy.2": 10}
    assert sum(st.values()) == xtrace.busy_ns([(o[1], o[2]) for o in ops],
                                              0, 200)
    assert xtrace.matching(ops, "paged_decode_attention", 0, 200) == (30, 1)


def test_exposed_collective_time():
    ops = [("%all-gather-start.1 = ..", 0, 50, ""),
           ("%fusion.2 = ..", 10, 30, ""),
           ("%all-reduce.3 = ..", 60, 70, ""),
           ("%fusion.4 = ..", 65, 90, "")]
    coll, exposed = xtrace.exposed_ns(ops, 0, 100)
    assert coll == 60
    assert exposed == 30 + 5


def test_gaps_take_the_innermost_open_host_span():
    idle = [(10, 20), (50, 60), (200, 210)]
    spans = [("decode.step", 0, 100), ("decode.prefill", 45, 70)]
    got = xtrace.attribute(idle, spans)
    assert got == {"decode.step": 10, "decode.prefill": 10,
                   "no host span": 10}


def test_recorded_tpu_trace():
    path = os.path.join(DATA, "small_trace.xplane.pb")
    with open(os.path.join(DATA, "small_trace.json")) as f:
        meta = json.load(f)
    assert os.path.getsize(path) < 512 * 1024
    devices, host = xtrace.load(path)
    assert list(devices) == ["/device:TPU:0"]
    a = xtrace.anchor_ns(host)
    assert a is not None
    t1 = a + meta["window_end_s"] * 1e9
    spans = [(n, s, e) for n, s, e in host if n == "host.sleep"]
    assert len(spans) == 3
    # the device's events sit about 1.3 ms early against the host's on
    # the profiler's clock (the first round's ops read -0.3 ms after the
    # anchor); a window of seconds does not feel it, this one opens 5 ms
    # before the anchor
    r = xtrace.Reduced(devices, host, a - 5e6, t1, spans)
    # the three rounds ran the matmul and the paged kernel on the device
    k_s, n = r.op_seconds("paged_decode_attention")
    assert n == 3 and 0 < k_s < r.window_s
    busy = r.busy_s()
    assert 0 < busy < r.window_s
    # the device idled through each 30 ms sleep, and those gaps are
    # named by the annotation the host had open at their midpoints (each
    # gap also holds the host's dispatch of the next round, ~1 ms)
    by_span = dict(r.idle_by_span())
    slept = sum(b - s for s, b in meta["sleeps_s"])
    assert slept <= by_span["host.sleep"] <= 1.1 * slept
    assert max(by_span, key=by_span.get) == "host.sleep"
    assert busy + sum(by_span.values()) == pytest.approx(r.window_s,
                                                         rel=1e-6)
    # spans on the harness's clock land on the profiler's clock through
    # the anchor
    for (s, e), (_, hs, he) in zip(meta["sleeps_s"], sorted(spans,
                                                            key=lambda x:
                                                            x[1])):
        assert abs((a + s * 1e9) - hs) < 2e6
