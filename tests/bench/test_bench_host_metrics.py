"""The readers of the program's own spans (host time per decode step,
replays, compiles in the window, the fused call's edges) and the two
notes on a traced window (the device clock's lead, device time by
scope): on hand-built cells, on the small recorded TPU trace, and in a
traced run of each cell on the CPU."""
import json
import os
import sys
import types

import pytest

from benchlib import ROOT, SMALL, run_small

from bench.core import device_notes, xtrace
from bench.core.registry import Resolved, benchmark

DATA = os.path.join(ROOT, "bench", "data")
NEW = {"qwen1.5-0.5b.chat-steady": ["step_host_ms.chat"],
       "qwen1.5-0.5b.gen-backlog": ["step_host_ms.backlog",
                                    "replay_share.backlog",
                                    "window_compile_s.backlog"],
       "vit-mnist.train-ensemble": ["call_overhead_ms.train"]}


def _cell(spans, t0=10.0, t1=20.0, **layer):
    return types.SimpleNamespace(
        layer=dict(layer, program_spans=spans), t_w0=t0, t_w1=t1,
        window_s=t1 - t0, notes={}, reduced=None)


def _read(name, cell):
    return Resolved.reader(name).read(cell)


def test_step_host_ms_reads_pack_dispatch_and_emit_per_step():
    spans = [("decode.step", 9.0, 11.0), ("decode.pack", 9.5, 10.5),
             ("decode.dispatch", 10.5, 10.6), ("decode.sync", 10.6, 11.0),
             ("decode.emit", 11.0, 11.2), ("decode.admit", 11.2, 11.3),
             ("decode.pack", 19.9, 20.5)]
    cell = _cell(spans, steps=2)
    # pack clipped to the window: 0.5 + 0.1; dispatch 0.1; emit 0.2
    for name in ("step_host_ms.chat", "step_host_ms.backlog"):
        assert _read(name, cell) == pytest.approx(1e3 * 0.9 / 2)
    assert cell.notes["sync_ms_per_step"] == pytest.approx(1e3 * 0.4 / 2)
    assert _read("step_host_ms.backlog", _cell([("decode.step", 10, 11)],
                                               steps=2)) is None
    assert _read("step_host_ms.backlog", _cell(spans, steps=0)) is None
    assert _read("step_host_ms.backlog", types.SimpleNamespace(
        layer={"steps": 3}, reduced=None)) is None


def test_replay_share_reads_replay_spans_over_the_window():
    spans = [("decode.emit", 11.0, 12.0), ("decode.replay", 11.0, 11.5),
             ("decode.replay", 19.5, 21.0)]
    cell = _cell(spans)
    assert _read("replay_share.backlog", cell) == pytest.approx(
        100.0 * 1.0 / 10.0)
    assert cell.notes["replays_in_window"] == 2
    assert _read("replay_share.backlog", _cell(
        [("decode.emit", 11.0, 12.0)])) == 0.0
    # a program that records no scheduler phases records no replays
    assert _read("replay_share.backlog", _cell(
        [("decode.step", 11.0, 12.0), ("decode.admit", 11.0, 11.0)])) \
        is None
    assert _read("replay_share.backlog", _cell([])) is None


def test_window_compile_s_is_the_union_of_compile_spans(monkeypatch):
    from repro.core import PushDistribution  # noqa: F401  (the program)
    assert "repro.runtime.compiles" in sys.modules
    spans = [("runtime.compile", 8.0, 11.0), ("runtime.compile", 10.5, 12.0),
             ("runtime.compile", 15.0, 15.5), ("program.x", 0.0, 30.0)]
    assert _read("window_compile_s.backlog", _cell(spans)) == \
        pytest.approx(2.0 + 0.5)
    assert _read("window_compile_s.backlog", _cell([])) == 0.0
    assert _read("window_compile_s.backlog", types.SimpleNamespace(
        layer={}, reduced=None)) is None
    # a program without the compile listener
    monkeypatch.delitem(sys.modules, "repro.runtime.compiles")
    assert _read("window_compile_s.backlog", _cell(spans)) is None


def test_call_overhead_is_the_call_less_its_epochs_and_device_wait():
    spans = [("bdl.fused_call", 11.0, 15.0), ("bdl.epoch", 11.1, 11.5),
             ("bdl.device_wait", 11.5, 14.9), ("bdl.loss_sync", 14.9, 14.95),
             ("bdl.fused_call", 15.0, 16.0), ("bdl.epoch", 15.2, 15.9),
             ("bdl.fused_call", 19.0, 21.0)]          # not inside the window
    cell = _cell(spans)
    want = ((4.0 - 0.4 - 3.4) + (1.0 - 0.7)) / 2
    assert _read("call_overhead_ms.train", cell) == pytest.approx(1e3 * want)
    assert cell.notes["loss_sync_ms_per_call"] == pytest.approx(25.0)
    assert cell.notes["fused_calls_in_window"] == 2
    assert _read("call_overhead_ms.train", _cell(
        [("bdl.epoch", 11.0, 12.0)])) is None
    assert _read("call_overhead_ms.train", _cell([])) is None


def test_clock_lead_pairs_each_gap_with_the_nearest_dispatch_end():
    ops = [("a", 0, 100, ""), ("b", 1_000_000, 1_100_000, ""),
           ("c", 1_100_050, 1_200_000, ""),            # gap < MIN_GAP
           ("d", 3_000_000, 3_500_000, "")]
    dispatches = [("repro.program.x", 500_000, 2_300_000),
                  ("repro.program.x", 2_500_000, 4_200_000),
                  ("repro.program.x", 9_000_000, 9_100_000)]
    # gap ends 1.0 ms (dispatch end 2.3 ms) and 3.0 ms (4.2 ms)
    assert device_notes.clock_lead_ns(ops, dispatches) == \
        pytest.approx((1.3e6 + 1.2e6) / 2)
    assert device_notes.clock_lead_ns(ops, dispatches[2:]) is None
    assert device_notes.clock_lead_ns(ops, []) is None


def test_clock_lead_max_pairs_each_wait_with_the_gap_it_ends_in():
    ops = [("a", 0, 1_000_000, ""), ("b", 3_000_000, 4_000_000, ""),
           ("c", 4_000_050, 4_100_000, ""), ("d", 9_000_000, 9_100_000, "")]
    waits = [("decode.sync", 200_000, 2_500_000),   # gap from 1.0 ms
             ("decode.sync", 3_200_000, 5_900_000),  # gap from 4.1 ms
             ("decode.sync", 9_200_000, 9_300_000),  # ends in no gap
             ("decode.sync", 100, 500)]              # before any gap
    assert device_notes.clock_lead_max_ns(ops, waits) == \
        pytest.approx((1.5e6 + 1.8e6) / 2)
    assert device_notes.clock_lead_max_ns(ops, waits[2:]) is None


def test_clock_lead_on_the_recorded_tpu_trace():
    """The recorded rounds are launched by plain jitted calls (the
    ``PjitFunction`` host events) and waited for with
    ``block_until_ready``: each round's first op sits before the call
    that launched it has returned, and its last op ends before the wait
    returns, by more (the wait's side is an upper bound)."""
    devices, host = xtrace.load(os.path.join(DATA, "small_trace.xplane.pb"))
    ops = devices["/device:TPU:0"]
    calls = [h for h in host if h[0].startswith("PjitFunction(")]
    waits = [h for h in host if "block_until_ready" in h[0]]
    lo = device_notes.clock_lead_ns(ops, calls)
    hi = device_notes.clock_lead_max_ns(ops, waits)
    assert lo is not None and hi is not None
    assert 0.2e6 < lo < hi < 3e6


def test_scope_seconds_take_the_outermost_push_scope():
    ops = [("%fusion.1 = ..", 0, 100,
            "jit(step)/jit(main)/vmap(push.attention)/dot_general"),
           ("%while.2 = ..", 100, 400,
            "jit(step)/while/body/push.mlp/push.attention/add"),
           ("%fusion.3 = ..", 150, 250, "jit(step)/push.lm_head/dot"),
           ("%copy.4 = ..", 400, 450, "")]
    got = device_notes.scope_seconds(ops, 0, 1000)
    assert got == pytest.approx({"push.attention": 100e-9,
                                 "push.mlp": 200e-9, "push.lm_head": 100e-9,
                                 "unscoped": 50e-9})
    cell = types.SimpleNamespace(notes={}, reduced=xtrace.Reduced(
        {"/device:TPU:0": ops}, [("repro.program.s", 0, 10)], 0, 1000))
    device_notes.note(cell)
    assert cell.notes["device_s_by_scope"]["push.mlp"] == \
        pytest.approx(200e-9)
    assert "device_clock_lead_ms" not in cell.notes    # no gap to pair
    # details without a scope (what the profiler's event stats carry on a
    # TPU today) give no scope note
    bare = types.SimpleNamespace(notes={}, reduced=xtrace.Reduced(
        {"/device:TPU:0": [o[:3] + ("",) for o in ops]}, [], 0, 1000))
    device_notes.note(bare)
    assert "device_s_by_scope" not in bare.notes


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_traced_run_reports_the_span_metrics(workload, capsys, monkeypatch,
                                             tmp_path):
    """A traced run of each cell on the CPU (no device plane, so the
    device metrics stay silent) reads the program's own spans. The
    model-step readers need peak rates: the CPU borrows the chip's. The
    profiler writes under the test's own directory."""
    from bench.core import cell, peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(cell, "TRACE_DIR", str(tmp_path / "trace"))
    rc, out = run_small(workload, capsys, trace="1")
    assert rc == 0
    last = json.loads(out[-1])
    r = Resolved(benchmark(ROOT), workload, ROOT)
    assert {m["name"] for m in r.per_layer()} >= set(NEW[workload])
    for name in NEW[workload]:
        assert name in last["metrics"], name
        assert last["metrics"][name]["value"] >= 0
    if workload != "qwen1.5-0.5b.gen-backlog":
        assert last["metrics"][NEW[workload][0]]["value"] > 0
