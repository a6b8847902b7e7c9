"""The training cell's comparison catches what it has to: the control
(the program one matmul precision below the configuration's) and the
timed path broken underneath. Each run goes through the harness's entry
on the CPU with the chip check skipped, at a small width.

The faults are held to the cell's own limits. The control's gaps scale
with the model, so at this width it is held to a limit set the way the
cell's are, from this width's readings: grad_gap reads at most 3.8e-7
sound and at least 7.9e-6 under the emulated control (two seeds each),
and the limit is 2e-6. A sound run must pass the same limit."""
import json

import pytest

from benchlib import emulate_high, run_small

from bench.core import faults

W = "vit-mnist.train-ensemble"


def _last(out):
    return json.loads(out[-1])


SMALL_LIMITS = {"grad_gap": 2e-6}


@pytest.mark.parametrize("control", [True, False])
def test_control_is_caught(control, capsys, monkeypatch):
    emulate_high(monkeypatch)
    rc, out = run_small(W, capsys, *(["--control"] if control else []),
                        limits=SMALL_LIMITS)
    last = _last(out)
    assert rc == 0 and last["correct"] is (not control)
    assert (last["checks"]["grad_gap"]["value"] > 2e-6) is control


def test_state_left_unchanged_is_caught(capsys, monkeypatch):
    faults.train_state_unchanged(monkeypatch.setattr)
    rc, out = run_small(W, capsys)
    last = _last(out)
    assert rc == 0 and last["correct"] is False
    assert last["checks"]["step_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_is_caught(capsys, monkeypatch):
    faults.train_half_batch(monkeypatch.setattr)
    rc, out = run_small(W, capsys)
    last = _last(out)
    assert rc == 0 and last["correct"] is False
    assert last["checks"]["loss_gap"]["value"] > \
        last["checks"]["loss_gap"]["limit"]
