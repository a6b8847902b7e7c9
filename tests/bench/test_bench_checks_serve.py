"""The serving cells' comparison catches what it has to: the control
(the reference with fp8 matmul operands, one step below the stated bf16
operands, in the service's place), and the timed decode path broken
underneath — a token altered where it is produced, the BMA taken over
half of the particles, a step that leaves the KV pages unchanged. Each
run goes through the harness's entry on the CPU with the chip check
skipped, at a small width.

The faults are held to the cells' own limits. The control's gaps scale
with the model, so at this width it is held to a limit set the way the
cells' are, from this width's readings: logprob_gap reads 4.8e-7 sound
(the CPU's matmuls are exact fp32) and at least 0.056 under the control
(both cells), and the limit is 1.5e-6. A sound run must pass the same
limit."""
import json

import pytest

from benchlib import run_small

from bench.core import faults

CELLS = ["qwen1.5-0.5b.chat-steady", "qwen1.5-0.5b.gen-backlog"]


def _incorrect(out):
    last = json.loads(out[-1])
    return last["correct"] is False


SMALL_LIMITS = {"logprob_gap": 1.5e-6}


@pytest.mark.parametrize("control", [True, False])
@pytest.mark.parametrize("workload", CELLS)
def test_control_is_caught(workload, control, capsys):
    rc, out = run_small(workload, capsys,
                        *(["--control"] if control else []),
                        limits=SMALL_LIMITS)
    last = json.loads(out[-1])
    assert rc == 0 and last["correct"] is (not control)


@pytest.mark.parametrize("fault", ["serve_altered_token",
                                   "serve_half_particles",
                                   "serve_pages_unchanged"])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_decode_is_caught(workload, fault, capsys, monkeypatch):
    faults.FAULTS[fault](monkeypatch.setattr)
    rc, out = run_small(workload, capsys)
    assert rc == 0 and _incorrect(out)
