"""chip_smoke.py on the CPU: the platform guard refuses a CPU device, and
every phase runs at a tiny configuration with the Pallas kernels
interpreted (so no ``tpu_custom_call`` is compiled into any program)."""
import json
import os
import sys

import jax
import pytest

from repro import configs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


def _vit():
    return configs.get("vit-mnist").replace(
        d_model=32, n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, n_units=2)


def _qwen():
    return configs.get("qwen1.5-0.5b").replace(
        n_units=2, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        d_ff=64, vocab_size=128, max_seq_len=128)


def _phase_line(out: str, name: str) -> dict:
    for line in out.splitlines():
        if line.startswith("{") and json.loads(line).get("phase") == name:
            return json.loads(line)
    raise AssertionError(f"no line for phase {name!r} in:\n{out}")


def test_guard_refuses_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as e:
        chip_smoke.require_tpu(jax.devices())
    assert "needs a TPU" in str(e.value.code)
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_train_and_serve_phases_tiny(capsys):
    vit = _vit()
    trained = chip_smoke.run_phase(
        "train", chip_smoke.phase_train, vit, particles=3, epochs=2,
        batch_size=16, num_batches=6, swag_rank=2, svgd_lr=0.05, seed=0)
    assert type(trained).__name__ == "MultiSWAG"
    chip_smoke.run_phase("serve", chip_smoke.phase_serve, trained, vit,
                         requests=3, seed=0)
    out = capsys.readouterr().out
    for name in ("train", "serve"):
        line = _phase_line(out, name)
        assert line["tpu_custom_call"] == []      # interpreted on CPU
        assert line["program_cache"]["cold_compiles"] > 0
        assert line["compile_s"] > 0.0 and line["run_s"] >= 0.0
    assert "swag_moments" in _phase_line(out, "train")["programs"]
    assert "swag_sample" in _phase_line(out, "serve")["programs"]


def test_decode_phase_tiny(capsys):
    qwen = _qwen()
    prompts = chip_smoke._decode_prompts(qwen, seed=0, n=2)
    chip_smoke.run_phase(
        "decode", chip_smoke.phase_decode, qwen, particles=2, num_pages=16,
        page_size=8, max_seq_pages=4, max_active=2, prompts=prompts,
        max_new=4, seed=0)
    line = _phase_line(capsys.readouterr().out, "decode")
    assert {"paged_decode_step", "spec_verify"} <= set(line["programs"])


def test_sharded_phase_tiny(capsys):
    n = len(jax.devices())
    chip_smoke.run_phase(
        "sharded", chip_smoke.phase_sharded, _vit(), n_devices=n,
        particles=2 * n, epochs=1, batch_size=8, num_batches=2,
        svgd_lr=0.05, seed=0)
    assert _phase_line(capsys.readouterr().out, "sharded")["programs"]
