"""2D (particle x model) placement: the ISSUE-7 acceptance subprocess.

The heavy check lives in tests/_sharded_2d_check.py and runs under 4
forced host devices arranged as a ``particle=2 x model=2`` mesh: fused
ensemble/SVGD parity vs single-device, serving + paged decode parity,
kv-page heads on the model axis, zero mid-run host transfers, second
service cold==0, and the ~4x per-device footprint drop on a model-only
placement of a llama3-8b stand-in. This wrapper keeps it in tier-1 and
in the ``sharded-2x2`` CI matrix job.
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_2d_placement_across_4_devices():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "_sharded_2d_check.py")],
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
             "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "OK" in out.stdout
