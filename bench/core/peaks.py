"""Per-chip peak rates, keyed by ``jax.devices()[0].device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip
interconnect. A device kind that is not in the table is an error, never
a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "hbm_bytes": 16e9,
                    "ici_bw": 200e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak rates recorded for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def roofline_seconds(flops: float, bytes_: float, peak: dict):
    """Least time on one chip for ``flops`` and ``bytes_``, and which
    bound sets it."""
    t_c, t_m = flops / peak["flops"], bytes_ / peak["hbm_bw"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
