"""Production mesh factory (functions only — importing never touches jax
device state; the dry-run sets XLA_FLAGS before any jax import).

Every mesh is built with ``AxisType.Auto`` axes: GSPMD propagates the
shardings the placement layer annotates.

All factories validate axis sizes against the visible device count up
front and raise a clear ``ValueError`` — a bad ``model=`` used to surface
as a cryptic reshape/XLA error from deep inside ``jax.make_mesh``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
from jax.sharding import AxisType


def _validate(shape, axes) -> None:
    if len(shape) != len(axes):
        raise ValueError(
            f"mesh shape {tuple(shape)} and axes {tuple(axes)} disagree: "
            f"{len(shape)} sizes for {len(axes)} axis names")
    if any(int(s) <= 0 for s in shape):
        raise ValueError(f"mesh shape {tuple(shape)} has a non-positive "
                         "axis size")
    want = math.prod(int(s) for s in shape)
    have = len(jax.devices())
    if want > have:
        raise ValueError(
            f"mesh {dict(zip(axes, shape))} needs {want} devices but only "
            f"{have} are visible (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={want} to "
            "emulate, or shrink an axis)")
    if have % want != 0:
        raise ValueError(
            f"mesh {dict(zip(axes, shape))} covers {want} of {have} visible "
            f"devices; {have} is not a multiple of {want}, so no axis size "
            "can be grown to use them all — pick axis sizes whose product "
            f"divides {have}")


def make_mesh(shape, axes):
    """Validated ``jax.make_mesh`` with Auto axis types."""
    _validate(shape, axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: 16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_bench_mesh(n_devices: int, model: int = 1):
    """2D ``(data=particle, model)`` mesh over ``n_devices`` (forced host
    devices on CPU benchmarks, real chips elsewhere). ``model`` must
    divide the device count — the particle axis gets the rest."""
    if model <= 0:
        raise ValueError(f"model axis size must be positive, got {model}")
    if n_devices % model != 0:
        raise ValueError(
            f"model axis size {model} does not divide the device count "
            f"{n_devices}: the particle axis would get {n_devices}/{model} "
            "devices — pick a model-axis size that divides the device count")
    data = n_devices // model
    return make_mesh((data, model), ("data", "model"))


def pick_model_axis(params_bytes: int, n_devices: int, *,
                    device_memory_bytes: Optional[int] = None,
                    fraction: float = 0.6) -> int:
    """Smallest model-axis size (a divisor of ``n_devices``) such that one
    particle's parameter shard, ``params_bytes / model``, fits within
    ``fraction`` of a device's memory — what ``Placement.auto(model=
    "auto")`` uses. When the backend reports no memory budget (CPU
    ``memory_stats()`` is often absent) or ``params_bytes`` is unknown,
    returns 1 (particle-parallel, today's behavior); when even
    ``model=n_devices`` does not fit, returns ``n_devices`` (best
    effort — the caller sees the OOM with the largest possible split).
    """
    if device_memory_bytes is None:
        try:
            stats = jax.local_devices()[0].memory_stats()
            device_memory_bytes = (stats or {}).get("bytes_limit")
        except Exception:
            device_memory_bytes = None
    if not device_memory_bytes or not params_bytes or n_devices <= 1:
        return 1
    budget = fraction * device_memory_bytes
    divisors = sorted(d for d in range(1, n_devices + 1)
                      if n_devices % d == 0)
    for m in divisors:
        if params_bytes / m <= budget:
            return m
    return n_devices
