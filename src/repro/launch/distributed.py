"""Multi-host bring-up for 2D (particle x model) placement.

``initialize()`` is the one call a multi-host launcher makes before
building a ``Placement``: it wires ``jax.distributed`` from explicit
arguments or the standard environment (JAX_COORDINATOR_ADDRESS /
JAX_NUM_PROCESSES / JAX_PROCESS_ID, falling back to cluster
auto-detection when jax supports it), after which ``jax.devices()``
spans every process and the same ``Placement.auto(model=...)`` code
path that runs single-process multi-device runs multi-host — the mesh
factories and sharding rules never special-case the host count.

Single-process launches (tests, benchmarks, CPU smokes) call this too:
with no coordinator configured it is a documented no-op returning
False, so library code can call it unconditionally. The call is
idempotent — a second ``initialize()`` in the same process returns
True without re-contacting the coordinator.
"""
from __future__ import annotations

import os
import threading
from typing import Optional

import jax

_lock = threading.Lock()
_initialized = False


def is_initialized() -> bool:
    """Whether this process already joined a jax.distributed cluster
    (via this module; out-of-band initialization is also detected)."""
    with _lock:
        if _initialized:
            return True
    return jax.distributed.is_initialized()


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids=None) -> bool:
    """Join (or skip joining) a multi-host jax cluster. Returns True when
    the process is part of a multi-process cluster afterwards, False for
    the single-process no-op path. Arguments default to the standard
    environment variables so launchers can configure placement without
    code changes."""
    global _initialized
    with _lock:
        if _initialized:
            return True
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])
    if coordinator_address is None and num_processes is None:
        # no cluster configured: the single-process fast path
        return False
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    try:
        jax.distributed.initialize(**kwargs)
    except RuntimeError as e:
        # already initialized out-of-band counts as success; anything
        # else (bad address, size mismatch) must surface to the launcher
        if "already initialized" not in str(e).lower():
            raise
    with _lock:
        _initialized = True
    return True
