"""Infer base class (paper App. B): BDL algorithms extend Infer and express
inference as concurrent procedures on particles. The same algorithm code is
agnostic to the number of devices (paper §B.2 comment 2).

Backend seam (DESIGN.md §3, §8): ``bayes_infer`` is the stable entry
point; it hands the algorithm to the PD's Runtime object
(``repro.runtime.backends``). Subclasses implement ``_nel_infer`` (the
paper-faithful message-passing procedure) and may implement
``_fused_infer`` (thin ProgramSpec builders + an epoch loop on the
store's checkout/commit protocol). The CompiledRuntime selects the fused
form transparently when present; algorithms without one fall back to the
NEL path, so every algorithm runs under either backend.

Placement (DESIGN.md §6): ``placement`` is the mesh/placement plan the
fused forms compile against — particle axis sharded over the mesh's
``data`` axis, within-particle sharding from ``sharding/rules``. The
default (no mesh) is the single-device fast path; ``placement="auto"``
builds a mesh over all local devices. The NEL path ignores the mesh (its
devices come from ``num_devices``), but both paths share the PD's
ParticleStore, so state written by one is visible to the other.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Callable, Optional, Union

import jax

from ..core import ParticleModule, Placement, PushDistribution
from ..obs import trace as _trace


def _init_shapes(module):
    """Abstract per-particle param tree (eval_shape: no FLOPs, no
    memory) for policy-aware ``Placement.auto`` sizing; None when the
    module's init cannot be traced abstractly."""
    try:
        return jax.eval_shape(module.init, jax.random.PRNGKey(0))
    except Exception:
        return None


class Infer:
    def __init__(self, module: ParticleModule, *, num_devices: int = 1,
                 cache_size: int = 4, view_size: int = 4, seed: int = 0,
                 backend: str = "nel",
                 placement: Optional[Union[Placement, str]] = None,
                 capacity: int = 0, precision=None):
        self.module = module
        self.num_devices = num_devices
        if placement == "auto":
            # policy-aware sizing: the model axis is picked against the
            # MASTER-dtype per-particle bytes, so a bf16 store does not
            # reserve 2x the model shards it needs
            placement = Placement.auto(
                model="auto", precision=precision,
                param_tree=_init_shapes(module))
        # capacity preallocates store slots so a planned lifecycle
        # (bayes_infer then lifecycle.grow) never pays a growth recompile
        self.push_dist = PushDistribution(module, num_devices=num_devices,
                                          cache_size=cache_size,
                                          view_size=view_size, seed=seed,
                                          backend=backend,
                                          placement=placement,
                                          capacity=capacity,
                                          precision=precision)

    @property
    def backend(self) -> str:
        return self.push_dist.backend

    @property
    def precision(self):
        return self.push_dist.precision

    @property
    def placement(self) -> Placement:
        return self.push_dist.placement

    @property
    def store(self):
        return self.push_dist.store

    def _has_fused(self) -> bool:
        return type(self)._fused_infer is not Infer._fused_infer

    @contextmanager
    def _checked_out(self, pids, keys):
        """Checkout/commit protocol shared by every fused epoch loop: yield
        a dict of stacked state (the loop rebinds its entries as it trains
        on donated buffers); whatever was successfully checked out is
        committed back exactly once, even on mid-loop failure."""
        store = self.push_dist.store
        co = {}
        try:
            for k in keys:
                co[k] = store.checkout(k, pids)
            yield co
        finally:
            for k, v in co.items():
                store.commit(k, v, pids)

    def _fused_plan(self, pids):
        """(checkout pids, active mask, row index per pid) for one fused
        run over `pids`.

        Full live set in slot order -> the canonical capacity-padded
        path: checkout with ``None`` (padded trees whose shapes survive
        churn) plus the store's active mask; any other subset -> a dense
        checkout of exactly those rows under an all-ones mask. Loss
        vectors coming back from masked programs are indexed with the
        returned slots."""
        import jax.numpy as jnp
        store = self.push_dist.store
        pids = list(pids)
        # set comparison, not order: after churn store.pids is in slot
        # order while callers enumerate in pid order — both mean "the
        # full live set", and the returned per-pid slots keep the loss
        # indexing right either way
        if len(pids) == len(store) and set(pids) == set(store.pids):
            return None, store.active_mask(), [store.slot_of(p)
                                               for p in pids]
        return pids, jnp.ones((len(pids),), jnp.float32), \
            list(range(len(pids)))

    def _compiled_runtime(self):
        """The PD's runtime when it is already the compiled one, else a
        CompiledRuntime over the same PD/cache — benchmarks drive
        ``_fused_epochs`` directly on NEL-backend instances to time the
        fused path in isolation."""
        from ..runtime import CompiledRuntime
        rt = self.push_dist.runtime
        return rt if isinstance(rt, CompiledRuntime) \
            else CompiledRuntime(self.push_dist, rt.cache)

    @staticmethod
    def _read_losses(ls, slots):
        """The per-particle losses of one fused run on the host (``[]``
        when no batch ran): a ``bdl.device_wait`` span while the device
        finishes the steps the host has run ahead of, then one
        ``float(ls[s])`` per slot in a ``bdl.loss_sync`` span."""
        if ls is None:
            return []
        with _trace.span("bdl.device_wait", "bdl"):
            ls.block_until_ready()
        with _trace.span("bdl.loss_sync", "bdl", particles=len(slots)):
            return [float(ls[s]) for s in slots]

    @staticmethod
    def _traced_epochs(epochs: int, label: str):
        """Iterate ``range(epochs)``, bracketing each epoch's body (the
        code between yields) in an obs ``bdl.epoch`` span plus a
        ``jax.profiler.StepTraceAnnotation`` so device profiles show
        per-epoch step markers. Free when tracing is off."""
        if not _trace.enabled():
            yield from range(epochs)
            return
        for e in range(epochs):
            with _trace.span("bdl.epoch", "bdl", algo=label, epoch=e), \
                    jax.profiler.StepTraceAnnotation(label, step_num=e):
                yield e

    def bayes_infer(self, dataloader, epochs: int, **kw):
        return self.push_dist.runtime.infer(self, dataloader, epochs, **kw)

    def _nel_infer(self, dataloader, epochs: int, **kw):
        raise NotImplementedError

    def _fused_infer(self, dataloader, epochs: int, **kw):
        raise NotImplementedError  # overriding marks the algorithm as fusable

    def posterior_pred(self, batch):
        return self.push_dist.p_predict(batch)

    def posterior_predictive(self, **kw):
        """Hand a trained posterior off to the serving layer: a
        PredictiveService doing fused BMA over this Infer's particles
        (repro.serve). Algorithms whose posterior is richer than its
        particles override this — MultiSWAG samples its Gaussians at
        serve time. Caller owns the service (use as a context manager)."""
        return self.push_dist.serve(**kw)

    def p_parameters(self):
        return [self.push_dist.p_params(pid)
                for pid in self.push_dist.particle_ids()]

    def cleanup(self):
        self.push_dist.cleanup()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.cleanup()
