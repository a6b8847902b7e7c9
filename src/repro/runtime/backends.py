"""Runtime protocol: ``backend="nel"|"compiled"`` selects an object.

``PushDistribution`` used to branch on a backend *string* at every seam
(``pd.p_predict``, ``Infer.bayes_infer``); each branch then kept its own
compile cache. Now the string selects a Runtime once, at construction:

  * ``NelRuntime``     — the paper-faithful actor path: inference runs the
    algorithm's message-passing procedure on the PR-1 Executor (persistent
    per-device event loops); prediction is n sequential per-particle
    forwards. Its per-particle step/forward programs ALSO compile through
    the shared ProgramCache (``jit_program`` via ``ParticleModule``), so
    all three workloads — train, serve, NEL — share one compile layer.
  * ``CompiledRuntime`` — the fused stacked-axis path: algorithms with a
    ``_fused_infer`` form run one XLA program over the store's stacked
    state (checkout -> donated epochs -> commit); algorithms without one
    transparently fall back to the NEL procedure.

Both expose ``stats()`` merging the executor's wait-vs-run counters with
the ProgramCache's hit/miss/cold-compile counters — the unified
observability surface ``PushDistribution.stats()`` returns.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Protocol, runtime_checkable

import jax

from ..obs import summary as _obs_summary
from .cache import ProgramCache, global_cache
from .program import ProgramSpec
from . import specs

BACKENDS = ("nel", "compiled")


@runtime_checkable
class Runtime(Protocol):
    """What a runtime backend must provide (DESIGN.md §8)."""
    name: str

    def infer(self, algo, dataloader, epochs: int, **kw): ...

    def predict(self, pd, batch): ...

    def run(self, spec: ProgramSpec, *args, placement=None,
            state_token=None): ...

    def stats(self) -> Dict[str, Any]: ...


class _BaseRuntime:
    def __init__(self, pd, cache: Optional[ProgramCache] = None):
        self.pd = pd
        # explicit None test: an *empty* ProgramCache is falsy (__len__)
        self.cache = cache if cache is not None else global_cache()

    def program(self, spec: ProgramSpec, *args, placement=None,
                state_token=None):
        """plan -> (cached) lower: the Program for this PD's placement
        and store generation. Epoch loops fetch it ONCE before the loop
        and call it per batch — the returned jit wrapper handles batch
        shape changes itself, so the per-step host cost is a plain call,
        not a cache-key construction over the whole state tree."""
        if placement is None:
            placement = self.pd.placement
        if state_token is None:
            state_token = self.pd.store.generation()
        return self.cache.program(spec, placement, args, state_token)

    def run(self, spec: ProgramSpec, *args, placement=None, state_token=None):
        """plan -> (cached) lower -> execute one fused program against
        this PD's placement and store generation."""
        return self.program(spec, *args, placement=placement,
                            state_token=state_token)(*args)

    def stats(self) -> Dict[str, Any]:
        ex = self.pd.nel.executor.stats()
        pl = self.pd.placement
        store_stats = self.pd.store.snapshot_stats()
        out = {
            "backend": self.name,
            "executor": ex,
            "dispatch": dict(self.pd.nel.stats),
            "store": store_stats,
            "program_cache": self.cache.snapshot_stats(),
            "lifecycle": {**self.pd.store.lifecycle_stats(),
                          **getattr(self.pd, "lifecycle", {})},
            # the 2D placement plan + its footprint: mesh shape, sharding
            # mode, per-device parameter bytes (drops by ~model-axis size
            # under tensor parallelism), and how often state was re-placed
            "placement": {
                "mesh_shape": (None if pl.mesh is None else
                               {a: int(pl.mesh.shape[a])
                                for a in pl.mesh.axis_names}),
                "mode": pl.mode,
                "particle_axis": pl.particle_axis,
                "model_axis": pl.model_axis,
                "model_axis_size": pl.model_axis_size(),
                "per_device_param_bytes":
                    self.pd.store.per_device_bytes("params"),
                "reshards": store_stats["device_puts"],
            },
            # tracer state (repro.obs): is tracing on,
            # how many spans recorded/buffered/dropped, ring capacity
            "obs": _obs_summary(),
        }
        # continuous-batching decode, when a DecodeScheduler serves this
        # store (lazy import: runtime must not depend on serve at module
        # scope — serve already imports runtime)
        from ..serve.batcher import decode_stats_for
        decode = decode_stats_for(self.pd.store)
        if decode is not None:
            out["decode"] = decode
        return out


class NelRuntime(_BaseRuntime):
    """Paper-faithful actor runtime (wraps the PR-1 Executor)."""

    name = "nel"

    def infer(self, algo, dataloader, epochs: int, **kw):
        return algo._nel_infer(dataloader, epochs, **kw)

    def predict(self, pd, batch):
        """n per-particle forwards on the event loops + host average."""
        futs = [pd.particles[pid].forward(batch)
                for pid in pd.particle_ids()]
        outs = [f.wait() for f in futs]

        def mean(*xs):
            # particles on several devices: average on the first one's
            home = xs[0].sharding
            return sum(x if x.sharding == home else jax.device_put(x, home)
                       for x in xs) / len(xs)

        return jax.tree.map(mean, *outs)


class CompiledRuntime(_BaseRuntime):
    """Fused stacked-axis runtime (wraps the store's checkout/commit
    protocol and the shared ProgramCache)."""

    name = "compiled"

    def infer(self, algo, dataloader, epochs: int, **kw):
        if algo._has_fused():
            return algo._fused_infer(dataloader, epochs, **kw)
        return algo._nel_infer(dataloader, epochs, **kw)

    def predict(self, pd, batch):
        pids = pd.particle_ids()
        if not pids:
            return NelRuntime.predict(self, pd, batch)
        # capacity-padded stacked params + active mask, read as ONE
        # atomic store snapshot (a mask bit never goes live before its
        # slot's data lands): the fused BMA averages live slots only,
        # and clone/kill churn within capacity reuses this exact
        # program (shapes and generation unchanged)
        _, mask, stacked = pd.store.snapshot("params")
        spec = specs.ensemble_predict(pd.module.forward)
        return self.run(spec, stacked, batch, mask)


def make_runtime(backend: str, pd,
                 cache: Optional[ProgramCache] = None) -> Runtime:
    if backend == "nel":
        return NelRuntime(pd, cache)
    if backend == "compiled":
        return CompiledRuntime(pd, cache)
    raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
