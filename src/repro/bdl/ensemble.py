"""Deep ensembles (Lakshminarayanan et al., 2017) on particles.

No communication between particles (paper §3.1) — each particle trains
independently on its own device timeline; the NEL overlaps their steps
across devices. This is the best-scaling algorithm in the paper's Fig. 4.

Under ``backend="compiled"`` the same algorithm lowers to one fused XLA
program over the stacked particle axis: ``_fused_epochs`` is a thin
builder over the runtime layer — one ``ensemble_step`` ProgramSpec
(repro.runtime.specs), lowered/cached by the shared ProgramCache, driven
on state checked out of the ParticleStore once, donated to XLA every step
(multi-epoch training never leaves the device), and committed back once
at the end. Identical per-particle inits (the PD's rng stream is shared
by both paths); with a mesh placement the particle axis is sharded across
devices (``spmd_axis_name`` + explicit in/out shardings).
"""
from __future__ import annotations

from ..obs.trace import traced
from ..runtime import specs
from .infer import Infer


class DeepEnsemble(Infer):
    def _nel_infer(self, dataloader, epochs: int, *, optimizer,
                   num_particles: int = 4):
        pids = [self.push_dist.p_create(optimizer) for _ in range(num_particles)]
        losses = []
        for _ in range(epochs):
            for batch in dataloader:
                futs = [self.push_dist.particles[pid].step(batch) for pid in pids]
                losses = [float(f.wait()) for f in futs]
        return pids, losses

    def _fused_infer(self, dataloader, epochs: int, *, optimizer,
                     num_particles: int = 4):
        pids = [self.push_dist.p_create(optimizer) for _ in range(num_particles)]
        losses = self._fused_epochs(pids, dataloader, epochs,
                                    optimizer=optimizer)
        return pids, losses

    @traced("bdl.fused_call", "bdl")
    def _fused_epochs(self, pids, dataloader, epochs: int, *, optimizer):
        """Train existing particles for `epochs` through the fused program
        (store checkout -> donated compiled loop -> one commit). Reused by
        benchmarks so the timed region is exactly the backend="compiled"
        epoch path."""
        rt = self._compiled_runtime()
        spec = specs.ensemble_step(self.module.loss, optimizer,
                                   precision=self.precision)
        co_pids, mask, slots = self._fused_plan(pids)
        prog, ls = None, None
        with self._checked_out(co_pids, ("params", "opt_state")) as co:
            for _ in self._traced_epochs(epochs, "ensemble"):
                for batch in dataloader:
                    if prog is None:  # one cache lookup per fused run
                        prog = rt.program(spec, co["params"],
                                          co["opt_state"], batch, mask)
                    co["params"], co["opt_state"], ls = prog(
                        co["params"], co["opt_state"], batch, mask)
        return self._read_losses(ls, slots)


def compiled_ensemble_step(module, optimizer):
    """Fused path: all particles in one XLA program. Returns a callable
    compiling lazily per argument shapes through the shared ProgramCache
    (single-device form; pass a placement via runtime specs for meshes).

    NON-donating (dataclasses.replace of the epoch-loop spec): callers
    of this standalone helper may reuse their input arrays after the
    call — the donation plan is part of the cache key, so this never
    collides with the donating program the epoch loop uses."""
    import dataclasses

    from ..runtime import global_cache
    spec = dataclasses.replace(specs.ensemble_step(module.loss, optimizer),
                               donate=())
    cache = global_cache()

    def step(stacked_params, stacked_opt_state, batch):
        return cache.run(spec, stacked_params, stacked_opt_state, batch)

    return step
