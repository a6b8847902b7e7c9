"""SWAG / multi-SWAG (Maddox et al., 2019; Wilson & Izmailov, 2020).

SWAG assumes the posterior is Normal with moments taken from the SGD
trajectory (paper §3.4's "assumptions that introduce densities"):

    mean     <- running average of theta
    sq_mean  <- running average of theta^2
    dev      <- ring buffer of the last K deviations (low-rank covariance)

sample:  theta = mean + sigma_diag^(1/2) z1 / sqrt(2)
                      + D z2 / sqrt(2 (K - 1))

multi-SWAG = an ensemble of SWAG particles: each particle carries its own
moments in particle.state (particle-local computation only -> scales like
deep ensembles in the paper's Fig. 4). The moment update runs through
repro.kernels.swag_moments (Pallas) when ``use_kernel=True``; interpret
mode is gated on the backend platform (compiled on TPU, interpreted
elsewhere) and the flag threads through ``swag_collect``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..obs.trace import traced
from .infer import Infer


# ---------------------------------------------------------------------------
# functional SWAG state ops (vmappable / jittable; used by both paths)
# ---------------------------------------------------------------------------

def swag_state_init(params, max_rank: int = 20):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {
        "n": jnp.zeros((), jnp.float32),
        "mean": zeros,
        "sq_mean": jax.tree.map(jnp.zeros_like, params),
        "dev": jax.tree.map(
            lambda p: jnp.zeros((max_rank,) + p.shape, p.dtype), params),
        "rank": jnp.zeros((), jnp.int32),
    }


def swag_collect(state, params, use_kernel: bool = True,
                 interpret: Optional[bool] = None):
    """One moment-collection step (after an SGD epoch in the paper's setup).

    ``interpret`` threads to the Pallas kernel: None (default) resolves to
    interpret-off-TPU-only (kernels/swag_moments), True/False force it."""
    n = state["n"]
    if use_kernel:
        from ..kernels import swag_moments as _k

        def upd(mean, sq, p, n_):
            return _k.update_moments(mean, sq, p, n_, interpret=interpret)
    else:
        upd = _update_moments_ref
    mean, sq = upd(state["mean"], state["sq_mean"], params, n)
    max_rank = jax.tree.leaves(state["dev"])[0].shape[0]
    slot = state["rank"] % max_rank
    dev = jax.tree.map(
        lambda d, p, m: jax.lax.dynamic_update_index_in_dim(
            d, (p - m).astype(d.dtype), slot, 0),
        state["dev"], params, mean)
    return {"n": n + 1, "mean": mean, "sq_mean": sq, "dev": dev,
            "rank": state["rank"] + 1}


def _update_moments_ref(mean, sq_mean, params, n):
    new_mean = jax.tree.map(lambda m, p: (m * n + p) / (n + 1), mean, params)
    new_sq = jax.tree.map(lambda s, p: (s * n + p * p) / (n + 1), sq_mean, params)
    return new_mean, new_sq


def swag_sample(state, rng, scale: float = 1.0, *, use_kernel: bool = False,
                interpret: Optional[bool] = None):
    """Draw one parameter sample from the SWAG Gaussian.

    ``use_kernel=True`` computes the diagonal scale through the fused
    Pallas pass (kernels/swag_moments.diag_std_flat); the interpret
    decision is NOT made here — it reuses the moment kernel's platform
    gating (compiled on TPU, interpreted elsewhere; ``interpret`` only
    forces it). This is the serve-time path of
    ``MultiSWAG.posterior_predictive``."""
    k1, k2 = jax.random.split(rng)
    leaves, tdef = jax.tree.flatten(state["mean"])
    z1_keys = jax.random.split(k1, len(leaves))
    max_rank = jax.tree.leaves(state["dev"])[0].shape[0]
    K_eff = jnp.maximum(jnp.minimum(state["rank"], max_rank).astype(jnp.float32), 2.0)
    z2 = jax.random.normal(k2, (max_rank,))
    rank_mask = (jnp.arange(max_rank) < state["rank"]).astype(jnp.float32)

    if use_kernel:
        from ..kernels import swag_moments as _k

        def diag_std(m, s):
            return _k.diag_std_flat(
                m.reshape(-1).astype(jnp.float32),
                s.reshape(-1).astype(jnp.float32),
                interpret=interpret).reshape(m.shape)
    else:
        def diag_std(m, s):
            return jnp.sqrt(jnp.maximum(s - m * m, 1e-30))

    def one(m, s, d, zk):
        diag = diag_std(m, s) * jax.random.normal(zk, m.shape) / jnp.sqrt(2.0)
        zw = (z2 * rank_mask).astype(d.dtype)
        lowrank = jnp.tensordot(zw, d, axes=(0, 0)) / jnp.sqrt(2.0 * (K_eff - 1.0))
        return m + scale * (diag + lowrank).astype(m.dtype)

    sq_leaves = tdef.flatten_up_to(state["sq_mean"])
    dev_leaves = tdef.flatten_up_to(state["dev"])
    out = [one(m, s, d, zk) for m, s, d, zk in
           zip(leaves, sq_leaves, dev_leaves, z1_keys)]
    return tdef.unflatten(out)


def swag_sample_stacked(stacked_state, rng, samples_per_particle: int,
                        scale: float = 1.0, *, use_kernel: bool = False,
                        interpret: Optional[bool] = None):
    """Serve-time sampling over the store's stacked SWAG moments: draw S
    samples from every particle's Gaussian in one vmapped program,
    returning stacked params with leading axis n*S (sample j of particle
    i at row i*S + j) — exactly the shape a PredictiveEngine serves."""
    n = jax.tree.leaves(stacked_state)[0].shape[0]
    S = samples_per_particle
    rep = jax.tree.map(lambda x: jnp.repeat(x, S, axis=0), stacked_state)
    keys = jax.random.split(rng, n * S)
    return jax.vmap(lambda st, k: swag_sample(
        st, k, scale, use_kernel=use_kernel, interpret=interpret))(rep, keys)


# ---------------------------------------------------------------------------
# particle-based multi-SWAG (the paper's path)
# ---------------------------------------------------------------------------

def _swag_collect_fused(state, params):
    """Module-level (stable identity) body of the fused moment-collection
    map_step — the ProgramCache keys on it, so every MultiSWAG in the
    process shares one compiled collection program per shape."""
    return swag_collect(state, params, use_kernel=False)


def _swag_step(particle, batch):
    return particle.step(batch).wait()


def _swag_collect_msg(particle):
    particle.state["swag"] = swag_collect(particle.state["swag"],
                                          particle.state["params"],
                                          use_kernel=False)
    return None


class MultiSWAG(Infer):
    def _create(self, optimizer, num_particles, max_rank):
        pids = []
        for _ in range(num_particles):
            pid = self.push_dist.p_create(
                optimizer, receive={"SWAG_COLLECT": _swag_collect_msg})
            p = self.push_dist.particles[pid]
            p.state["swag"] = swag_state_init(p.state["params"], max_rank)
            pids.append(pid)
        return pids

    def _nel_infer(self, dataloader, epochs: int, *, optimizer,
                   num_particles: int = 4, pretrain_epochs: int = 0,
                   max_rank: int = 20):
        pids = self._create(optimizer, num_particles, max_rank)
        losses = []
        for e in range(epochs):
            for batch in dataloader:
                futs = [self.push_dist.particles[pid].step(batch) for pid in pids]
                losses = [float(f.wait()) for f in futs]
            if e >= pretrain_epochs:  # collect moments once per epoch
                futs = [self.push_dist.p_launch(pid, "SWAG_COLLECT")
                        for pid in pids]
                self.push_dist.p_wait(futs)
        return pids, losses

    def _fused_infer(self, dataloader, epochs: int, *, optimizer,
                     num_particles: int = 4, pretrain_epochs: int = 0,
                     max_rank: int = 20):
        pids = self._create(optimizer, num_particles, max_rank)
        losses = self._fused_epochs(pids, dataloader, epochs,
                                    optimizer=optimizer,
                                    pretrain_epochs=pretrain_epochs)
        return pids, losses

    @traced("bdl.fused_call", "bdl")
    def _fused_epochs(self, pids, dataloader, epochs: int, *, optimizer,
                      pretrain_epochs: int = 0):
        """Stacked-axis multi-SWAG on existing particles — two thin
        ProgramSpecs on the runtime layer (vmapped train step + vmapped
        moment collection), all state (params, opt, SWAG moments) checked
        out of the store once, donated across the epoch loop, and
        committed back once at the end."""
        from ..runtime import specs
        rt = self._compiled_runtime()
        # the SWAG moments follow the master dtype automatically
        # (zeros_like of the cast params); only the train step carries
        # the compute-cast split
        step_spec = specs.ensemble_step(self.module.loss, optimizer,
                                        precision=self.precision)
        collect_spec = specs.map_step(_swag_collect_fused,
                                      key=("swag_collect",), n_state=2,
                                      masked=True)
        co_pids, mask, slots = self._fused_plan(pids)
        step, collect, ls = None, None, None
        with self._checked_out(co_pids,
                               ("params", "opt_state", "swag")) as co:
            for e in self._traced_epochs(epochs, "swag"):
                for batch in dataloader:
                    if step is None:  # one cache lookup per fused run
                        step = rt.program(step_spec, co["params"],
                                          co["opt_state"], batch, mask)
                    co["params"], co["opt_state"], ls = step(
                        co["params"], co["opt_state"], batch, mask)
                if e >= pretrain_epochs:
                    if collect is None:
                        collect = rt.program(collect_spec, co["swag"],
                                             co["params"], mask)
                    co["swag"] = collect(co["swag"], co["params"], mask)
        return self._read_losses(ls, slots)

    def posterior_predictive(self, *, samples_per_particle: int = 0,
                             rng=None, scale: float = 1.0,
                             use_kernel: bool = True, **kw):
        """Serve-time handoff: with ``samples_per_particle=S > 0`` the
        service does BMA over n*S fresh draws from each particle's SWAG
        Gaussian (sampled once, up front, into a static stacked tree —
        the multi-SWAG predictive of Wilson & Izmailov 2020) instead of
        the particle means. S=0 serves the live particle params like any
        other Infer. The diagonal-scale read goes through the Pallas
        moments kernel with its platform gating (``use_kernel=True``)."""
        if samples_per_particle <= 0:
            return super().posterior_predictive(**kw)
        from ..runtime import jit_program
        rng = jax.random.PRNGKey(0) if rng is None else rng
        # dense live rows (not the capacity-padded canonical form): a
        # padding slot's zero moments must never be sampled as a member
        args = (self.store.dense("swag"), rng)
        # one cached program for the whole draw (not one eager dispatch
        # per op and leaf)
        sample = jit_program(
            "swag_sample",
            ("swag_sample", samples_per_particle, float(scale),
             bool(use_kernel)),
            lambda st, key: swag_sample_stacked(
                st, key, samples_per_particle, scale, use_kernel=use_kernel),
            args)
        return self.push_dist.serve(params=sample(*args), **kw)

    def sample_predict(self, batch, *, samples_per_particle: int = 5,
                       rng=None, scale: float = 1.0):
        """multi-SWAG prediction: average over SWAG samples of every particle."""
        rng = jax.random.PRNGKey(0) if rng is None else rng
        outs = []
        for pid in self.push_dist.particle_ids():
            p = self.push_dist.particles[pid]
            for _ in range(samples_per_particle):
                rng, sub = jax.random.split(rng)
                theta = swag_sample(p.state["swag"], sub, scale)
                outs.append(self.module._forward(theta, batch))
        return jax.tree.map(lambda *xs: sum(xs) / len(xs), *outs)
