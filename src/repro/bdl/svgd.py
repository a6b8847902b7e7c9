"""Stein Variational Gradient Descent (Liu & Wang, 2016) on particles.

Two implementations, benchmarked against each other in EXPERIMENTS.md §Perf:

1. ``SteinVGD`` — the paper-faithful message-passing version (paper Fig. 5/6):
   a leader particle drives SVGD_STEP (local backward on every particle),
   gathers every particle's (params, grads) via read-only views (all-to-all,
   paper Fig. 1), computes the kernel update, and sends SVGD_FOLLOW to each
   particle. Updates are applied concurrently from read-only snapshots —
   the property the paper credits for beating its monolithic baseline.

2. ``fused_svgd_step`` — the compiled path (``backend="compiled"``):
   stacked particle axis, flattened (n, D) parameter matrix, RBF kernel +
   driving force in one XLA program (Pallas kernels on TPU; jnp oracle
   elsewhere). ``SteinVGD._fused_infer`` drives it on the same particles
   the NEL path would create.

Update rule (standard SVGD, descent form; see DESIGN.md for the sign
discrepancy in the paper's Fig. 6 listing):

    theta_i <- theta_i - (lr / n) * sum_j [ k(theta_j, theta_i) * g_j
                                            - (theta_i - theta_j)/ell^2 * k_ji ]

with g_j = grad of the loss (= -grad log posterior), k = RBF with
bandwidth ell (fixed, or the median heuristic when lengthscale <= 0).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from ..core import functional
from ..core.store import Placement
from ..obs.trace import traced
from .infer import Infer


# ---------------------------------------------------------------------------
# functional core (used by both paths; Pallas-accelerated when enabled)
# ---------------------------------------------------------------------------

def rbf_lengthscale(theta, lengthscale: float):
    """Median heuristic when lengthscale <= 0 (Liu & Wang §5)."""
    if lengthscale > 0:
        return jnp.asarray(lengthscale, jnp.float32)
    n = theta.shape[0]
    sq = pairwise_sqdist(theta)
    med = jnp.median(sq)
    return jnp.sqrt(0.5 * med / jnp.log(n + 1.0) + 1e-12)


def pairwise_sqdist(theta):
    """theta: (n, D) -> (n, n) squared distances (jnp oracle)."""
    sq = jnp.sum(theta * theta, axis=1)
    d2 = sq[:, None] + sq[None, :] - 2.0 * theta @ theta.T
    return jnp.maximum(d2, 0.0)


def svgd_force(theta, grads, lengthscale: float, use_kernel: bool = False,
               mask=None):
    """theta, grads: (n, D) -> phi: (n, D) descent direction.

    phi_i = (1/n) sum_j [ k_ji g_j - k_ji (theta_i - theta_j) / ell^2 ]

    With a (n,) active ``mask`` (capacity-padded stores, DESIGN.md §9)
    the sum runs over live slots only — dead rows are where-zeroed on
    the way in (so even NaN padding cannot leak), excluded from the
    kernel matrix via the mask outer product, and get phi = 0 out. The
    result restricted to live rows equals the dense force over just
    those rows. Masked forces use the jnp oracle (the Pallas kernel is
    dense-only)."""
    if mask is None:
        if use_kernel:
            # ops.svgd_force gates Pallas interpret mode on the platform
            # (compiled on TPU, interpreted elsewhere)
            from ..kernels import ops as _k
            return _k.svgd_force(theta, grads, lengthscale)
        ell = rbf_lengthscale(theta, lengthscale)
        K_w, n_eff = 1.0, theta.shape[0]
    else:
        m = mask.astype(theta.dtype)
        mb = m > 0
        theta = jnp.where(mb[:, None], theta, 0.0)
        grads = jnp.where(mb[:, None], grads, 0.0)
        n_eff = jnp.maximum(jnp.sum(m), 1.0)
        if lengthscale > 0:
            ell = jnp.asarray(lengthscale, theta.dtype)
        else:
            # median heuristic over live pairs only
            sq = pairwise_sqdist(theta)
            pair = mb[:, None] & mb[None, :]
            med = jnp.nan_to_num(jnp.nanmedian(jnp.where(pair, sq, jnp.nan)))
            ell = jnp.sqrt(0.5 * med / jnp.log(n_eff + 1.0) + 1e-12)
        K_w = m[:, None] * m[None, :]   # dead pairs fall out of the kernel
    d2 = pairwise_sqdist(theta) * (1.0 - jnp.eye(theta.shape[0]))
    K = jnp.exp(-0.5 * d2 / (ell * ell)) * K_w                 # (n, n), k_ji
    ksum = K.sum(axis=0)                                       # sum_j k_ji
    attract = K.T @ grads                                      # (n, D)
    repulse = (ksum[:, None] * theta - K.T @ theta) / (ell * ell)
    return (attract - repulse) / n_eff


def fused_svgd_step(loss_fn, *, lr: float, lengthscale: float = 1.0,
                    use_kernel: bool = False, placement=None,
                    num_particles: Optional[int] = None,
                    compute_dtype=None):
    """One compiled SVGD step over stacked particles.

    With a mesh placement the per-particle backward pass is distributed
    over the particle axis (``spmd_axis_name``); the cross-particle kernel
    matrix is expressed as an on-device all-gather over that axis: the
    flattened (n, D) matrix is constrained particle-sharded for the local
    math, then constrained gathered (replicated rows, D over `model`) to
    feed the RBF kernel + force, then re-constrained particle-sharded —
    GSPMD lowers those transitions to all-gathers, never to host copies."""
    placement = placement or Placement()
    spmd = (placement.spmd_axis(num_particles)
            if num_particles is not None else None)
    vag = jax.vmap(jax.value_and_grad(lambda p, b: loss_fn(p, b)[0]),
                   in_axes=(0, None), spmd_axis_name=spmd)

    def step(stacked_params, batch, mask=None):
        if compute_dtype is not None:
            # backward pass in the compute dtype; the kernel force below
            # is fp32 regardless (theta32/g32), and the update lands back
            # in the master dtype via new_theta = theta - ...
            from ..core.precision import cast_floats
            losses, grads = vag(cast_floats(stacked_params, compute_dtype),
                                cast_floats(batch, compute_dtype))
            losses = losses.astype(jnp.float32)
        else:
            losses, grads = vag(stacked_params, batch)
        theta, unravel = functional.flatten_stacked(stacked_params)
        g, _ = functional.flatten_stacked(grads)
        theta32 = theta.astype(jnp.float32)
        g32 = g.astype(jnp.float32)
        if placement.mesh is not None:
            n, d = theta32.shape
            wide = placement.matrix(n, d)
            gathered = placement.gathered_matrix(d)
            theta32 = jax.lax.with_sharding_constraint(theta32, wide)
            g32 = jax.lax.with_sharding_constraint(g32, wide)
            # the all-to-all the paper identifies as SVGD's bottleneck
            # (§5.1), as one on-device collective over the particle axis:
            theta_all = jax.lax.with_sharding_constraint(theta32, gathered)
            g_all = jax.lax.with_sharding_constraint(g32, gathered)
            phi = svgd_force(theta_all, g_all, lengthscale,
                             use_kernel=use_kernel, mask=mask)
            phi = jax.lax.with_sharding_constraint(phi, wide)
        else:
            phi = svgd_force(theta32, g32, lengthscale, use_kernel=use_kernel,
                             mask=mask)
        new_theta = theta - lr * phi.astype(theta.dtype)
        if mask is not None:
            # dead slots stay bit-for-bit frozen and report loss 0.0
            new_theta = jnp.where(mask[:, None] > 0, new_theta, theta)
            losses = jnp.where(mask > 0, losses, 0.0)
        new_params = jax.vmap(unravel)(new_theta)
        return new_params, losses

    return step


def svgd_step_spec(loss_fn, *, lr: float, lengthscale: float = 1.0,
                   use_kernel: bool = False, precision=None):
    """ProgramSpec for the fused SVGD step: stacked params sharded over
    the particle axis and donated across the epoch loop; the kernel
    matrix's all-to-all stays an on-device all-gather (fused_svgd_step).

    ``precision`` (None | preset name | Precision) selects the compute
    dtype of the per-particle backward pass; the kernel force is fp32
    either way. The policy is folded into ``ProgramSpec.precision`` —
    the compute cast is traced over the same master inputs, so abstract
    dtypes alone cannot distinguish the programs."""
    from ..core import precision as precision_mod
    from ..runtime import ProgramSpec, ident
    prec = precision_mod.get(precision)
    cd = prec.compute if prec.casts_compute else None

    def make(ctx):
        return fused_svgd_step(
            loss_fn, lr=lr, lengthscale=lengthscale, use_kernel=use_kernel,
            placement=ctx.placement,
            num_particles=ctx.num_particles or None,
            compute_dtype=cd)

    return ProgramSpec(
        name="svgd_step",
        key=("svgd_step", ident(loss_fn), float(lr), float(lengthscale),
             bool(use_kernel)),
        make=make,
        in_kinds=("state", "replicated", "replicated"),
        out_kinds=("in:0", "vector"),
        donate=(0,),
        precision=prec.key() if prec.casts_compute else None)


def compile_svgd_step(loss_fn, placement, stacked, batch, mask=None, *,
                      lr: float, lengthscale: float = 1.0,
                      use_kernel: bool = False, state_token=None,
                      precision=None):
    """The fused SVGD step against a placement plan, lowered and cached
    by the shared ProgramCache (runtime layer). Pass
    ``mask=store.active_mask()`` for the capacity-padded masked program
    and ``state_token=store.generation()`` to share the entry with
    programs the Runtime lowered against that store."""
    from ..runtime import global_cache
    spec = svgd_step_spec(loss_fn, lr=lr, lengthscale=lengthscale,
                          use_kernel=use_kernel, precision=precision)
    args = (stacked, batch) + (() if mask is None else (mask,))
    return global_cache().program(spec, placement, args, state_token)


# ---------------------------------------------------------------------------
# paper-faithful message-passing SVGD (Fig. 5 / Fig. 6)
# ---------------------------------------------------------------------------

def _svgd_step(particle, batch):
    """SVGD_STEP handler: local backward pass, stash grads."""
    return particle.grad(batch).wait()


def _svgd_follow(particle, lr, update):
    """SVGD_FOLLOW handler: apply the leader's kernel update."""
    return particle.apply_update(update, lr).wait()


def _svgd_leader(particle, lr, lengthscale, dataloader, epochs):
    """SVGD_LEADER handler (paper Fig. 6, jax-native).

    Per batch: (1) step every particle (concurrent backward passes),
    (2) gather every particle's params+grads via read-only views,
    (3) compute the kernel force, (4) send SVGD_FOLLOW to every particle.
    """
    n_pids = particle.particle_ids()
    others = [pid for pid in n_pids if pid != particle.pid]
    losses = []
    for _ in range(epochs):
        for batch in dataloader:
            # 1. step every particle
            fut = particle.grad(batch)
            futs = [particle.send(pid, "SVGD_STEP", batch) for pid in others]
            losses = [float(fut.wait())] + [float(f.wait()) for f in futs]

            # 2. gather every other particle's parameters + grads
            views = {pid: particle.get(pid) for pid in others}
            views = {pid: f.wait() for pid, f in views.items()}

            flat, unravel = ravel_pytree(particle.state["params"])

            def here(x, home=flat.sharding):
                # followers may live on other devices: gather to the leader
                return x if x.sharding == home else jax.device_put(x, home)

            theta = [flat] + [here(ravel_pytree(views[pid].parameters())[0])
                              for pid in others]
            gflat = [ravel_pytree(particle.state["grads"])[0]] + \
                    [here(ravel_pytree(views[pid].gradients())[0])
                     for pid in others]
            theta = jnp.stack(theta).astype(jnp.float32)
            g = jnp.stack(gflat).astype(jnp.float32)

            # 3. kernel force
            phi = svgd_force(theta, g, lengthscale)

            # 4. send updates (concurrent follow)
            futs = [particle.send(pid, "SVGD_FOLLOW", lr, unravel(phi[i + 1]))
                    for i, pid in enumerate(others)]
            _svgd_follow(particle, lr, unravel(phi[0]))
            for f in futs:
                f.wait()
    return losses


class SteinVGD(Infer):
    def _create(self, num_particles: int):
        pid_leader = self.push_dist.p_create(
            None, device=0, receive={"SVGD_LEADER": _svgd_leader,
                                     "SVGD_STEP": _svgd_step,
                                     "SVGD_FOLLOW": _svgd_follow})
        pids = [pid_leader]
        for p in range(num_particles - 1):
            pid = self.push_dist.p_create(
                None, device=(p + 1) % self.num_devices,
                receive={"SVGD_STEP": _svgd_step, "SVGD_FOLLOW": _svgd_follow})
            pids.append(pid)
        return pids

    def _nel_infer(self, dataloader, epochs: int, *, num_particles: int = 4,
                   lengthscale: float = 1.0, lr: float = 1e-3):
        pids = self._create(num_particles)
        losses = self.push_dist.p_wait([self.push_dist.p_launch(
            pids[0], "SVGD_LEADER", lr, lengthscale, dataloader, epochs)])[0]
        return pids, losses

    def _fused_infer(self, dataloader, epochs: int, *, num_particles: int = 4,
                     lengthscale: float = 1.0, lr: float = 1e-3):
        """Compiled stacked-axis SVGD: identical particles (same rng stream
        as the NEL path), the whole kernel step in one XLA program."""
        pids = self._create(num_particles)
        losses = self._fused_epochs(pids, dataloader, epochs, lr=lr,
                                    lengthscale=lengthscale)
        return pids, losses

    @traced("bdl.fused_call", "bdl")
    def _fused_epochs(self, pids, dataloader, epochs: int, *,
                      lr: float = 1e-3, lengthscale: float = 1.0):
        rt = self._compiled_runtime()
        spec = svgd_step_spec(self.module.loss, lr=lr,
                              lengthscale=lengthscale,
                              precision=self.precision)
        co_pids, mask, slots = self._fused_plan(pids)
        prog, ls = None, None
        with self._checked_out(co_pids, ("params",)) as co:
            for _ in self._traced_epochs(epochs, "svgd"):
                for batch in dataloader:
                    if prog is None:  # one cache lookup per fused run
                        prog = rt.program(spec, co["params"], batch, mask)
                    co["params"], ls = prog(co["params"], batch, mask)
        return self._read_losses(ls, slots)
