"""Compiled functional view of a Push distribution (beyond-paper fast path).

The host-side NEL (nel.py) reproduces the paper's runtime faithfully. This
module restates the same particle programs over a *stacked particle axis*:
params of all n particles live in one pytree with leading axis n, every
particle-local computation is vmapped, and every particle-to-particle
communication pattern becomes an array op (all-to-all gather = the stacked
matrix itself; on a sharded mesh, XLA's all-gather over the particle axis).

Mesh-aware compilation (`compile_*`) delegates to the runtime layer
(``repro.runtime``, DESIGN.md §8): a ProgramSpec names the step and the
role of each argument; `runtime.program.lower` jits it with explicit
``in_shardings``/``out_shardings`` derived from ``sharding/rules``
(particle axis leading, within-particle rules on the trailing dims),
``donate_argnums`` on the stacked state so multi-epoch training never
leaves the device (XLA reuses the buffers in place), and ``vmap(...,
spmd_axis_name=particle_axis)`` so GSPMD distributes particles across the
mesh. With ``Placement(mesh=None)`` the same specs degrade to plain
single-device jit — one code path, placement decided by shardings; the
process-wide ProgramCache dedupes compiles across train/predict/serve.
EXPERIMENTS.md §Perf quantifies NEL vs compiled on identical SVGD
workloads.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from .store import Placement


def init_stacked(module, n: int, rng):
    """n independent inits, stacked on a leading particle axis."""
    return jax.vmap(module.init)(jax.random.split(rng, n))


def stack_pytrees(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def unstack_pytree(stacked, n: int):
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(n)]


def flatten_stacked(stacked):
    """(pytree with leading n) -> (n, D) matrix + unravel for one particle."""
    one = jax.tree.map(lambda x: x[0], stacked)
    _, unravel = ravel_pytree(one)
    flat = jax.vmap(lambda t: ravel_pytree(t)[0])(stacked)
    return flat, unravel


# ---------------------------------------------------------------------------
# active-mask helpers (elastic lifecycle, DESIGN.md §9): stacked trees are
# capacity-padded; ``mask`` is the store's (capacity,) active mask. Every
# masked op uses ``where`` (not multiply) so garbage in dead slots — even
# NaN — can never leak into live results.
# ---------------------------------------------------------------------------

def expand_mask(mask, ndim: int):
    """(P,) mask broadcast-shaped against a (P, ...) array of `ndim`."""
    return mask.reshape(mask.shape + (1,) * (ndim - 1))


def masked_select(mask, new_tree, old_tree):
    """Per-slot select: live slots take `new`, dead slots keep `old`
    (the frozen padding row) — the update rule of every masked train
    step, so dead slots never accumulate garbage."""
    return jax.tree.map(
        lambda nw, od: jnp.where(expand_mask(mask, nw.ndim) > 0, nw, od),
        new_tree, old_tree)


def masked_mean(tree, mask):
    """Mean over the leading particle axis restricted to live slots."""
    live = jnp.maximum(jnp.sum(mask), 1.0)
    return jax.tree.map(
        lambda o: jnp.sum(
            jnp.where(expand_mask(mask, o.ndim) > 0, o, 0.0), axis=0) / live,
        tree)


def ensemble_value_and_grad(loss_fn: Callable,
                            spmd_axis_name: Optional[str] = None):
    """vmap over particles; each particle sees the same batch (deep-ensemble
    semantics, paper §3.1) unless the batch itself has a particle axis."""
    vag = jax.value_and_grad(lambda p, b: loss_fn(p, b)[0])

    def f(stacked_params, batch):
        return jax.vmap(vag, in_axes=(0, None),
                        spmd_axis_name=spmd_axis_name)(stacked_params, batch)

    return f


def ensemble_step(loss_fn: Callable, optimizer,
                  spmd_axis_name: Optional[str] = None,
                  compute_dtype=None):
    """One compiled train step for all particles: grads + optimizer update.

    ``mask=None`` is the dense form; with a (capacity,) active mask, dead
    slots keep their params/opt state bit-for-bit (frozen padding rows)
    and report loss 0.0.

    ``compute_dtype`` is the mixed-precision split (DESIGN.md §13): the
    loss/grad pass runs on a *traced* cast of the masters (and of the
    batch's float leaves), gradients are cast back per-leaf to each
    master's dtype, and the optimizer update applies against the fp32
    masters — all inside this one donated program, so the compute copy
    never exists as a store key, never costs an H2D, and never bumps the
    generation. ``None`` keeps the default path bit-identical to the
    pre-policy code."""
    vag = ensemble_value_and_grad(loss_fn, spmd_axis_name)

    def step(stacked_params, stacked_opt_state, batch, mask=None):
        # device-side names: a profile splits the step's time into the
        # forward/backward pass and the optimizer update
        with jax.named_scope("push.loss_grad"):
            if compute_dtype is not None:
                from .precision import cast_floats
                losses, grads = vag(
                    cast_floats(stacked_params, compute_dtype),
                    cast_floats(batch, compute_dtype))
                grads = jax.tree.map(lambda g, p: g.astype(p.dtype),
                                     grads, stacked_params)
                losses = losses.astype(jnp.float32)
            else:
                losses, grads = vag(stacked_params, batch)
        with jax.named_scope("push.optimizer"):
            new_p, new_s = jax.vmap(optimizer.update,
                                    spmd_axis_name=spmd_axis_name)(
                stacked_params, grads, stacked_opt_state)
            if mask is not None:
                new_p = masked_select(mask, new_p, stacked_params)
                new_s = masked_select(mask, new_s, stacked_opt_state)
                losses = jnp.where(mask > 0, losses, 0.0)
        return new_p, new_s, losses

    return step


def ensemble_predict(forward: Callable,
                     spmd_axis_name: Optional[str] = None):
    """hat f(x) = (1/n) sum_i nn_{theta_i}(x) — one fused program; with a
    mask, the BMA averages live slots only."""

    def f(stacked_params, batch, mask=None):
        outs = jax.vmap(forward, in_axes=(0, None),
                        spmd_axis_name=spmd_axis_name)(stacked_params, batch)
        if mask is None:
            return jax.tree.map(lambda o: jnp.mean(o, axis=0), outs)
        return masked_mean(outs, mask)

    return f


# ---------------------------------------------------------------------------
# mesh-aware compilation — kept as thin entry points that delegate to the
# runtime layer (repro.runtime): the spec builders below name the program,
# the ProgramCache lowers/jits/caches it. These helpers return the cached
# Program for the given example arguments; repeated calls with the same
# shapes are cache hits, not recompiles. (Lazy imports: core must not
# import repro.runtime at module load — runtime imports core.store.)
# ---------------------------------------------------------------------------

def compile_ensemble_step(loss_fn: Callable, optimizer,
                          placement: Optional[Placement],
                          stacked, opt_state, batch, mask=None, *,
                          state_token=None):
    """One ensemble train step against a placement plan.

    State shardings come from the placement (particle axis + rules); the
    batch is replicated (every particle sees the same data). The stacked
    params/opt buffers are donated: across a multi-epoch loop the state
    never leaves the device — write-back happens once, at commit time.

    Pass ``mask=store.active_mask()`` to get the capacity-padded masked
    program the fused epoch loops run, and
    ``state_token=store.generation()`` to share the cache entry with
    programs the Runtime lowered against that store."""
    from ..runtime import global_cache, specs
    args = (stacked, opt_state, batch)
    if mask is not None:
        args += (mask,)
    return global_cache().program(specs.ensemble_step(loss_fn, optimizer),
                                  placement, args, state_token)


def compile_ensemble_predict(forward: Callable,
                             placement: Optional[Placement], stacked, batch,
                             mask=None, *, state_token=None):
    """The fused posterior-predictive program against a placement."""
    from ..runtime import global_cache, specs
    args = (stacked, batch) + (() if mask is None else (mask,))
    return global_cache().program(specs.ensemble_predict(forward),
                                  placement, args, state_token)


def compile_map_step(fn: Callable, placement: Optional[Placement],
                     *stacked_args, state_token=None):
    """A per-particle map (e.g. SWAG moment collection) over stacked
    state trees, sharded and donated like the train step.

    NOTE: the cache keys on ``fn``'s identity — pass a module-level (or
    otherwise long-lived) function; a fresh lambda per call defeats the
    cache and cold-compiles every time (bounded only by the cache's LRU)."""
    from ..runtime import global_cache, ident, specs
    spec = specs.map_step(fn, key=(ident(fn),), n_state=len(stacked_args))
    return global_cache().program(spec, placement, stacked_args, state_token)
