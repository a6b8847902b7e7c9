"""The particle abstraction (paper §3.2).

A particle wraps a NN with (1) local state — parameters, optimizer state,
user state —, (2) its own logical thread of execution (dispatches run on
its device's NEL worker), and (3) message passing: a receive dictionary
mapping messages to locally-defined functions, plus send/get primitives
returning PFutures.

The paper's Fig. 1 `_gather` runs on this API verbatim (modulo torch->jax):

    futures  = {pid: particle.get(pid) for pid in other_particles}
    views    = {pid: fut.wait() for pid, fut in futures.items()}
    views[other].view()
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from .messages import ParticleView, PFuture, snapshot
from .store import ParticleStore, StoreState


class ParticleModule:
    """Bundle of pure functions defining the NN a particle wraps.

    init(rng) -> params ; loss(params, batch) -> (scalar, metrics) ;
    forward(params, batch) -> outputs.

    The per-particle step/forward programs compile through the shared
    runtime layer (``repro.runtime.jit_program``), keyed on the loss /
    forward function identity — so every particle of a PD (and every PD
    over the same module) shares ONE compiled program, and NEL compiles
    show up in the same ProgramCache stats as fused and serving ones
    (``PushDistribution.stats()``). The Program is fetched from the
    cache once and memoized on the module: its plain-jit wrapper is
    shape-polymorphic (no shardings, no donation), so the per-dispatch
    hot path after the first call is a single attribute check — no
    process-wide lock, no key construction (the PR-1 executor's
    dispatch-throughput bar depends on this staying cheap).
    """

    def __init__(self, init: Callable, loss: Callable, forward: Callable,
                 cfg: Any = None):
        self.init = init
        self.loss = loss
        self.forward = forward
        self.cfg = cfg
        self._vag = lambda p, b: jax.value_and_grad(
            lambda pp: loss(pp, b)[0])(p)
        self._vag_progs: Dict[Optional[str], Any] = {}
        self._fwd_prog = None
        self._loss_prog = None

    def _value_and_grad(self, params, batch, compute_dtype=None):
        """Jitted value_and_grad; ``compute_dtype`` applies the same
        master/compute split as the fused path (core.functional) so NEL
        and compiled backends stay in numerical agreement under one
        precision policy: cast params+batch to the compute dtype inside
        the trace, cast grads back per-leaf, surface the loss fp32. The
        dtype is part of the program key (None keeps the original key)."""
        tok = str(jnp.dtype(compute_dtype)) if compute_dtype is not None \
            else None
        prog = self._vag_progs.get(tok)
        if prog is None:
            from ..runtime import ident, jit_program
            if compute_dtype is None:
                fn = self._vag
                key = ("nel_value_and_grad", ident(self.loss))
            else:
                from .precision import cast_floats

                def fn(p, b):
                    l, g = self._vag(cast_floats(p, compute_dtype),
                                     cast_floats(b, compute_dtype))
                    g = jax.tree.map(
                        lambda gg, pp: gg.astype(pp.dtype), g, p)
                    return l.astype(jnp.float32), g
                key = ("nel_value_and_grad", ident(self.loss), tok)
            prog = jit_program("nel_value_and_grad", key, fn,
                               (params, batch))
            self._vag_progs[tok] = prog
        return prog(params, batch)

    def _forward(self, params, batch):
        if self._fwd_prog is None:
            from ..runtime import ident, jit_program
            self._fwd_prog = jit_program(
                "nel_forward", ("nel_forward", ident(self.forward)),
                self.forward, (params, batch))
        return self._fwd_prog(params, batch)

    def _loss_value(self, params, batch):
        """Jitted scalar loss (no grads) through the shared cache — one
        compiled program for all particles; lifecycle weight policies
        evaluate this per member without retracing the forward."""
        if self._loss_prog is None:
            from ..runtime import ident, jit_program
            self._loss_prog = jit_program(
                "nel_loss", ("nel_loss", ident(self.loss)),
                lambda p, b: self.loss(p, b)[0], (params, batch))
        return self._loss_prog(params, batch)


class Particle:
    def __init__(self, pid: int, nel, module: ParticleModule, params,
                 optimizer=None, opt_state=None, state: Optional[dict] = None,
                 store: Optional[ParticleStore] = None,
                 write_state: bool = True):
        self.pid = pid
        self.nel = nel
        self.module = module
        self.optimizer = optimizer
        # All per-particle state lives in the (possibly shared) ParticleStore;
        # ``state`` is this particle's mapping view of it (store.py). A
        # standalone particle gets a private store so the API is unchanged.
        # ``write_state=False`` attaches to state the caller already put
        # in the store (p_clone's fused slot copy).
        if store is None:
            store = ParticleStore()
            store.register(pid)
        self.store = store
        self.state: StoreState = StoreState(store, pid)
        if write_state:
            for k, v in (state or {}).items():
                self.state[k] = v
            self.state["params"] = params
            self.state["opt_state"] = opt_state
            self.state["grads"] = None
        self.receive: Dict[str, Callable] = {}

    # -- local state access ------------------------------------------------
    def parameters(self):
        return self.state["params"]

    def gradients(self):
        return self.state["grads"]

    # -- registry ------------------------------------------------------------
    def particle_ids(self) -> List[int]:
        return self.nel.particle_ids()

    def on(self, msg: str, fn: Callable):
        self.receive[msg] = fn

    # -- messaging (actor + async-await) ------------------------------------
    def send(self, pid: int, msg: str, *args, **kwargs) -> PFuture:
        """Trigger `msg`'s handler on particle `pid` (its own timeline)."""
        target = self.nel.particle(pid)
        if msg not in target.receive:
            raise KeyError(f"particle {pid} has no handler for {msg!r}")
        if self.nel._device_of[pid] != self.nel._device_of[self.pid]:
            self.nel._bump("xdev_transfers")
        fn = target.receive[msg]
        return self.nel.dispatch(pid, fn, target, *args, **kwargs)

    def get(self, pid: int) -> PFuture:
        """Asynchronously snapshot particle `pid`'s parameters (read-only)."""
        target = self.nel.particle(pid)
        if self.nel._device_of[pid] != self.nel._device_of[self.pid]:
            self.nel._bump("xdev_transfers")

        def grab(_t):
            return ParticleView(pid, snapshot(_t.state["params"]),
                                None if _t.state["grads"] is None
                                else snapshot(_t.state["grads"]))

        # lock-free read: runs on the shared pool, never queues behind the
        # target device's compute (paper §4.2 — same-device communication
        # "can be eliminated")
        return self.nel.dispatch(pid, grab, target, lightweight=True)

    # -- local NN computations (dispatched to this particle's device) -------
    def step(self, batch) -> PFuture:
        """Forward+backward+optimizer update on this particle's device."""

        def do(_self):
            loss, grads = _self.module._value_and_grad(
                _self.state["params"], batch,
                getattr(_self, "compute_dtype", None))
            _self.state["grads"] = grads
            if _self.optimizer is not None:
                p, s = _self.optimizer.update(_self.state["params"], grads,
                                              _self.state["opt_state"])
                _self.state["params"], _self.state["opt_state"] = p, s
            return loss

        return self.nel.dispatch(self.pid, do, self, needs_device=True)

    def grad(self, batch) -> PFuture:
        """Backward only: stash grads, do not update params (SVGD phase 1)."""

        def do(_self):
            loss, grads = _self.module._value_and_grad(
                _self.state["params"], batch,
                getattr(_self, "compute_dtype", None))
            _self.state["grads"] = grads
            return loss

        return self.nel.dispatch(self.pid, do, self, needs_device=True)

    def forward(self, batch) -> PFuture:
        def do(_self):
            return _self.module._forward(_self.state["params"], batch)

        return self.nel.dispatch(self.pid, do, self, needs_device=True)

    def apply_update(self, update, lr: float) -> PFuture:
        """theta <- theta - lr * update (SVGD follow; paper Fig. 6)."""

        def do(_self):
            # the update may come from a leader on another device
            def upd(p, u):
                if u.sharding != p.sharding:
                    u = jax.device_put(u, p.sharding)
                return p - lr * u.astype(p.dtype)

            _self.state["params"] = jax.tree.map(
                upd, _self.state["params"], update)
            return None

        return self.nel.dispatch(self.pid, do, self, needs_device=True)
