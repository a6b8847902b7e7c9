"""Faults planted under the timed path, to show that ``correct`` catches
them: the CPU tests plant them at a small size, and
``bench/tools/calibrate.py --fault`` plants them on the chip at the
cell's own size. Each takes a ``setattr(obj, name, value)`` callable
(pytest's ``monkeypatch.setattr``, or ``Patches.setattr``) so that it can
be undone.

  train_state_unchanged  the fused ensemble step returns its params and
                         optimizer state unchanged
  train_half_batch       the loss is taken over the first half of each
                         batch
  serve_altered_token    the decode step's sampled token is moved by one
  serve_half_particles   the BMA heads are taken over half the particles
  serve_pages_unchanged  the decode step returns the KV pages unchanged
"""
from __future__ import annotations

import dataclasses


class Patches:
    """Minimal undoable ``setattr`` for runs outside pytest."""

    def __init__(self):
        self._undo = []

    def setattr(self, obj, name, value):
        self._undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def undo(self):
        while self._undo:
            obj, name, value = self._undo.pop()
            setattr(obj, name, value)


def train_state_unchanged(setattr):
    from repro.core import functional
    orig = functional.ensemble_step

    def frozen(loss_fn, optimizer, spmd_axis_name=None, compute_dtype=None):
        step = orig(loss_fn, optimizer, spmd_axis_name, compute_dtype)

        def run(params, opt_state, batch, mask=None):
            return (params, opt_state) + tuple(
                step(params, opt_state, batch, mask)[2:])
        return run

    setattr(functional, "ensemble_step", frozen)


def train_half_batch(setattr):
    import jax
    from repro.models import api
    orig = api.loss_fn

    def half(params, batch, cfg):
        return orig(params, jax.tree.map(lambda x: x[:x.shape[0] // 2],
                                         batch), cfg)

    setattr(api, "loss_fn", half)


def serve_altered_token(setattr):
    from repro.serve import engine
    orig = engine.PagedDecodeEngine._reduce_fn

    def reduce_fn(self):
        f = orig(self)

        def g(member_logits, mask, ctx):
            out = f(member_logits, mask, ctx)
            return dict(out, token=(out["token"] + 1)
                        % member_logits.shape[-1])
        return g

    setattr(engine.PagedDecodeEngine, "_reduce_fn", reduce_fn)


def serve_half_particles(setattr):
    from repro.serve import engine
    orig = engine._bma_reduce_heads

    def half(outs, placement, n, kind, mask=None):
        k = max(1, outs.shape[0] // 2)
        return orig(outs[:k], placement, k, kind,
                    None if mask is None else mask[:k])

    setattr(engine, "_bma_reduce_heads", half)


def serve_pages_unchanged(setattr):
    from repro.serve import engine
    orig = engine.paged_decode_step

    def stale(decode_fn, reduce_fn, *, key):
        spec = orig(decode_fn, reduce_fn, key=key)

        def make(ctx):
            f = spec.make(ctx)

            def g(params, pages, packed, mask):
                return f(params, pages, packed, mask)[0], pages
            return g
        return dataclasses.replace(spec, make=make)

    setattr(engine, "paged_decode_step", stale)


FAULTS = {f.__name__: f for f in (
    train_state_unchanged, train_half_batch, serve_altered_token,
    serve_half_particles, serve_pages_unchanged)}
