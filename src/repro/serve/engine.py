"""PredictiveEngine: one fused Bayesian-model-averaging forward per request.

The serving head of the runtime layer (DESIGN.md §8): the engine builds
*ProgramSpecs* — a single XLA program that runs *all* particles over the
store's stacked axis (``vmap(forward, spmd_axis_name=...)``), computes
every uncertainty head (serve/uncertainty.py) inside that program, and
reduces over the particle axis **on device** — on a mesh placement the
member outputs are sharding-constrained particle-sharded for the local
math, then constrained replicated, which GSPMD lowers to an all-gather
over the particle axis (the same transition pattern as SVGD's kernel
matrix, DESIGN.md §6) rather than a host round trip.

Stacked params are read straight from the ParticleStore (``stacked()``
returns the canonical placed tree; the store's version counter lets the
engine cache the reference between commits), so serving never unshards,
restacks, or re-places particle state — the sharded subprocess test
asserts those stats stay flat across requests.

Compilation and caching are the shared ProgramCache's job: request
batches are padded up to the next power of two (runtime.bucketing), the
cache key carries (spec, placement, store generation, bucketed shapes) —
so an engine serving mixed batch sizes holds one program per bucket, a
second engine over the same store+module compiles NOTHING, and training
commits (which bump the version but not the generation) never invalidate
serving programs.

Elastic stores (DESIGN.md §9) serve through the same programs under
clone/kill churn: the stacked tree is capacity-padded (shapes are
churn-invariant), the store's active mask is re-read per request and
threaded in as a replicated runtime value, and every particle-axis head
is mask-weighted over live slots — so p_clone/p_kill between requests
change WHAT is served, never what is compiled, and in-flight requests
drain against the mask and buffers they already read.

Two program shapes:

  predict(batch)        stateless BMA forward     forward(params, batch)
  step(state, batch)    stateful serving (LM decode: per-particle KV
                        caches ride the stacked axis and never leave the
                        device)                    forward(params, state,
                                                   batch) -> (out, state)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import precision as precision_mod
from ..core.store import ParticleStore, Placement
from ..runtime import (ProgramCache, ProgramSpec, abstract_key, bucket_size,
                       global_cache, ident, pad_rows)
from ..runtime.specs import paged_decode_step, paged_prefill
from . import uncertainty


def _leading(tree) -> int:
    return jax.tree.leaves(tree)[0].shape[0]


def _bma_reduce_heads(outs, placement: Placement, n: int, kind: str,
                      mask=None):
    """Heads from stacked member outputs, with the particle-axis
    reduction expressed as sharding-constraint transitions. ``mask`` is
    the (capacity,) active mask of an elastic store — the heads weight
    live slots only (replicated alongside the gathered outputs, so the
    masked reduction is local to every device)."""
    if placement.mesh is not None:
        row_sh = placement.vector(n)           # P(particle_axis), rest ∅
        outs = jax.lax.with_sharding_constraint(outs, row_sh)
        # the BMA all-to-all as one on-device collective: every device
        # gets all members' outputs, then reduces locally (replicated)
        outs = jax.lax.with_sharding_constraint(
            outs, placement.replicated(outs))
    with jax.named_scope("push.bma_heads"):
        return uncertainty.predictive_heads(outs, kind, mask), outs


class PredictiveEngine:
    """Compiled posterior-predictive service core over a ParticleStore.

    Parameters
    ----------
    forward:    stateless mode — ``forward(params_row, batch) -> outputs``
                (leading batch axis); stateful mode (``stateful=True``) —
                ``forward(params_row, state_row, batch) -> (out, state)``.
    store/key:  where stacked params live (the PD's store). Mutually
                exclusive with ``params`` — a static stacked tree (e.g.
                serve-time SWAG samples, bdl/swag.py).
    placement:  mesh plan; defaults to the store's. Decides the particle
                axis sharding + the on-device BMA all-gather.
    kind:       "classify" (member outputs are logits) or "regress".
    cache:      ProgramCache override (tests); defaults process-wide.
    """

    def __init__(self, forward: Callable, *,
                 store: Optional[ParticleStore] = None, key: str = "params",
                 params: Any = None, placement: Optional[Placement] = None,
                 kind: str = "classify", stateful: bool = False,
                 cache: Optional[ProgramCache] = None, precision: Any = None):
        if (store is None) == (params is None):
            raise ValueError("pass exactly one of store= or params=")
        if kind not in uncertainty.KINDS:
            raise ValueError(f"kind must be one of {uncertainty.KINDS}")
        self.forward = forward
        self.store = store
        self.key = key
        self.kind = kind
        self.stateful = stateful
        # serve-side precision ladder (DESIGN.md §13): explicit arg >
        # the store's policy > fp32. ``casts_serve`` engines maintain a
        # version-memoized serve-dtype (optionally int8-quantized) copy
        # of the stacked params; the fp32 default is a zero-overhead
        # pass-through (identical programs to the pre-policy code).
        if precision is None and store is not None:
            precision = getattr(store, "precision", None)
        self.precision = precision_mod.get(precision)
        if placement is None:
            placement = store.placement if store is not None else Placement()
        self.placement = placement
        # explicit None test: an *empty* ProgramCache is falsy (__len__)
        self.cache = cache if cache is not None else global_cache()
        self._static_params = params
        if params is not None and placement.mesh is not None:
            self._static_params = jax.device_put(
                params, placement.shardings(params))
        if params is not None and self.precision.casts_serve:
            # one-time transform for static trees (serve-time SWAG
            # samples): eager is fine here, there is no commit cadence
            self._static_params = precision_mod.cast_for_serve(
                self._static_params, self.precision)
        self._static_mask: Any = None
        self._live_idx: Any = None      # (mask object, live row indices)
        self._params_version: Any = None
        self._params_cache: Any = None
        # hot-path memos: the abstract key of the (large) stacked-params
        # tree is recomputed only on store refresh, and the ProgramSpecs
        # (whose construction takes the ident() token lock) are built
        # once per engine — a request's host cost is one cache lookup
        # over the (small) batch shapes
        self._params_key: Any = None
        self._spec_memo: Dict[Any, ProgramSpec] = {}
        self._keys = set()
        self.stats = {"calls": 0, "compiles": 0, "bucket_hits": 0,
                      "param_refreshes": 0}
        if self._static_params is not None:
            self._params_key = abstract_key(self._static_params)

    # -- stacked params ------------------------------------------------------
    def stacked_params(self):
        """The canonical stacked params, cached between store commits
        (store.version) so the hot path is one dict lookup — and never a
        reshard: the store hands back the already-placed tree."""
        if self._static_params is not None:
            return self._static_params
        v = self.store.version(self.key)
        if v != self._params_version:
            self._refresh_params(v, self.store.stacked(self.key))
        return self._params_cache

    def _serve_cast_spec(self) -> ProgramSpec:
        memo = self._spec_memo.get("serve_cast")
        if memo is not None:
            return memo
        prec = self.precision
        spec = ProgramSpec(
            name="serve_cast",
            # no ident(): every engine over any store shares one compiled
            # cast per (policy, placement, shapes) — a second service on
            # the same store compiles nothing, and churn re-runs it warm
            key=("serve_cast",),
            make=lambda ctx: lambda stacked: precision_mod.cast_for_serve(
                stacked, prec),
            in_kinds=("state",),
            out_kinds=None,
            precision=prec.key())
        self._spec_memo["serve_cast"] = spec
        return spec

    def _refresh_params(self, version, stacked):
        """Install a freshly flushed stacked tree in the memo. Shapes are
        capacity-padded, so the abstract key can only change with the
        generation — content edits (incl. clone/kill churn) refresh the
        tree reference without re-walking it.

        Under a ``casts_serve`` policy the memo holds the serve copy —
        cast (and optionally int8-packed) from the masters by one
        compiled program per store commit. The copy is a derived value:
        never a store key, never committed back."""
        if self.precision.casts_serve:
            stacked = self.cache.run(self._serve_cast_spec(), stacked,
                                     placement=self.placement,
                                     state_token=self._state_token())
        self._params_cache = stacked
        if self._params_version is None \
                or version[0] != self._params_version[0]:
            self._params_key = abstract_key(stacked)
        self._params_version = version
        self.stats["param_refreshes"] += 1

    def active_mask(self):
        """The store's (capacity,) live-slot mask, re-read per request —
        THIS is what lets clone/kill churn between requests take effect
        with zero recompiles (mask content is a runtime value, not part
        of any cache key). Static-params engines serve a dense all-ones
        mask."""
        if self.store is not None:
            return self.store.active_mask()
        if self._static_mask is None:
            self._static_mask = jnp.ones((_leading(self._static_params),),
                                         jnp.float32)
        return self._static_mask

    def _mask_and_params(self):
        """Consistent (mask, stacked params) pair under concurrent
        churn: one atomic ``store.snapshot`` under the store lock, so a
        mask bit can never go live before its slot's data landed and
        capacity growth can never split the pair. The engine-side memos
        (params reference, abstract key) refresh off the snapshot's
        version exactly as ``stacked_params`` would."""
        if self.store is None:
            return self.active_mask(), self.stacked_params()
        v, mask, stacked = self.store.snapshot(self.key)
        if v != self._params_version:
            self._refresh_params(v, stacked)
        return mask, self._params_cache

    @property
    def num_particles(self) -> int:
        """Leading member axis of the served stacked tree — the store's
        capacity for elastic stores (use ``store.live_count()`` for the
        live member count)."""
        return _leading(self.stacked_params())

    def _state_token(self):
        """Store generation for the ProgramCache key (particle-set
        changes recompile; content commits do not); static-params
        engines key purely on shapes."""
        return self.store.generation() if self.store is not None else None

    # -- ProgramSpec builders ------------------------------------------------
    def _predict_spec(self, members: bool) -> ProgramSpec:
        memo = self._spec_memo.get(("predict", members))
        if memo is not None:
            return memo
        fwd, kind, prec = self.forward, self.kind, self.precision

        def make(ctx):
            def fused(stacked_params, b, mask):
                if prec.casts_serve:
                    # dequantize int8 packs / finish the serve cast at
                    # trace top (a no-op on already-cast leaves), cast
                    # the batch floats alongside; the uncertainty heads
                    # reduce in fp32 regardless of the member dtype
                    stacked_params = precision_mod.dequantize(stacked_params,
                                                              prec.serve)
                    b = precision_mod.cast_floats(b, prec.serve)
                outs = jax.vmap(fwd, in_axes=(0, None),
                                spmd_axis_name=ctx.spmd_axis)(
                    stacked_params, b)
                if prec.casts_serve:
                    outs = precision_mod.cast_floats(outs, jnp.float32)
                heads, outs_rep = _bma_reduce_heads(outs, ctx.placement,
                                                    ctx.num_particles, kind,
                                                    mask)
                return (heads, outs_rep) if members else heads

            return fused

        spec = ProgramSpec(
            name="bma_predict",
            key=("bma_predict", ident(fwd), kind, members),
            make=make,
            in_kinds=("state", "replicated", "replicated"),
            out_kinds=("replicated",),
            precision=prec.key() if prec.casts_serve else None)
        self._spec_memo[("predict", members)] = spec
        return spec

    def _step_spec(self) -> ProgramSpec:
        memo = self._spec_memo.get("step")
        if memo is not None:
            return memo
        fwd, kind, prec = self.forward, self.kind, self.precision

        def make(ctx):
            def fused(stacked_params, st, b, mask):
                if prec.casts_serve:
                    stacked_params = precision_mod.dequantize(stacked_params,
                                                              prec.serve)
                    b = precision_mod.cast_floats(b, prec.serve)
                outs, new_st = jax.vmap(fwd, in_axes=(0, 0, None),
                                        spmd_axis_name=ctx.spmd_axis)(
                    stacked_params, st, b)
                if prec.casts_serve:
                    outs = precision_mod.cast_floats(outs, jnp.float32)
                heads, _ = _bma_reduce_heads(outs, ctx.placement,
                                             ctx.num_particles, kind, mask)
                return heads, new_st

            return fused

        spec = ProgramSpec(
            name="bma_step",
            key=("bma_step", ident(fwd), kind),
            make=make,
            in_kinds=("state", "rows", "replicated", "replicated"),
            out_kinds=("replicated", "in:1"),
            precision=prec.key() if prec.casts_serve else None)
        self._spec_memo["step"] = spec
        return spec

    def _program(self, spec: ProgramSpec, args):
        # args[0] is always the stacked params tree: reuse the key
        # memoized at refresh time instead of re-flattening per request
        arg_keys = (self._params_key,) + (None,) * (len(args) - 1)
        prog, hit = self.cache.lookup(spec, self.placement, args,
                                      self._state_token(), arg_keys)
        self._keys.add(prog.cache_key)
        self.stats["bucket_hits" if hit else "compiles"] += 1
        return prog

    # -- serving entry points ------------------------------------------------
    def predict(self, batch, members: bool = False):
        """Fused BMA forward over a request batch (leading axis B).

        Pads B up to the bucket, runs the cached program for that bucket,
        slices the heads back to B. ``members=True`` additionally returns
        the raw stacked member outputs (P, B, ...)."""
        if self.stateful:
            raise RuntimeError("stateful engine: use step(state, batch)")
        self.stats["calls"] += 1
        mask, stacked = self._mask_and_params()
        m = _leading(batch)
        padded = pad_rows(batch, bucket_size(m))
        prog = self._program(self._predict_spec(members),
                             (stacked, padded, mask))
        out = prog(stacked, padded, mask)
        heads, outs = out if members else (out, None)
        heads = jax.tree.map(lambda a: a[:m], heads)
        if members:
            # members keeps its pre-elastic contract: exactly the live
            # rows, slot order (a host-side gather on the replicated
            # outputs — per-request live counts never touch the program).
            # Live indices memoized on the mask object, which the store
            # caches between lifecycle events: no per-request device sync
            if self._live_idx is None or self._live_idx[0] is not mask:
                self._live_idx = (mask,
                                  np.flatnonzero(np.asarray(mask) > 0))
            live = self._live_idx[1]
            return heads, jax.tree.map(lambda a: a[live, :m], outs)
        return heads

    def step(self, state, batch):
        """One stateful serving step (LM decode): per-particle state (KV
        caches, leading axis P) stays stacked and on device across steps.
        Returns (heads, new_state)."""
        if not self.stateful:
            raise RuntimeError("stateless engine: use predict(batch)")
        self.stats["calls"] += 1
        mask, stacked = self._mask_and_params()
        prog = self._program(self._step_spec(), (stacked, state, batch, mask))
        return prog(stacked, state, batch, mask)

    def init_state(self, make_state: Callable):
        """Build stacked per-particle serving state: ``make_state(row)``
        maps one particle's params to its state (e.g. prefill -> caches);
        vmapped over the stacked axis so state is born sharded."""
        stacked = self.stacked_params()
        prec = self.precision

        def make(ctx):
            row_fn = make_state
            if prec.casts_serve:
                # the memoized copy may hold int8 packs: expand per row
                # so make_state sees ordinary float params
                def row_fn(row):
                    return make_state(precision_mod.dequantize(row,
                                                               prec.serve))
            return jax.vmap(row_fn, spmd_axis_name=ctx.spmd_axis)

        spec = ProgramSpec(
            name="serve_init_state",
            key=("serve_init_state", ident(make_state)),
            make=make,
            in_kinds=("state",),
            precision=prec.key() if prec.casts_serve else None)
        # not counted in the request-path compile stats: state init is a
        # one-off setup call, not part of the serving hot path
        return self.cache.run(spec, stacked,
                              placement=self.placement,
                              state_token=self._state_token())

    def snapshot_stats(self) -> Dict[str, int]:
        return dict(self.stats, programs=len(self._keys),
                    program_cache=self.cache.snapshot_stats())


class PagedDecodeEngine(PredictiveEngine):
    """Continuous-batching LM decode core over the paged KV pool.

    Two fixed-shape programs ride the shared ProgramCache:

      decode_step(packed)   one token for every active row — params and
                            pages stacked over the particle axis, BMA +
                            greedy sampling fused on device, pages
                            donated (in-place pool update);
      prefill(packed)       admit one sequence: chunked prompt prefill
                            into its pages + the first sampled token
                            (one program per pow2 prompt bucket).

    The pages tree lives in the store under ``pages_key`` and crosses
    each call by checkout/commit — content-version bumps only, so churn
    in page ownership or page contents never recompiles anything; the
    cache key carries the store *generation* exactly like params.

    Packed input layouts are the runtime.specs contract: decode ships
    ``(B, 2 + n_pmax)`` i32 (tokens / seq_lens / block tables), prefill
    ships ``(Sp + n_pmax + 1,)`` i32 (tokens / block row / n_tokens) —
    ONE H2D transfer per scheduler step by construction.
    """

    def __init__(self, decode_fn: Callable, prefill_fn: Callable, *,
                 store: ParticleStore, n_pmax: int, key: str = "params",
                 pages_key: str = "kv_pages",
                 placement: Optional[Placement] = None,
                 cache: Optional[ProgramCache] = None, precision: Any = None):
        super().__init__(decode_fn, store=store, key=key, kind="classify",
                         placement=placement, cache=cache,
                         precision=precision)
        if self.precision.serve_quant is not None:
            # int8 packing is a BMA-forward optimization; the decode path
            # serves the plain serve-dtype cast (pages dominate its HBM)
            self.precision = dataclasses.replace(self.precision,
                                                 serve_quant=None)
        self.decode_fn = decode_fn
        self.prefill_fn = prefill_fn
        self.pages_key = pages_key
        self.n_pmax = n_pmax
        self._pages_gen = None
        self._pages_abs_key = None

    # -- fused BMA + sampling head -------------------------------------------
    def _reduce_fn(self):
        kind = self.kind

        def reduce_fn(member_logits, mask, ctx):
            heads, _ = _bma_reduce_heads(member_logits, ctx.placement,
                                         ctx.num_particles, kind, mask)
            mean = heads["mean"]                        # (B, V) BMA probs
            token = jnp.argmax(mean, axis=-1).astype(jnp.int32)
            logprob = jnp.log(jnp.take_along_axis(
                mean, token[:, None], axis=-1)[:, 0] + 1e-12)
            return {"token": token, "logprob": logprob,
                    "entropy": heads["entropy"],
                    "mutual_info": heads["mutual_info"]}

        return reduce_fn

    def _decode_spec(self) -> ProgramSpec:
        memo = self._spec_memo.get("paged_decode")
        if memo is None:
            memo = paged_decode_step(
                self.decode_fn, self._reduce_fn(),
                key=(ident(self.decode_fn), self.kind))
            if self.precision.casts_serve:
                memo = dataclasses.replace(memo,
                                           precision=self.precision.key())
            self._spec_memo["paged_decode"] = memo
        return memo

    def _prefill_spec(self) -> ProgramSpec:
        memo = self._spec_memo.get("paged_prefill")
        if memo is None:
            memo = paged_prefill(
                self.prefill_fn, self._reduce_fn(), n_pmax=self.n_pmax,
                key=(ident(self.prefill_fn), self.kind))
            if self.precision.casts_serve:
                memo = dataclasses.replace(memo,
                                           precision=self.precision.key())
            self._spec_memo["paged_prefill"] = memo
        return memo

    def kv_page_info(self) -> Dict[str, Any]:
        """Dtype + bytes gauges for the paged KV pool (DecodeScheduler
        stats / obs.device): leaf dtype histogram and total store bytes
        of the ``pages_key`` tree."""
        info: Dict[str, Any] = {"key": self.pages_key}
        try:
            info["dtypes"] = self.store.key_dtypes(self.pages_key)
            info["per_device_bytes"] = \
                self.store.per_device_bytes(self.pages_key)
        except Exception:
            info["dtypes"] = {}
        return info

    # -- pages checkout/commit ------------------------------------------------
    def _checkout_pages(self):
        pages = self.store.checkout(self.pages_key)
        gen = self.store.generation()
        if gen != self._pages_gen:
            # capacity-padded shapes: the abstract key can only change
            # with the generation, so churn steps skip the tree walk
            self._pages_abs_key = abstract_key(pages)
            self._pages_gen = gen
        return pages

    def _run_paged(self, spec: ProgramSpec, packed):
        self.stats["calls"] += 1
        mask, params = self._mask_and_params()
        pages = self._checkout_pages()
        try:
            args = (params, pages, packed, mask)
            prog, hit = self.cache.lookup(
                spec, self.placement, args, self._state_token(),
                (self._params_key, self._pages_abs_key, None, None))
            self._keys.add(prog.cache_key)
            self.stats["bucket_hits" if hit else "compiles"] += 1
            heads, new_pages = prog(*args)
        except BaseException:
            # return the (possibly donated-and-dead on a mid-execute
            # failure, but always schema-correct) tree so the store key
            # stays present for the next caller
            self.store.commit(self.pages_key, pages)
            raise
        self.store.commit(self.pages_key, new_pages)
        return heads

    # -- serving entry points -------------------------------------------------
    def decode_step(self, packed):
        """packed: (B, 2 + n_pmax) i32 host array — [tokens, seq_lens,
        block tables]; rows with seq_len -1 are inactive (their heads are
        garbage — mask downstream). Returns the heads tree on device."""
        return self._run_paged(self._decode_spec(), packed)

    def prefill(self, packed):
        """packed: (Sp + n_pmax + 1,) i32 host array — [prompt tokens
        padded to the Sp bucket, block table row, n_tokens]. Returns
        heads for the first generated token (leading axis 1)."""
        return self._run_paged(self._prefill_spec(), packed)
