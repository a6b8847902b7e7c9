"""Speculative BMA decoding: one particle drafts, the ensemble verifies
(DESIGN.md §14).

The fused-BMA decode program (DESIGN.md §10) pays its dispatch + particle
fan-out once PER TOKEN. But any single particle is a cheap approximation
of the full Bayesian model average — so let it run ahead: a designated
*draft particle* autoregressively proposes K tokens per sequence through
the existing single-row paged decode path (ONE program with an internal
``lax.scan``, the argmax fed back in-trace), then ONE fused verify
program scores the whole drafted window across every live particle at
once (``kernels.paged_decode_attention.paged_decode_window_attention``
streams each KV page once per window instead of once per token) and the
scheduler accepts the longest prefix on which the drafts match the BMA
argmax. Every emitted token IS a verify-output BMA argmax, so greedy
decode stays token-exact by construction — the draft particle's quality
affects only speed, never output.

Cache-key anatomy (the churn invariants of §9/§10 carry over):

  * program shapes are fixed at ``(max_active, k_max)`` — per-sequence
    adaptive K rides in the packed array as a runtime value (``k_len`` /
    ``win_len`` columns), so admission/retirement/preemption and any
    K schedule reuse the same two compiled programs;
  * the draft slot is a traced i32 scalar sliced with
    ``dynamic_index_in_dim`` — clone/kill churn re-picks the slot by
    re-uploading one scalar, never recompiling;
  * pages cross both programs by checkout/commit with donation, exactly
    like the plain decode step — no ``generation()`` bump anywhere in
    the steady loop.

Rollback protocol: the draft program writes the draft particle's KV for
positions ``n-1 .. n+k-2``; verify then overwrites them (bit-identical —
same tokens, same params row, same rope positions) and writes every
OTHER particle's window KV before attending, so after accepting m tokens
the pool is exactly what m sequential committed steps would have left
for positions ``<= n+m-2``. Device state past the accepted prefix is
stale-but-unreachable (the kernels mask on position), so rollback is
pure host page accounting: ``PagePool.release_tail`` returns any page
the rejected tail had crossed into.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import precision as precision_mod
from ..obs import clock
from ..obs import trace as _trace
from ..runtime import abstract_key, ident
from ..runtime.specs import spec_draft_step, spec_verify
from .batcher import DecodeScheduler, _Seq
from .engine import PagedDecodeEngine, _bma_reduce_heads


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative-decode policy knobs (``serve_decode(speculative=...)``).

    k_max:      drafted tokens per sequence per step (the program shape;
                per-sequence K adapts at runtime below it).
    adaptive:   drive per-sequence K from an acceptance-rate EMA (greedy
                text that the draft particle nails gets longer windows;
                disagreeing rows fall back toward K=1).
    ema_alpha:  EMA smoothing for the measured acceptance rate.
    ema_init:   optimistic prior (start at full K, shrink on evidence).
    quantized:  draft from an int8-quantized copy of the draft particle
                (rebuilt per params commit) instead of the live row —
                cheaper drafts, identical outputs (verify decides).
    """
    k_max: int = 4
    adaptive: bool = True
    ema_alpha: float = 0.3
    ema_init: float = 1.0
    quantized: bool = False

    def __post_init__(self):
        if self.k_max < 1:
            raise ValueError("k_max must be >= 1")
        if not 0.0 < self.ema_alpha <= 1.0:
            raise ValueError("ema_alpha must be in (0, 1]")


def resolve_spec_config(speculative) -> Optional[SpecConfig]:
    """``None``/``False`` -> off; ``True`` -> defaults; int -> that
    ``k_max``; a ``SpecConfig`` passes through."""
    if speculative is None or speculative is False:
        return None
    if speculative is True:
        return SpecConfig()
    if isinstance(speculative, SpecConfig):
        return speculative
    if isinstance(speculative, int):
        return SpecConfig(k_max=speculative)
    raise TypeError(f"speculative= takes None/bool/int/SpecConfig, "
                    f"got {type(speculative).__name__}")


class SpecDecodeEngine(PagedDecodeEngine):
    """PagedDecodeEngine + the two speculative programs.

      draft_step(packed, slot)   K greedy tokens from ONE particle row
                                 (or its int8 copy), scan fused — one
                                 dispatch per drafted window;
      verify_step(packed)        the W = K+1 token window scored by the
                                 whole ensemble in one pass, per-position
                                 BMA heads fused on device.

    Both ride the shared ProgramCache with fixed shapes; the draft slot
    is a device-cached scalar re-uploaded only when churn re-picks it.
    """

    def __init__(self, decode_fn: Callable, prefill_fn: Callable,
                 verify_fn: Callable, *, spec_cfg: SpecConfig, **kw):
        super().__init__(decode_fn, prefill_fn, **kw)
        self.verify_fn = verify_fn
        self.spec_cfg = spec_cfg
        self.k_max = spec_cfg.k_max
        self.w_max = spec_cfg.k_max + 1
        self._slot_dev: Any = None          # (value, device scalar)
        self._draft_slot_memo: Any = None   # (mask object, slot int)
        self._qpack: Any = None             # (params version, slot, pack, key)
        self.stats.setdefault("slot_uploads", 0)
        self.stats.setdefault("draft_packs", 0)

    # -- draft slot ----------------------------------------------------------
    def pick_draft_slot(self, mask) -> int:
        """First live slot of the store's active mask, memoized on the
        mask object (the store caches it between lifecycle events, so the
        host sync happens once per churn event, not per step)."""
        memo = self._draft_slot_memo
        if memo is not None and memo[0] is mask:
            return memo[1]
        live = np.flatnonzero(np.asarray(mask) > 0)
        if live.size == 0:
            raise RuntimeError("no live particles to draft from")
        slot = int(live[0])
        self._draft_slot_memo = (mask, slot)
        return slot

    def _slot_scalar(self, slot: int):
        if self._slot_dev is None or self._slot_dev[0] != slot:
            self._slot_dev = (slot, jnp.asarray(slot, jnp.int32))
            self.stats["slot_uploads"] += 1
        return self._slot_dev[1]

    # -- quantized draft pack ------------------------------------------------
    def _draft_params(self, params, slot: int):
        """The draft program's first operand: the full stacked tree (the
        program slices the row in-trace), or — quantized mode — an int8
        pack of the draft row, memoized per (params version, slot). The
        pack is a derived value: never a store key, never in the mask."""
        if not self.spec_cfg.quantized:
            return params, self._params_key
        version = self._params_version
        memo = self._qpack
        if memo is not None and memo[0] == version and memo[1] == slot:
            return memo[2], memo[3]
        # keepdims row slice: the leading axis of 1 keeps quantize_int8's
        # stacked-tree semantics (per-output-channel scales, >=3D leaves)
        row = jax.tree.map(lambda a: a[slot:slot + 1], params)
        pack = precision_mod.quantize_int8(row)
        key = abstract_key(pack)
        self._qpack = (version, slot, pack, key)
        self.stats["draft_packs"] += 1
        return pack, key

    def _dequant_dtype(self):
        prec = self.precision
        return prec.serve if prec.casts_serve else jnp.float32

    # -- ProgramSpecs --------------------------------------------------------
    def _draft_spec(self):
        memo = self._spec_memo.get("spec_draft")
        if memo is None:
            memo = spec_draft_step(
                self.decode_fn, k_max=self.k_max,
                key=(ident(self.decode_fn),),
                quantized=self.spec_cfg.quantized,
                dequant_dtype=self._dequant_dtype())
            if self.precision.casts_serve:
                memo = dataclasses.replace(memo,
                                           precision=self.precision.key())
            self._spec_memo["spec_draft"] = memo
        return memo

    def _verify_reduce_fn(self):
        kind = self.kind

        def reduce_fn(member_logits, mask, ctx):
            # member_logits: (P, B, W, V); the heads are per window
            # position — predictive_heads is shape-generic (softmax and
            # mask-weighting over the trailing/leading axes)
            heads, _ = _bma_reduce_heads(member_logits, ctx.placement,
                                         ctx.num_particles, kind, mask)
            mean = heads["mean"]                    # (B, W, V) BMA probs
            token = jnp.argmax(mean, axis=-1).astype(jnp.int32)  # (B, W)
            logprob = jnp.log(jnp.take_along_axis(
                mean, token[..., None], axis=-1)[..., 0] + 1e-12)
            return {"token": token, "logprob": logprob,
                    "entropy": heads["entropy"],
                    "mutual_info": heads["mutual_info"]}

        return reduce_fn

    def _verify_spec(self):
        memo = self._spec_memo.get("spec_verify")
        if memo is None:
            memo = spec_verify(
                self.verify_fn, self._verify_reduce_fn(), w_max=self.w_max,
                key=(ident(self.verify_fn), self.kind))
            if self.precision.casts_serve:
                memo = dataclasses.replace(memo,
                                           precision=self.precision.key())
            self._spec_memo["spec_verify"] = memo
        return memo

    # -- entry points --------------------------------------------------------
    def draft_step(self, packed, slot: int):
        """packed: (B, 3 + n_pmax) i32 host array — [last token, its
        position (-1 inactive), k_len, block tables]. Returns the
        (B, k_max) drafted token array (host side ignores entries past
        each row's k_len)."""
        self.stats["calls"] += 1
        mask, params = self._mask_and_params()
        del mask
        draft_params, draft_key = self._draft_params(params, slot)
        pages = self._checkout_pages()
        try:
            args = (draft_params, pages, packed, self._slot_scalar(slot))
            prog, hit = self.cache.lookup(
                self._draft_spec(), self.placement, args,
                self._state_token(),
                (draft_key, self._pages_abs_key, None, None))
            self._keys.add(prog.cache_key)
            self.stats["bucket_hits" if hit else "compiles"] += 1
            drafts, new_pages = prog(*args)
        except BaseException:
            self.store.commit(self.pages_key, pages)
            raise
        self.store.commit(self.pages_key, new_pages)
        return drafts

    def verify_step(self, packed):
        """packed: (B, w_max + 2 + n_pmax) i32 host array — [window
        tokens, window-start position (-1 inactive), win_len, block
        tables]. Returns the per-position heads tree (each (B, w_max))."""
        return self._run_paged(self._verify_spec(), packed)


class _SpecState:
    """Per-sequence adaptive-K state (side table keyed by sid — survives
    preemption/replay, dropped at retirement)."""
    __slots__ = ("ema", "k")

    def __init__(self, ema: float, k: int):
        self.ema = ema
        self.k = k


class SpeculativeDecodeScheduler(DecodeScheduler):
    """DecodeScheduler whose step loop drafts K tokens per sequence and
    verifies them in one fused pass — variable tokens per step, identical
    tokens to the plain scheduler.

    One iteration: admit (unchanged) -> ensure pages THROUGH the drafted
    window -> ONE draft program call (skipped when every row's K is 0) ->
    ONE verify call -> per row accept the longest draft prefix matching
    the BMA argmax (eos-truncated), roll rejected-tail pages back
    page-granularly, update the acceptance EMA, retire. Two dispatches
    per iteration amortized over up to ``(K+1) x rows`` emitted tokens is
    the entire speedup; correctness never depends on K.
    """

    def __init__(self, engine: SpecDecodeEngine, pool, **kw):
        super().__init__(engine, pool, **kw)
        cfg = engine.spec_cfg
        self.spec_cfg = cfg
        self.k_max = cfg.k_max
        self.w_max = cfg.k_max + 1
        self._spec_state: Dict[int, _SpecState] = {}
        # fixed-shape staging: draft [tok, pos, k_len, bt...], verify
        # [window tokens, pos, win_len, bt...] — refilled in place, one
        # H2D each per iteration
        self._draft_packed = np.zeros((self.max_active, 3 + self.n_pmax),
                                      np.int32)
        self._verify_packed = np.zeros(
            (self.max_active, self.w_max + 2 + self.n_pmax), np.int32)
        self.spec_stats: Dict[str, Any] = {
            "spec_steps": 0, "draft_calls": 0, "verify_calls": 0,
            "drafted_tokens": 0, "accepted_tokens": 0, "rollback_pages": 0,
        }

    # -- adaptive K ----------------------------------------------------------
    def _state_for(self, seq: _Seq) -> _SpecState:
        st = self._spec_state.get(seq.sid)
        if st is None:
            st = _SpecState(self.spec_cfg.ema_init, self.k_max)
            self._spec_state[seq.sid] = st
        return st

    def _plan_k(self, seq: _Seq) -> int:
        """Tokens to draft for ``seq`` this iteration: the adaptive-K
        target clipped to what the sequence can still emit (k <=
        remaining - 1 keeps every emitted token a verify output)."""
        remaining = seq.max_new - len(seq.generated)
        k = self._state_for(seq).k if self.spec_cfg.adaptive else self.k_max
        return max(0, min(k, remaining - 1, self.k_max))

    def _observe_acceptance(self, seq: _Seq, k: int, accepted: int):
        if not self.spec_cfg.adaptive or k < 1:
            return
        st = self._state_for(seq)
        a = self.spec_cfg.ema_alpha
        st.ema = (1.0 - a) * st.ema + a * (accepted / k)
        st.k = max(1, min(self.k_max, 1 + round(st.ema * (self.k_max - 1))))

    # -- step loop -----------------------------------------------------------
    def warmup(self, prompt_buckets=()):
        """Compile the draft + verify programs (all rows inactive: no
        page writes, pool untouched) and one prefill program per bucket.
        The single-token decode program is never used in speculative
        mode, so it is not warmed."""
        from .engine import bucket_size
        with self.step_lock:
            self._draft_packed[:] = 0
            self._draft_packed[:, 1] = -1
            jax.block_until_ready(jax.tree.leaves(
                self.engine.draft_step(self._draft_packed,
                                       self.engine.pick_draft_slot(
                                           self.engine.active_mask()))))
            self._verify_packed[:] = 0
            self._verify_packed[:, self.w_max] = -1
            jax.block_until_ready(jax.tree.leaves(
                self.engine.verify_step(self._verify_packed)))
            for b in prompt_buckets:
                buf = self._prefill_buf(bucket_size(int(b)))
                buf[:] = 0
                jax.block_until_ready(
                    jax.tree.leaves(self.engine.prefill(buf)))

    def _step(self):
        import time
        with _trace.span("decode.admit", "decode"):
            self._admit()
        active = [(i, s) for i, s in enumerate(self._rows) if s is not None]
        if not active:
            if self._waiting:
                time.sleep(1e-3)
            return
        # grow THROUGH the drafted window: the draft writes positions
        # len-1 .. len-2+k, verify writes one more; submit-time bounds
        # guarantee the window always fits a sequence's page cap
        plans: Dict[int, int] = {}
        with _trace.span("decode.grow", "decode"):
            for i, seq in active:
                if self._rows[i] is not seq:
                    continue
                k_i = self._plan_k(seq)
                if self._ensure_page(seq, extra=k_i):
                    plans[seq.sid] = k_i
        active = [(i, s) for i, s in enumerate(self._rows) if s is not None]
        if not active:
            return
        slot = self.engine.pick_draft_slot(self.engine.active_mask())

        drafts = None
        if any(plans.get(s.sid, 0) > 0 for _, s in active):
            with _trace.span("decode.draft", "decode", rows=len(active),
                             slot=slot,
                             tokens=sum(plans.get(s.sid, 0)
                                        for _, s in active)):
                t_pack = clock.now()
                d = self._draft_packed
                d[:, 0] = 0
                d[:, 1] = -1
                d[:, 2:] = 0
                for i, seq in active:
                    d[i, 0] = seq.all_tokens[-1]
                    d[i, 1] = len(seq.all_tokens) - 1
                    d[i, 2] = plans.get(seq.sid, 0)
                    self.pool.fill_block_row(seq.sid, d[i, 3:])
                self.stats["h2d_transfers"] += 1
                drafts = np.asarray(
                    self._launch(t_pack, self.engine.draft_step, d, slot))
            self.spec_stats["draft_calls"] += 1
            self.spec_stats["drafted_tokens"] += int(
                sum(plans.get(s.sid, 0) for _, s in active))

        with _trace.span("decode.verify", "decode", rows=len(active)):
            t_pack = clock.now()
            v = self._verify_packed
            v[:] = 0
            v[:, self.w_max] = -1
            for i, seq in active:
                k_i = plans.get(seq.sid, 0)
                v[i, 0] = seq.all_tokens[-1]
                if k_i:
                    v[i, 1:1 + k_i] = drafts[i, :k_i]
                v[i, self.w_max] = len(seq.all_tokens) - 1
                v[i, self.w_max + 1] = k_i + 1
                self.pool.fill_block_row(seq.sid, v[i, self.w_max + 2:])
            # the window's last query attends through position
            # start + win_len - 1 (inactive rows: -1 + 0 - 1, still < 0)
            self._count_kv_pages(v[:, self.w_max] + v[:, self.w_max + 1] - 1)
            self.stats["h2d_transfers"] += 1
            heads = self._launch(t_pack, self.engine.verify_step, v)
        self.spec_stats["verify_calls"] += 1
        self.spec_stats["spec_steps"] += 1
        self.stats["steps"] += 1
        self.stats["active_row_steps"] += len(active)

        t_emit = clock.now()
        with _trace.span("decode.emit", "decode"):
            for i, seq in active:
                k_i = plans.get(seq.sid, 0)
                bma = heads["token"][i]         # (W,) per-position argmax
                # accept rule: position 0's argmax is always right (it
                # conditions only on committed tokens); draft j survives
                # iff it equals the BMA argmax at position j-1, and each
                # surviving draft unlocks the argmax after it
                m = 1
                while m <= k_i and \
                        int(drafts[i, m - 1]) == int(bma[m - 1]):
                    m += 1
                emitted = 0
                for j in range(m):
                    self._append_window_token(seq, heads, i, j)
                    emitted += 1
                    if seq.finish_reason() == "eos":
                        break
                self.spec_stats["accepted_tokens"] += max(0, emitted - 1)
                self._observe_acceptance(seq, k_i, m - 1)
                # rollback: keep pages for the KV the accepted prefix
                # needs (entries for all_tokens[:-1]); the rejected tail's
                # pages come back page-granularly, its KV is
                # position-masked dead
                freed = self.pool.release_tail(seq.sid,
                                               len(seq.all_tokens) - 1)
                if freed:
                    self.spec_stats["rollback_pages"] += freed
                    _trace.instant("decode.rollback", "decode", sid=seq.sid,
                                   pages=freed)
                self._maybe_retire(i, seq)
        self.stats["emit_s"] += clock.now() - t_emit

    def _append_window_token(self, seq: _Seq, heads, i: int, j: int):
        seq.generated.append(int(heads["token"][i][j]))
        seq.logprobs.append(float(heads["logprob"][i][j]))
        seq.entropy.append(float(heads["entropy"][i][j]))
        seq.mutual_info.append(float(heads["mutual_info"][i][j]))
        seq.token_times.append(clock.now())
        self.stats["generated_tokens"] += 1

    # -- bookkeeping overrides ------------------------------------------------
    def _maybe_retire(self, row: int, seq: _Seq):
        done = seq.finish_reason() is not None
        super()._maybe_retire(row, seq)
        if done:
            self._spec_state.pop(seq.sid, None)

    def _fail_all(self, e: BaseException):
        super()._fail_all(e)
        self._spec_state.clear()

    def snapshot_stats(self) -> Dict[str, Any]:
        out = super().snapshot_stats()
        ss = dict(self.spec_stats)
        drafted = max(1, ss["drafted_tokens"])
        ss["acceptance_rate"] = ss["accepted_tokens"] / drafted
        steps = max(1, ss["spec_steps"])
        ss["tokens_per_step"] = self.stats["generated_tokens"] / steps
        ss["k_max"] = self.k_max
        ss["adaptive"] = self.spec_cfg.adaptive
        ss["quantized"] = self.spec_cfg.quantized
        ks = [st.k for st in self._spec_state.values()]
        ss["mean_k"] = (sum(ks) / len(ks)) if ks else float(self.k_max)
        out["speculative"] = ss
        return out
