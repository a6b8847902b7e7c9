"""Request scheduling: micro-batching (stateless) + continuous batching
(LM decode).

``MicroBatcher`` is the serving analogue of the paper's dispatch problem:
one fused BMA forward per *request* wastes the accelerator exactly the
way thread-per-dispatch wasted the host (PR 1), so requests are
coalesced into padded batches and flushed by whichever trigger fires
first:

  size      the pending set reached ``max_batch`` — flush immediately;
  deadline  the oldest pending request has waited ``max_wait_ms`` —
            flush whatever has accumulated (bounded tail latency);
  close     the batcher is shutting down — flush the remainder.

The flush loop is not a new thread model: it runs as work items on a
``core.executor.Executor`` (the PR 1 persistent worker loop — one device
queue, FIFO mailbox, no thread churn), scheduled only while requests are
pending, so an idle batcher costs one parked worker. The queue is
bounded: ``submit`` blocks once ``max_queue`` requests are pending
(backpressure, mirroring the executor's ``max_pending`` admission).

Each request is ONE example (no leading batch axis); the batcher fills a
*preallocated per-bucket host staging buffer* (reused across flushes —
no np.stack scratch allocation per flush, exactly one H2D transfer per
flush), calls ``predict_fn`` once, and resolves each request's PFuture
with its row of the result tree. Per-request latency (enqueue ->
resolve) lands in a ring buffer for the service's p50/p95/p99.

``DecodeScheduler`` is the continuous-batching upgrade for stateful LM
decode (DESIGN.md §10): where MicroBatcher admits and retires work per
*flush*, the decode loop admits and retires sequences per *decode step*.
A fixed grid of ``max_active`` rows runs one fused paged-decode program
per step; finished rows free their KV pages and are refilled from the
waiting queue in the SAME loop iteration, so divergent sequence lengths
never leave rows idling at the barrier the way flush-batched decode
does. Admission backpressure is keyed on free pages in the PagePool;
when a running row cannot get its next page, the youngest row is
preempted (pages reclaimed, sequence requeued — greedy sampling makes
the re-run deterministic).
"""
from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from ..core.executor import Executor
from ..core.messages import PFuture
from ..obs import clock, metrics
from ..obs import trace as _trace
from .engine import bucket_size

_LAT_RING = 4096


class _Request:
    __slots__ = ("x", "future", "t_enqueue")

    def __init__(self, x, future: PFuture):
        self.x = x
        self.future = future
        self.t_enqueue = clock.now()


class _Staging:
    """Preallocated host staging buffers, one set per (bucket, tree
    structure, leaf shapes/dtypes).

    ``np.stack`` per flush allocates a fresh scratch batch every time; a
    steady-state server flushing the same bucket thousands of times per
    second spends that allocation (and the page faults behind it) on
    every flush. Instead each distinct batch signature gets ONE
    preallocated buffer per leaf, request rows are copied in place, and
    pad rows repeat the last real row (same semantics as
    ``runtime.bucketing.pad_rows``). The buffer feeds exactly one H2D
    transfer when the fused program consumes it.

    Reuse across flushes is safe because the flush is synchronous: the
    single pump thread device_gets the result before the next flush can
    touch the buffer, and JAX copies host numpy input at dispatch."""

    def __init__(self):
        self._bufs: Dict[Any, List[np.ndarray]] = {}
        self.builds = 0
        self.reuses = 0

    def batch(self, rows: List[Any], bucket: int):
        leaves, treedef = jax.tree.flatten(rows[0])
        sig = (bucket, treedef,
               tuple((np.shape(l), np.result_type(l)) for l in leaves))
        bufs = self._bufs.get(sig)
        if bufs is None:
            bufs = [np.empty((bucket,) + np.shape(l), np.result_type(l))
                    for l in leaves]
            self._bufs[sig] = bufs
            self.builds += 1
        else:
            self.reuses += 1
        for i, row in enumerate(rows):
            for buf, leaf in zip(bufs, treedef.flatten_up_to(row)):
                buf[i] = leaf
        m = len(rows)
        if m < bucket:
            for buf in bufs:
                buf[m:] = buf[m - 1]        # pad = repeat last real row
        return jax.tree.unflatten(treedef, bufs)


class MicroBatcher:
    def __init__(self, predict_fn: Callable, *, max_batch: int = 32,
                 max_wait_ms: float = 2.0, max_queue: int = 512,
                 executor: Optional[Executor] = None):
        if max_batch < 1 or max_queue < 1:
            raise ValueError("max_batch and max_queue must be >= 1")
        self.predict_fn = predict_fn
        self.max_batch = max_batch
        self.max_wait = max_wait_ms / 1e3
        self.max_queue = max_queue
        self._owns_executor = executor is None
        # one "device" worker is the flush loop; no pool threads needed
        self._exec = executor or Executor(num_devices=1, pool_size=0,
                                          max_pending=2 * max_queue)
        self._pump_pid = id(self)  # any stable key works as a mailbox id
        self._exec.add_particle(self._pump_pid, 0)
        self._cond = threading.Condition()
        self._pending: deque = deque()
        self._pump_scheduled = False
        self._closed = False
        # per-request enqueue->resolve latency: an obs.metrics Histogram
        # (same ring bound as the old hand-rolled deque, one percentile
        # implementation for the whole serving layer)
        self.latency = metrics.Histogram("serve_request_latency_seconds",
                                         ring=_LAT_RING)
        self._staging = _Staging()
        self.stats: Dict[str, Any] = {
            "requests": 0, "batches": 0, "rows": 0, "padded_rows": 0,
            "size_flushes": 0, "deadline_flushes": 0, "close_flushes": 0,
            "max_queue_depth": 0, "errors": 0, "h2d_transfers": 0,
        }

    # -- submission ----------------------------------------------------------
    def submit(self, x) -> PFuture:
        """Enqueue one example; resolves to its row of the prediction.
        Blocks while ``max_queue`` requests are already pending."""
        fut = PFuture()
        req = _Request(x, fut)
        with self._cond:
            if self._closed:
                raise RuntimeError("batcher is closed")
            while len(self._pending) >= self.max_queue:
                self._cond.wait(0.05)
                if self._closed:
                    raise RuntimeError("batcher is closed")
            self._pending.append(req)
            self.stats["requests"] += 1
            depth = len(self._pending)
            if depth > self.stats["max_queue_depth"]:
                self.stats["max_queue_depth"] = depth
            if not self._pump_scheduled:
                self._pump_scheduled = True
                self._exec.submit(self._pump_pid, self._pump)
            self._cond.notify_all()
        return fut

    # -- flush loop (runs on the executor worker) ----------------------------
    def _pump(self):
        while True:
            with self._cond:
                if not self._pending:
                    self._pump_scheduled = False
                    return
                deadline = self._pending[0].t_enqueue + self.max_wait
                while (not self._closed
                       and len(self._pending) < self.max_batch):
                    rem = deadline - clock.now()
                    if rem <= 0:
                        break
                    self._cond.wait(rem)
                if len(self._pending) >= self.max_batch:
                    reason = "size"
                elif self._closed:
                    reason = "close"
                else:
                    reason = "deadline"
                reqs = [self._pending.popleft()
                        for _ in range(min(len(self._pending),
                                           self.max_batch))]
                self._cond.notify_all()   # wake backpressured submitters
            if not reqs:    # close() raced the deadline wait and drained
                continue    # the queue itself; nothing to flush
            self._flush(reqs, reason)

    def _flush(self, reqs: List[_Request], reason: str):
        self.stats[f"{reason}_flushes"] += 1
        self.stats["batches"] += 1
        self.stats["rows"] += len(reqs)
        try:
            bucket = bucket_size(len(reqs))
            with _trace.span("serve.flush", "serve", reason=reason,
                             rows=len(reqs), bucket=bucket):
                padded = self._staging.batch([r.x for r in reqs], bucket)
                self.stats["padded_rows"] += bucket - len(reqs)
                # the staging buffer is the ONE host->device transfer of
                # the flush (asserted: h2d_transfers == batches)
                self.stats["h2d_transfers"] += 1
                # one host transfer for the whole result tree; per-request
                # rows are then free numpy slices (n lazy device slices
                # would each pay a dispatch)
                result = jax.device_get(self.predict_fn(padded))
            now = clock.now()
            for i, r in enumerate(reqs):
                self.latency.observe(now - r.t_enqueue)
                r.future._resolve(
                    jax.tree.map(lambda a, i=i: a[i], result))
        except BaseException as e:       # surfaced on each request's wait()
            self.stats["errors"] += 1
            for r in reqs:
                r.future._reject(e)

    # -- introspection -------------------------------------------------------
    def queue_depth(self) -> int:
        with self._cond:
            return len(self._pending)

    def latencies_s(self) -> List[float]:
        return self.latency.values()

    def snapshot_stats(self) -> Dict[str, Any]:
        with self._cond:
            out = dict(self.stats)
            out["queue_depth"] = len(self._pending)
            out["staging_builds"] = self._staging.builds
            out["staging_reuses"] = self._staging.reuses
        n = max(1, out["rows"] + out["padded_rows"])
        out["occupancy"] = out["rows"] / n
        return out

    # -- lifecycle -----------------------------------------------------------
    def close(self, timeout: float = 30.0):
        """Flush whatever is pending, then stop accepting requests."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._owns_executor:
            self._exec.shutdown(drain=True, timeout=timeout)
        # reject anything the pump never got to (executor already down)
        with self._cond:
            leftovers = list(self._pending)
            self._pending.clear()
        for r in leftovers:
            r.future._reject(RuntimeError("batcher closed"))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# Continuous batching for paged LM decode
# ---------------------------------------------------------------------------

@dataclass
class Generation:
    """Resolved result of one decode request (PFuture payload)."""
    prompt: List[int]
    tokens: List[int]                       # generated ids (incl. eos if hit)
    logprobs: List[float] = field(default_factory=list)   # BMA log p(token)
    entropy: List[float] = field(default_factory=list)    # total predictive
    mutual_info: List[float] = field(default_factory=list)  # epistemic part
    finish_reason: str = "length"           # "eos" | "length"
    preemptions: int = 0
    # obs.clock stamps: submitted, first admitted, each token on the host
    t_enqueue: Optional[float] = None
    t_admit: Optional[float] = None
    token_times: List[float] = field(default_factory=list)

    @property
    def text_ids(self) -> List[int]:
        return self.prompt + self.tokens


class _Seq:
    """One in-flight sequence. ``all_tokens`` (prompt + generated) is the
    whole decode state: the KV pool holds entries for ``all_tokens[:-1]``
    and the next step feeds ``all_tokens[-1]`` at position
    ``len(all_tokens) - 1`` — so preemption can drop every page and later
    rebuild them with one prefill over ``all_tokens[:-1]`` (greedy
    sampling makes the replay exact)."""
    __slots__ = ("sid", "prompt", "max_new", "eos_id", "future", "generated",
                 "logprobs", "entropy", "mutual_info", "t_enqueue",
                 "t_admit", "token_times", "preemptions")

    def __init__(self, sid: int, prompt: List[int], max_new: int,
                 eos_id: Optional[int], future: PFuture):
        self.sid = sid
        self.prompt = prompt
        self.max_new = max_new
        self.eos_id = eos_id
        self.future = future
        self.generated: List[int] = []
        self.logprobs: List[float] = []
        self.entropy: List[float] = []
        self.mutual_info: List[float] = []
        self.t_enqueue = clock.now()
        self.t_admit: Optional[float] = None
        self.token_times: List[float] = []
        self.preemptions = 0

    @property
    def all_tokens(self) -> List[int]:
        return self.prompt + self.generated

    def finish_reason(self) -> Optional[str]:
        if self.generated and self.eos_id is not None \
                and self.generated[-1] == self.eos_id:
            return "eos"
        if len(self.generated) >= self.max_new:
            return "length"
        return None

    def result(self) -> Generation:
        return Generation(prompt=self.prompt, tokens=self.generated,
                          logprobs=self.logprobs, entropy=self.entropy,
                          mutual_info=self.mutual_info,
                          finish_reason=self.finish_reason() or "length",
                          preemptions=self.preemptions,
                          t_enqueue=self.t_enqueue, t_admit=self.t_admit,
                          token_times=self.token_times)


# store -> scheduler, consumed by runtime/backends.stats() (lazy import
# there avoids a runtime<->serve cycle). Weak values: a dropped scheduler
# must not be pinned by its stats hook.
_DECODE_SCHEDULERS: "weakref.WeakValueDictionary" = \
    weakref.WeakValueDictionary()


def decode_stats_for(store) -> Optional[Dict[str, Any]]:
    """Decode-section stats for ``pd.stats()`` — None when no
    DecodeScheduler serves this store."""
    sched = _DECODE_SCHEDULERS.get(id(store))
    return None if sched is None else sched.snapshot_stats()


class DecodeScheduler:
    """Continuous batching over a ``PagedDecodeEngine`` + ``PagePool``.

    A fixed grid of ``max_active`` rows runs ONE fused decode program per
    step (fixed shapes: the packed ``(max_active, 2 + n_pmax)`` i32 step
    input is a preallocated staging buffer refilled in place — one H2D
    per step, one D2H for the small heads). Scheduling is per step, not
    per flush:

      admit    while rows are free and the pool can cover a waiting
               prompt's pages, pop it, prefill its prompt (one program
               per pow2 prompt bucket), seat it in a row;
      grow     a running row crossing a page boundary allocates its next
               page; if the pool is dry the YOUNGEST row is preempted —
               pages released, sequence requeued at the front, replayed
               later via prefill over its accumulated tokens (greedy
               sampling ⇒ deterministic);
      decode   one fused step for all seated rows (inactive rows ride
               along masked with seq_len -1);
      retire   rows hitting eos/max_new release pages and resolve their
               PFuture in the SAME iteration the row frees up.

    Each phase is an obs span (``decode.admit``, ``decode.grow``,
    ``decode.step`` with its pack / dispatch / sync, ``decode.emit`` for
    append + retire), and ``stats`` keeps its host seconds whether
    tracing is on or not (DESIGN.md §12).

    ``step_lock`` serializes steps against external store churn: hold it
    around ``pd.p_clone``/``p_kill`` so lifecycle ops never interleave
    with the engine's pages checkout/commit window (the lifecycle test
    drives exactly this). The loop itself runs as work items on a
    PR 1 Executor, like MicroBatcher — idle scheduler, parked worker.
    """

    # a prefill of this many tokens or fewer is bound by reading the
    # weights, not by its token count, so padding a prompt up to it is
    # cheaper than compiling a program for a smaller bucket
    PREFILL_FREE_PAD = 16

    def __init__(self, engine, pool, *, max_active: int = 8,
                 eos_id: Optional[int] = None, max_queue: int = 256,
                 executor: Optional[Executor] = None):
        if max_active < 1 or max_queue < 1:
            raise ValueError("max_active and max_queue must be >= 1")
        self.engine = engine
        self.pool = pool
        self.max_active = max_active
        self.eos_id = eos_id
        self.max_queue = max_queue
        self.n_pmax = engine.n_pmax
        if pool.max_seq_pages != self.n_pmax:
            raise ValueError(
                f"pool.max_seq_pages ({pool.max_seq_pages}) must equal the "
                f"engine's block-table width n_pmax ({self.n_pmax})")
        self._owns_executor = executor is None
        self._exec = executor or Executor(num_devices=1, pool_size=0,
                                          max_pending=2 * max_queue)
        self._pump_pid = id(self)
        self._exec.add_particle(self._pump_pid, 0)
        self._cond = threading.Condition()
        self._waiting: deque = deque()
        self._rows: List[Optional[_Seq]] = [None] * max_active
        self._pump_scheduled = False
        self._closed = False
        self._next_sid = 0
        # submit->retire latency per sequence (obs.metrics Histogram,
        # same ring bound as the old hand-rolled deque)
        self.latency = metrics.Histogram("decode_request_latency_seconds",
                                         ring=_LAT_RING)
        # fixed-shape decode staging buffer: [:, 0] token, [:, 1] seq_len,
        # [:, 2:] block table — refilled in place, ONE H2D per step
        self._packed = np.zeros((max_active, 2 + self.n_pmax), np.int32)
        self._prefill_bufs: Dict[int, np.ndarray] = {}
        self.step_lock = threading.Lock()
        self.stats: Dict[str, Any] = {
            "submitted": 0, "admitted": 0, "retired": 0, "preempted": 0,
            "steps": 0, "prefills": 0, "generated_tokens": 0,
            "active_row_steps": 0, "admission_blocked": 0,
            "h2d_transfers": 0, "errors": 0, "max_queue_depth": 0,
            # host seconds by phase (obs.clock, always on): packing the
            # step input, dispatching it, the device_get of its heads,
            # appending/retiring after it; every prefill, and the replays
            # among them (re-admissions after a preemption)
            "pack_s": 0.0, "dispatch_s": 0.0, "sync_s": 0.0, "emit_s": 0.0,
            "prefill_s": 0.0, "replay_prefill_s": 0.0, "replay_prefills": 0,
            # page slots the paged attention kernel walks (rows x n_pmax a
            # step) and the live ones among them, which alone cost a copy
            "kv_pages_live": 0, "kv_page_slots": 0,
        }
        _DECODE_SCHEDULERS[id(engine.store)] = self

    # -- submission ----------------------------------------------------------
    def submit(self, prompt, *, max_new: int,
               eos_id: Optional[int] = None) -> PFuture:
        """Enqueue one prompt (list/array of token ids); resolves to a
        ``Generation``. Blocks while ``max_queue`` sequences wait."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        ps = self.pool.page_size
        worst = len(prompt) + max_new
        limit = min(self.n_pmax, self.pool.num_pages) * ps
        if worst > limit:
            raise ValueError(
                f"prompt + max_new = {worst} tokens needs "
                f"{-(-worst // ps)} pages; pool/block-table limit is "
                f"{limit // ps} pages ({limit} tokens)")
        fut = PFuture()
        seq = _Seq(self._next_sid, prompt, max_new,
                   self.eos_id if eos_id is None else eos_id, fut)
        with self._cond:
            if self._closed:
                raise RuntimeError("scheduler is closed")
            self._next_sid += 1
            seq.sid = self._next_sid - 1
            while len(self._waiting) >= self.max_queue:
                self._cond.wait(0.05)
                if self._closed:
                    raise RuntimeError("scheduler is closed")
            self._waiting.append(seq)
            self.stats["submitted"] += 1
            depth = len(self._waiting)
            if depth > self.stats["max_queue_depth"]:
                self.stats["max_queue_depth"] = depth
            if not self._pump_scheduled:
                self._pump_scheduled = True
                self._exec.submit(self._pump_pid, self._pump)
            self._cond.notify_all()
        return fut

    def warmup(self, prompt_buckets=()):
        """Compile the decode-step program (all rows masked inactive) and
        one prefill program per requested pow2 prompt bucket — with zero
        tokens, so no page is written and the pool is untouched. After
        this, steady-state serving is ZERO cold compiles."""
        with self.step_lock:
            self._packed[:, 0] = 0
            self._packed[:, 1] = -1
            self._packed[:, 2:] = 0
            jax.block_until_ready(
                jax.tree.leaves(self.engine.decode_step(self._packed)))
            for b in prompt_buckets:
                buf = self._prefill_buf(bucket_size(int(b)))
                buf[:] = 0          # n_tokens = 0: every write masked out
                jax.block_until_ready(
                    jax.tree.leaves(self.engine.prefill(buf)))

    # -- scheduler loop (runs on the executor worker) ------------------------
    def _pump(self):
        while True:
            with self._cond:
                if not self._waiting and not any(self._rows):
                    self._pump_scheduled = False
                    self._cond.notify_all()
                    return
            try:
                with self.step_lock:
                    self._step()
            except BaseException as e:
                # engine-level failure (not per-sequence): fail every
                # in-flight sequence rather than spin on a broken program
                self.stats["errors"] += 1
                self._fail_all(e)

    def _step(self):
        with _trace.span("decode.admit", "decode"):
            self._admit()
        active = [(i, s) for i, s in enumerate(self._rows) if s is not None]
        if not active:
            if self._waiting:     # admission blocked on a dry pool with
                time.sleep(1e-3)  # nothing decoding: don't spin hot
            return
        # grow: every seated row needs the page holding the position its
        # next token writes; dry pool preempts youngest-first
        with _trace.span("decode.grow", "decode"):
            for i, seq in active:
                if self._rows[i] is seq:    # not preempted by an earlier row
                    self._ensure_page(seq)
        active = [(i, s) for i, s in enumerate(self._rows) if s is not None]
        if not active:
            return
        with _trace.span("decode.step", "decode", rows=len(active)):
            t_pack = clock.now()
            self._packed[:, 0] = 0
            self._packed[:, 1] = -1
            self._packed[:, 2:] = 0
            for i, seq in active:
                self._packed[i, 0] = seq.all_tokens[-1]
                self._packed[i, 1] = len(seq.all_tokens) - 1
                self.pool.fill_block_row(seq.sid, self._packed[i, 2:])
            self._count_kv_pages(self._packed[:, 1])
            self.stats["h2d_transfers"] += 1
            heads = self._launch(t_pack, self.engine.decode_step,
                                 self._packed)
        self.stats["steps"] += 1
        self.stats["active_row_steps"] += len(active)
        t_emit = clock.now()
        with _trace.span("decode.emit", "decode"):
            for i, seq in active:
                self._append_token(seq, heads, i)
                self._maybe_retire(i, seq)
        self.stats["emit_s"] += clock.now() - t_emit

    def _count_kv_pages(self, last_pos: np.ndarray):
        """Add one step's walk to ``kv_pages_live`` / ``kv_page_slots``:
        ``last_pos`` holds each row's last attended position (-1 for an
        inactive row), so a row's live pages are ``last_pos // page_size
        + 1``."""
        live = last_pos[last_pos >= 0] // self.pool.page_size + 1
        self.stats["kv_pages_live"] += int(live.sum())
        self.stats["kv_page_slots"] += self.max_active * self.n_pmax

    def _launch(self, t_pack: float, fn, *args):
        """Dispatch one packed step (``fn(*args)``) and bring its output
        to the host. ``t_pack`` is when packing its input began. Adds the
        pack / dispatch / sync phases to the counters and, with tracing
        on, records them as ``decode.pack`` / ``decode.dispatch`` (the
        program's own span nests inside) / ``decode.sync`` spans."""
        t_disp = clock.now()
        out = fn(*args)
        t_sync = clock.now()
        host = jax.device_get(out)
        t_done = clock.now()
        st = self.stats
        st["pack_s"] += t_disp - t_pack
        st["dispatch_s"] += t_sync - t_disp
        st["sync_s"] += t_done - t_sync
        tr = _trace.TRACER
        if tr.enabled:
            tr.record("decode.pack", "decode", t_pack, t_disp)
            tr.record("decode.dispatch", "decode", t_disp, t_sync)
            tr.record("decode.sync", "decode", t_sync, t_done)
        return host

    def _admit(self):
        ps = self.pool.page_size
        while True:
            with self._cond:
                if not self._waiting:
                    return
                try:
                    row = self._rows.index(None)
                except ValueError:
                    return
                seq = self._waiting[0]
                # initial admission prefills the prompt; re-admission
                # after preemption replays everything but the pending
                # token (whose KV slot the next decode step writes)
                n_pf = len(seq.prompt) if not seq.generated \
                    else len(seq.all_tokens) - 1
                if self.pool.alloc(seq.sid, -(-n_pf // ps)) is None:
                    self.stats["admission_blocked"] += 1
                    return                    # backpressure: pool is dry
                self._waiting.popleft()
                self._cond.notify_all()       # wake backpressured submitters
            if seq.t_admit is None:           # first admission: its wait in
                seq.t_admit = clock.now()     # the queue ends, prefill starts
            try:
                heads = self._prefill(seq, n_pf)
            except BaseException as e:
                self.stats["errors"] += 1
                self.pool.release(seq.sid)
                seq.future._reject(e)
                continue
            self._rows[row] = seq
            self.stats["admitted"] += 1
            _trace.instant("decode.seat", "decode", sid=seq.sid,
                           replay=bool(seq.generated))
            if not seq.generated:
                # the prefill head IS the first generated token; replays
                # discard it (greedy ⇒ it equals the token already held)
                self._append_token(seq, heads, 0)
                self._maybe_retire(row, seq)

    def _prefill_buf(self, bucket: int) -> np.ndarray:
        buf = self._prefill_bufs.get(bucket)
        if buf is None:
            buf = np.zeros((bucket + self.n_pmax + 1,), np.int32)
            self._prefill_bufs[bucket] = buf
        return buf

    def _prefill_bucket(self, n_pf: int) -> int:
        """The prompt's own pow2 bucket, or the smallest bucket that
        already has a program (warmed or used before) and pads the prompt
        by at most 2x its own bucket or up to ``PREFILL_FREE_PAD`` tokens.
        Prefill masks by ``n_tokens``, so a wider bucket changes only the
        padding: a very short prompt reuses a warmed program instead of
        cold-compiling a tiny one, while a service warmed only at large
        buckets still runs short prompts at their own size."""
        own = bucket_size(n_pf)
        limit = max(2 * own, self.PREFILL_FREE_PAD)
        fits = [b for b in self._prefill_bufs if own <= b <= limit]
        return min(fits) if fits else own

    def _prefill(self, seq: _Seq, n_pf: int):
        tokens = seq.all_tokens[:n_pf]
        bucket = self._prefill_bucket(n_pf)
        t0 = clock.now()
        with _trace.span("decode.prefill", "decode", sid=seq.sid,
                         tokens=n_pf, bucket=bucket):
            buf = self._prefill_buf(bucket)
            buf[:n_pf] = tokens
            buf[n_pf:bucket] = 0
            self.pool.fill_block_row(seq.sid,
                                     buf[bucket:bucket + self.n_pmax])
            buf[-1] = n_pf
            self.stats["prefills"] += 1
            self.stats["h2d_transfers"] += 1
            heads = jax.device_get(self.engine.prefill(buf))
        t1 = clock.now()
        st = self.stats
        st["prefill_s"] += t1 - t0
        if seq.generated:
            # a replay: the sequence was preempted and rebuilds its pages;
            # its span encloses the prefill's
            st["replay_prefills"] += 1
            st["replay_prefill_s"] += t1 - t0
            if _trace.TRACER.enabled:
                _trace.TRACER.record("decode.replay", "decode", t0, t1,
                                     {"sid": seq.sid, "tokens": n_pf})
        return heads

    def _ensure_page(self, seq: _Seq, extra: int = 0) -> bool:
        """Make the page for ``seq``'s next write position resident —
        plus ``extra`` further positions (a speculative draft window
        writes through position ``len - 1 + extra``); preempt youngest
        rows while the pool is dry. False iff ``seq`` itself got
        preempted (it WAS the youngest)."""
        need = (len(seq.all_tokens) - 1 + extra) // self.pool.page_size + 1
        while len(self.pool.pages_of(seq.sid)) < need:
            if self.pool.alloc(seq.sid,
                               need - len(self.pool.pages_of(seq.sid))):
                _trace.instant("decode.new_page", "decode", sid=seq.sid,
                               pages=need)
                return True
            victim = max((s for s in self._rows if s is not None),
                         key=lambda s: s.sid)
            self._preempt(victim)
            if victim is seq:
                return False
        return True

    def _preempt(self, seq: _Seq):
        row = self._rows.index(seq)
        self._rows[row] = None
        self.pool.release(seq.sid)
        seq.preemptions += 1
        self.stats["preempted"] += 1
        _trace.instant("decode.preempt", "decode", sid=seq.sid,
                       tokens=len(seq.all_tokens))
        with self._cond:
            self._waiting.appendleft(seq)

    def _append_token(self, seq: _Seq, heads, i: int):
        seq.generated.append(int(heads["token"][i]))
        seq.logprobs.append(float(heads["logprob"][i]))
        seq.entropy.append(float(heads["entropy"][i]))
        seq.mutual_info.append(float(heads["mutual_info"][i]))
        seq.token_times.append(clock.now())
        self.stats["generated_tokens"] += 1

    def _maybe_retire(self, row: int, seq: _Seq):
        if seq.finish_reason() is None:
            return
        self._rows[row] = None
        self.pool.release(seq.sid)
        self.stats["retired"] += 1
        self.latency.observe(clock.now() - seq.t_enqueue)
        _trace.instant("decode.retire", "decode", sid=seq.sid,
                       tokens=len(seq.generated),
                       reason=seq.finish_reason() or "length")
        seq.future._resolve(seq.result())

    def _fail_all(self, e: BaseException):
        for i, seq in enumerate(self._rows):
            if seq is not None:
                self._rows[i] = None
                self.pool.release(seq.sid)
                seq.future._reject(e)
        with self._cond:
            leftovers = list(self._waiting)
            self._waiting.clear()
            self._cond.notify_all()
        for seq in leftovers:
            self.pool.release(seq.sid)
            seq.future._reject(e)

    # -- introspection -------------------------------------------------------
    def queue_depth(self) -> int:
        with self._cond:
            return len(self._waiting)

    def active_count(self) -> int:
        return sum(1 for s in self._rows if s is not None)

    def latencies_s(self) -> List[float]:
        return self.latency.values()

    def snapshot_stats(self) -> Dict[str, Any]:
        with self._cond:
            out = dict(self.stats)
            out["queue_depth"] = len(self._waiting)
        out["active_seqs"] = self.active_count()
        out["max_active"] = self.max_active
        steps = max(1, out["steps"])
        out["row_occupancy"] = out["active_row_steps"] / (
            steps * self.max_active)
        out["pool"] = self.pool.snapshot_stats()
        # KV storage gauges (precision ladder, DESIGN.md §13): page dtype
        # histogram + resident bytes, so bf16/fp8 pools are visible in
        # pd.stats()["decode"] next to the page-churn counters
        out["kv_pages"] = self.engine.kv_page_info()
        # speculative-decode gauges (DESIGN.md §14): None on the plain
        # scheduler; SpeculativeDecodeScheduler fills the section in
        out["speculative"] = None
        return out

    # -- lifecycle -----------------------------------------------------------
    def close(self, timeout: float = 60.0):
        """Stop accepting, drain everything in flight (waiting sequences
        included — rows free up as retirements land), shut the pump."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._owns_executor:
            self._exec.shutdown(drain=True, timeout=timeout)
        with self._cond:
            leftovers = list(self._waiting)
            self._waiting.clear()
        for seq in leftovers:    # pump never got to them (executor down)
            seq.future._reject(RuntimeError("scheduler closed"))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
