"""Serving mode: an ensemble LM behind the program's continuous-batching
decode service (``serve_decode``: paged KV pool, Pallas paged kernel,
BMA heads, greedy tokens).

Set-up makes the weights from the seed with the configuration's
reference, builds the service with the configuration's serving sizes,
warms the decode program and the traffic's prefill buckets, and sends
one short request through. Token times are taken where a token reaches
the host-side sequence: ``DecodeScheduler._append_token``, wrapped on the
instance.

  open loop    requests are sent at their due times (Poisson arrivals at
               the traffic's fixed rate). The window is ``--seconds``
               long; every request due in it is waited for (``drain_s``
               at most), and every gap between its tokens counts. Its
               first token, timed from its due time, is noted.
  closed loop  ``clients`` requests are kept in the system: a finished
               one is replaced at once. The rate is the tokens appended
               inside the window over the window.

Once the window has closed and the service and its state are freed, a
sample of the finished requests drawn from the seed, the longest among
them, is run through the reference: the whole prompt and served tokens
in one causal pass per particle, and the BMA heads at every served
position (see ``compare``).
"""
from __future__ import annotations

import gc
import queue
import time

import numpy as np


class Rec:
    __slots__ = ("req", "due", "sent", "times", "handle", "gen", "error")

    def __init__(self, req, due):
        self.req, self.due = req, due
        self.sent = None
        self.times = []
        self.handle = self.gen = self.error = None


class TokenLog:
    """Wraps the scheduler's ``_append_token`` on this instance: every
    token's host time, whether a prefill or a decode step made it, and
    how many positions a decoded token attended to."""

    def __init__(self, sched):
        self.sched = sched
        self.recs = {}                 # sid -> Rec
        self.events = []               # (time, decode?, ctx)
        self._orig = sched._append_token
        sched._append_token = self._hook

    def _hook(self, seq, heads, i):
        decode = bool(seq.generated)
        ctx = len(seq.prompt) + len(seq.generated)
        self._orig(seq, heads, i)
        t = time.perf_counter()
        self.events.append((t, decode, ctx))
        rec = self.recs.get(seq.sid)
        if rec is not None:
            rec.times.append(t)

    def send(self, svc, rec):
        """Submit ``rec`` from the one sending thread: its sid is the
        scheduler's next, so it is registered before any token lands."""
        self.recs[self.sched._next_sid] = rec
        rec.sent = time.perf_counter()
        rec.handle = svc.generate_async(rec.req.prompt,
                                        max_new=rec.req.max_new)
        return rec.handle


def _finish(recs, deadline):
    for rec in recs:
        try:
            rec.gen = rec.handle.result(max(0.0, deadline - time.perf_counter()))
        except Exception as e:      # refused, failed, or never came
            rec.error = e


def pct(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else \
        float("nan")


class Cancelled(RuntimeError):
    """A request the harness ended after the window (the service has no
    cancel of its own)."""


def cancel_all(sched):
    """End every request still in the scheduler, between two steps,
    through its own failure path."""
    with sched.step_lock:
        sched._fail_all(Cancelled("ended after the window"))


def build(cell):
    """Weights from the seed, the decode service, warm-up. Returns the
    distribution, the service, its token log and the particle keys."""
    import jax
    from repro.core import ParticleModule, PushDistribution
    from repro.models import api
    from repro.serve import serve_decode

    from bench.core import refops

    spec, tr, ref = cell.spec, cell.traffic, cell.r.reference
    cfg = ref.program_config(spec)
    sv = spec["serving"]
    module = ParticleModule(init=None,
                            loss=lambda p, b: api.loss_fn(p, b, cfg),
                            forward=lambda p, b: api.forward(p, b, cfg)[0],
                            cfg=cfg)
    pd = PushDistribution(module, num_devices=1, seed=cell.seed)
    init_one = jax.jit(lambda k: ref.init_params(k, spec))
    keys = refops.particle_keys(cell.seed, int(spec["particles"]))
    want = jax.tree.structure(jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), cfg)))
    got = jax.tree.structure(jax.eval_shape(init_one, keys[0]))
    if want != got:
        raise ValueError(f"reference weights do not fit the program's "
                         f"tree: {got} vs {want}")
    for k in keys:
        pd.p_create(params=init_one(k))
    jax.block_until_ready(pd.store.stacked("params"))
    cell.mark("weights")

    svc = serve_decode(pd, cfg, num_pages=int(sv["num_pages"]),
                       page_size=int(sv["page_size"]),
                       max_active=int(sv["max_active"]),
                       max_seq_pages=int(sv["max_seq_pages"]),
                       warmup_buckets=tuple(tr["warm_buckets"]))
    log = TokenLog(svc.scheduler)
    warm = svc.generate_async(list(range(1, 1 + min(tr["warm_buckets"]))),
                              max_new=2)
    warm.result(600.0)
    cell.mark("warmup")
    return pd, svc, log, keys


def run(cell):
    from bench.core import traffic

    spec, tr, ref = cell.spec, cell.traffic, cell.r.reference
    sv = spec["serving"]
    n_p = int(spec["particles"])
    pd, svc, log, keys = build(cell)
    sched = svc.scheduler
    reqs = traffic.requests(tr, seed=cell.seed, seconds=cell.seconds,
                            vocab=int(spec["vocab_size"]))
    if tr["loop"] == "open":
        recs = open_loop(cell, svc, log, reqs)
    else:
        recs = _closed_loop(cell, svc, log, reqs, sched)
    s0, s1 = cell.layer.pop("stats_at_start"), cell.layer.pop("stats_at_end")
    svc.close()
    cell.read_memory()

    t0, t1 = cell.t_w0, cell.t_w1
    cell.attempted = len(recs)
    cell.failed = sum(1 for r in recs if r.gen is None
                      and not isinstance(r.error, Cancelled))
    ok = [r for r in recs if r.gen is not None]
    win = [e for e in log.events if t0 <= e[0] <= t1]
    dec = [e for e in win if e[1]]
    cell.layer.update(
        steps=s1["steps"] - s0["steps"],
        prefills=s1["prefills"] - s0["prefills"],
        preempted=s1["preempted"] - s0["preempted"],
        row_steps=s1["active_row_steps"] - s0["active_row_steps"],
        tokens=len(win), decode_tokens=len(dec),
        decode_ctx=sum(e[2] for e in dec),
        prefill_lens=[len(r.req.prompt) for r in recs
                      if r.times and t0 <= r.times[0] <= t1],
        particles=n_p, max_active=int(sv["max_active"]))
    if tr["loop"] == "open":
        # first-token times are noted, not held: at the cell's rate a
        # window holds some tens of requests, too few for a tail
        ttft = [(r.times[0] - r.due) * 1e3 for r in recs if r.times]
        itl = [(b - a) * 1e3 for r in recs for a, b in zip(r.times,
                                                           r.times[1:])]
        lag = [(r.sent - r.due) * 1e3 for r in recs]
        cell.e2e["itl_p95_ms"] = pct(itl, 95)
        cell.notes.update(ttft_p50_ms=pct(ttft, 50),
                          ttft_p95_ms=pct(ttft, 95), itl_p50_ms=pct(itl, 50),
                          generator_lag_p95_ms=pct(lag, 95),
                          requests=len(recs), tokens_in_window=len(win),
                          itl_gaps=len(itl))
    else:
        cell.e2e["decode_tokens_per_s"] = len(win) / cell.window_s
    cell.notes.update(decode_steps=cell.layer["steps"],
                      row_occupancy=cell.layer["row_steps"] / max(
                          1, cell.layer["steps"] * int(sv["max_active"])),
                      preempted=cell.layer["preempted"],
                      finished=len(ok))
    finished = [r.gen for r in ok]
    sample = traffic.check_sample(finished, seed=cell.seed,
                                  tokens=int(tr["check_tokens"]))
    pd.cleanup()
    del svc, sched, pd, log
    gc.collect()
    cell.note_memory("bytes_in_use_before_reference")
    compare(cell, ref, spec, keys, sample)


def open_loop(cell, svc, log, reqs):
    recs = [Rec(r, None) for r in reqs]
    cell.start_window()
    cell.layer["stats_at_start"] = svc.scheduler.snapshot_stats()
    t0 = cell.t_w0
    for rec in recs:
        rec.due = t0 + rec.req.due
        wait = rec.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        log.send(svc, rec)
    t_end = t0 + cell.seconds
    wait = t_end - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    cell.layer["stats_at_end"] = svc.scheduler.snapshot_stats()
    cell.end_window(max(t_end, time.perf_counter()))
    deadline = time.perf_counter() + float(cell.traffic["drain_s"])
    for rec in recs:                 # wait for every request due
        try:
            rec.handle.result(max(0.0, deadline - time.perf_counter()))
        except Exception:
            pass
    cancel_all(svc.scheduler)        # what never came is failed
    _finish(recs, time.perf_counter() + 5.0)
    for rec in recs:
        if isinstance(rec.error, Cancelled):
            rec.error = RuntimeError("not finished within drain_s")
    return recs


def _closed_loop(cell, svc, log, pool, sched):
    done = queue.SimpleQueue()
    recs = []
    nxt = [0]

    def send():
        rec = Rec(pool[nxt[0] % len(pool)], None)
        nxt[0] += 1
        h = log.send(svc, rec)
        h._future._on_done(lambda rec=rec: done.put(rec))
        return rec

    live = [send() for _ in range(int(cell.traffic["clients"]))]
    t_fill = time.perf_counter() + 60.0
    while sched.snapshot_stats()["steps"] < int(cell.traffic["fill_steps"]) \
            and time.perf_counter() < t_fill:
        time.sleep(0.005)
    cell.start_window()
    cell.layer["stats_at_start"] = sched.snapshot_stats()
    t_end = cell.t_w0 + cell.seconds
    n_win = 0
    while True:
        left = t_end - time.perf_counter()
        if left <= 0:
            break
        try:
            done.get(timeout=left)
        except queue.Empty:
            break
        live.append(send())
        n_win += 1
    cell.layer["stats_at_end"] = sched.snapshot_stats()
    cell.end_window()
    cancel_all(sched)                # the clients stop with the window
    _finish(live, time.perf_counter() + 5.0)
    cell.notes["sent_in_window"] = n_win
    return live


def compare(cell, ref, spec, keys, sample):
    """The reference's BMA heads at every served position of the sample,
    against what was served:

      token_gap     widest gap, in log BMA probability, by which a served
                    token lies below the reference's best token;
      logprob_gap   widest |served log-probability - reference's| of the
                    served token;
      entropy_gap   widest |served predictive entropy - reference's|;
      mi_gap        widest |served mutual information - reference's|.

    The control (``cell.control``, a dtype) puts the reference in the
    service's place with every matmul operand rounded to that dtype: at
    each position of the same prompts and served tokens, its own best
    token and heads are compared as if served."""
    import jax
    import jax.numpy as jnp
    if not sample:
        cell.failed = max(cell.failed, 1)
        return
    heads = ref.bma_heads_fn(spec)
    with jax.default_matmul_precision("highest"):
        params = jax.jit(jax.vmap(lambda k: ref.init_params(k, spec)))(
            jnp.stack(keys))
    gaps = {"token_gap": 0.0, "logprob_gap": 0.0, "entropy_gap": 0.0,
            "mi_gap": 0.0}
    n_tok = 0
    control = ref.bma_heads_fn(spec, jnp.dtype(cell.control)) \
        if cell.control else None
    for g in sample:
        tokens, lp, ent_s, mi_s = g.tokens, g.logprobs, g.entropy, \
            g.mutual_info
        with jax.default_matmul_precision("highest"):
            if control is not None:
                lp, tokens, _, ent_s, mi_s = control(params, g.prompt,
                                                     g.tokens)
            best, _, served, ent, mi = (np.asarray(x, np.float64) for x in
                                        heads(params, g.prompt, g.tokens,
                                              served=tokens))
        n_tok += len(tokens)
        gaps["token_gap"] = max(gaps["token_gap"], float(np.max(best - served)))
        gaps["logprob_gap"] = max(gaps["logprob_gap"], float(np.max(
            np.abs(np.asarray(lp, np.float64) - served))))
        gaps["entropy_gap"] = max(gaps["entropy_gap"], float(np.max(
            np.abs(np.asarray(ent_s, np.float64) - ent))))
        gaps["mi_gap"] = max(gaps["mi_gap"], float(np.max(
            np.abs(np.asarray(mi_s, np.float64) - mi))))
    cell.notes.update(checked_requests=len(sample), checked_tokens=n_tok)
    for k, v in gaps.items():
        cell.check(k, v)
