"""Paged KV serving (DESIGN.md §10): page-pool allocator semantics, the
Pallas paged-decode kernel vs its NumPy oracle AND the dense decode
kernel, model-level paged-vs-dense decode parity, and the continuous-
batching DecodeScheduler end to end — token-exact against a solo dense
decode, zero cold compiles at steady state, deterministic under
preemption, stats surfaced through pd.stats()["decode"]."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import ParticleModule, PushDistribution
from repro.kernels import ops, ref
from repro.models import api
from repro.runtime import global_cache
from repro.serve import PagePool, serve_decode


def _cold():
    return global_cache().snapshot_stats()["cold_compiles"]


# ---------------------------------------------------------------------------
# PagePool: host-side allocator semantics
# ---------------------------------------------------------------------------

def test_page_pool_alloc_release_and_backpressure():
    pool = PagePool(num_pages=4, page_size=8, max_seq_pages=4)
    a = pool.alloc(0, 2)
    b = pool.alloc(1, 2)
    assert sorted(a + b) == [0, 1, 2, 3]
    assert pool.free_pages == 0 and pool.used_pages == 4
    assert pool.alloc(2, 1) is None            # dry pool -> backpressure
    assert pool.alloc(0, 1) is None
    st = pool.snapshot_stats()
    assert st["alloc_failures"] == 2 and st["peak_used"] == 4
    assert pool.release(1) == 2                # retire: pages come back
    assert pool.free_pages == 2
    got = pool.alloc(2, 1)
    assert got == [2]                          # freed page recycled
    assert pool.pages_of(0) == a
    assert pool.release(99) == 0               # unknown seq: no-op


def test_page_pool_max_seq_pages_and_block_row():
    pool = PagePool(num_pages=8, page_size=4, max_seq_pages=2)
    assert pool.alloc(7, 2) == [0, 1]
    assert pool.alloc(7, 1) is None            # per-seq cap, pool not dry
    assert pool.free_pages == 6
    row = np.full((2,), -9, np.int32)
    pool.fill_block_row(7, row)
    assert row.tolist() == [0, 1]
    pool.release(7)
    pool.alloc(8, 1)
    pool.fill_block_row(8, row)
    assert row.tolist()[1] == 0                # unused tail cleared to 0
    with pytest.raises(ValueError):
        PagePool(0, 4, 2)


def test_page_pool_release_tail_invariants():
    """Rejected-draft rollback: tail truncation is page-granular, free
    counts are conserved, block tables stay consistent, and releasing a
    sequence the pool does not own raises."""
    pool = PagePool(num_pages=8, page_size=4, max_seq_pages=8)
    pool.alloc(3, 5)                            # covers 20 token positions
    assert pool.free_pages == 3
    owned_before = pool.pages_of(3)
    # 9 tokens need ceil(9/4) = 3 pages: 2 come back, prefix preserved
    assert pool.release_tail(3, 9) == 2
    assert pool.free_pages == 5
    assert pool.pages_of(3) == owned_before[:3]
    row = np.full((8,), -9, np.int32)
    pool.fill_block_row(3, row)
    assert row[:3].tolist() == owned_before[:3] and row[3:].tolist() == [0] * 5
    # page-granular: a partially-used last page is kept
    assert pool.release_tail(3, 9) == 0
    assert pool.release_tail(3, 12) == 0        # exact page boundary
    # n_tokens = 0 keeps zero pages (sequence stays owned, list empty)
    assert pool.release_tail(3, 0) == 3
    assert pool.free_pages == 8 and pool.pages_of(3) == []
    # conservation: freed pages are allocatable again
    assert pool.alloc(4, 8) is not None
    st = pool.snapshot_stats()
    assert st["allocs"] == 13 and st["releases"] == 5
    with pytest.raises(ValueError):
        pool.release_tail(3, -1)
    pool.release(3)                             # full release pops the seq
    with pytest.raises(KeyError):               # double release raises
        pool.release_tail(3, 1)
    with pytest.raises(KeyError):               # never-owned seq raises
        pool.release_tail(77, 1)


# ---------------------------------------------------------------------------
# paged decode kernel vs oracle and vs the dense decode kernel
# ---------------------------------------------------------------------------

def _paged_case(seed, B, H, KVH, hd, ps, n_pmax, NP, lens, dirty=False):
    """Random pages + block tables with the PagePool conventions: rows
    with len -1 inactive, unused block-table entries 0, tail slots of the
    last page holding stale garbage from 'previous owners'. ``dirty``:
    unused entries hold in-range page ids instead, and every page that no
    row owns is NaN, so reading one would show in the output."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    k = rng.standard_normal((NP, ps, KVH, hd)).astype(np.float32)
    v = rng.standard_normal((NP, ps, KVH, hd)).astype(np.float32)
    bt = rng.integers(0, NP, (B, n_pmax)).astype(np.int32) if dirty \
        else np.zeros((B, n_pmax), np.int32)
    free = list(rng.permutation(NP))
    for b, sl in enumerate(lens):
        if sl < 0:
            continue
        for i in range(sl // ps + 1):
            bt[b, i] = free.pop()
    if dirty:
        k[free] = np.nan
        v[free] = np.nan
    return (q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(bt),
            jnp.asarray(lens, jnp.int32))


@pytest.mark.parametrize("B,H,KVH,hd,ps,n_pmax,lens", [
    (2, 4, 2, 32, 16, 4, [47, 63]),        # GQA, partial + full pages
    (3, 8, 1, 16, 8, 6, [0, 33, 21]),      # MQA, single-token row
    (2, 4, 4, 8, 16, 3, [-1, 40]),         # MHA + an inactive row
    (4, 6, 3, 64, 32, 2, [5, -1, 63, 31]), # group=2, mixed ragged
    (3, 16, 16, 64, 16, 12, [20, 5, 40]),  # MHA at hd 64, n_pmax >> live
    (3, 8, 2, 32, 16, 4, [15, 16, 17]),    # page boundaries ps-1, ps, ps+1
    (4, 16, 16, 64, 128, 3, [-1, 127, -1, 129]),  # pages of 128; leading,
                                                  # inner inactive rows
    (2, 4, 2, 16, 8, 3, [-1, -1]),         # no live page at all
])
def test_paged_kernel_vs_oracle(B, H, KVH, hd, ps, n_pmax, lens):
    """Unused block-table entries hold in-range garbage and every page no
    row owns is NaN: the kernel reads live pages only. The oracle, which
    gathers every slot, reads the same pool with the NaN zeroed."""
    NP = B * n_pmax + 2
    q, k, v, bt, sl = _paged_case(B * 7 + ps, B, H, KVH, hd, ps, n_pmax,
                                  NP, lens, dirty=True)
    out = ops.paged_decode_attention(q, k, v, bt, sl)
    want = ref.paged_decode_attention(q, jnp.nan_to_num(k),
                                      jnp.nan_to_num(v), bt, sl)
    assert float(jnp.abs(out - want).max()) < 1e-4
    for b, L in enumerate(lens):           # inactive rows exactly zero
        if L < 0:
            assert float(jnp.abs(out[b]).max()) == 0.0


def test_paged_kernel_matches_dense_decode_kernel():
    """Gathering a row's pages into a contiguous cache and running the
    dense decode kernel must agree with reading the pages in place."""
    B, H, KVH, hd, ps, n_pmax = 2, 4, 2, 32, 8, 5
    lens = [29, 37]
    NP = B * n_pmax + 1
    q, k, v, bt, sl = _paged_case(3, B, H, KVH, hd, ps, n_pmax, NP, lens)
    paged = ops.paged_decode_attention(q, k, v, bt, sl)
    C = n_pmax * ps
    kd = jnp.take(k, bt, axis=0).reshape(B, C, KVH, hd)
    vd = jnp.take(v, bt, axis=0).reshape(B, C, KVH, hd)
    col = jnp.broadcast_to(jnp.arange(C), (B, C))
    pos = jnp.where(col <= sl[:, None], col, -1)
    dense = ops.decode_attention(q, kd, vd, pos)
    assert float(jnp.abs(paged - dense).max()) < 1e-4


def test_paged_kernel_vmaps_over_particle_axis():
    """serve stacks the kernel over the ParticleStore capacity axis:
    q and pages batched, block table + seq_lens shared."""
    P = 3
    B, H, KVH, hd, ps, n_pmax = 2, 4, 2, 16, 8, 3
    NP = B * n_pmax + 1
    cases = [_paged_case(11 + p, B, H, KVH, hd, ps, n_pmax, NP, [20, 13])
             for p in range(P)]
    qs = jnp.stack([c[0] for c in cases])
    ks = jnp.stack([c[1] for c in cases])
    vs = jnp.stack([c[2] for c in cases])
    bt, sl = cases[0][3], cases[0][4]
    outs = jax.vmap(lambda q, k, v: ops.paged_decode_attention(q, k, v,
                                                               bt, sl))(
        qs, ks, vs)
    for p in range(P):
        want = ref.paged_decode_attention(qs[p], ks[p], vs[p], bt, sl)
        assert float(jnp.abs(outs[p] - want).max()) < 1e-4


# ---------------------------------------------------------------------------
# model level: paged prefill + decode vs the dense cache path
# ---------------------------------------------------------------------------

def _tiny_cfg():
    return configs.get("qwen1.5-0.5b").replace(
        n_units=2, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        d_ff=64, vocab_size=128, max_seq_len=128)


def test_model_paged_decode_matches_dense_path():
    cfg = _tiny_cfg()
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    L, steps, ps, n_pmax = 13, 4, 8, 6
    rng = np.random.default_rng(5)
    prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, L)), jnp.int32)

    dense_first, caches = api.prefill(params, {"tokens": prompt}, cfg,
                                      max_len=L + steps + 1)
    pages = api.paged_cache_init(cfg, num_pages=16, page_size=ps)
    bt_row = jnp.asarray(list(range(2, 2 + n_pmax)), jnp.int32)
    bucket = 16                               # L=13 padded to its bucket
    padded = jnp.zeros((1, bucket), jnp.int32).at[:, :L].set(prompt)
    paged_first, pages = api.prefill_paged(params, padded, pages, bt_row,
                                           jnp.int32(L), cfg)
    assert float(jnp.abs(dense_first - paged_first).max()) < 1e-4

    tok = jnp.argmax(dense_first, -1).astype(jnp.int32).reshape(1)
    bt = bt_row[None, :]
    for step in range(steps):
        member, caches = api.decode_step(params, tok, caches,
                                         jnp.int32(L + step), cfg,
                                         decode_kernel=False)
        sl = jnp.asarray([L + step], jnp.int32)
        pmember, pages = api.decode_step_paged(params, tok, pages, bt, sl,
                                               cfg)
        assert float(jnp.abs(member - pmember).max()) < 1e-4, step
        tok = jnp.argmax(member, -1).astype(jnp.int32).reshape(1)


# ---------------------------------------------------------------------------
# DecodeScheduler end to end
# ---------------------------------------------------------------------------

def _lm_pd(cfg, n=2):
    module = ParticleModule(
        init=lambda rng: api.init_params(rng, cfg),
        loss=lambda p, b: api.loss_fn(p, b, cfg),
        forward=lambda p, b: api.forward(p, b, cfg)[0], cfg=cfg)
    pd = PushDistribution(module, num_devices=1, seed=0)
    for _ in range(n):
        pd.p_create()
    return pd


def _ref_decode(pd, cfg, prompt, max_new):
    """Solo dense greedy BMA decode — the oracle the scheduler's batched,
    paged, preempted output must match token for token."""
    toks = jnp.asarray([prompt], jnp.int32)
    stacked = pd.store.stacked("params")
    first, caches = jax.vmap(
        lambda p: api.prefill(p, {"tokens": toks}, cfg,
                              max_len=len(prompt) + max_new + 1))(stacked)
    probs = jnp.mean(jax.nn.softmax(first.astype(jnp.float32), -1), 0)
    out = [int(jnp.argmax(probs, -1)[0])]
    for step in range(max_new - 1):
        tok = jnp.asarray([out[-1]], jnp.int32)
        member, caches = jax.vmap(
            lambda p, c: api.decode_step(p, tok, c,
                                         jnp.int32(len(prompt) + step),
                                         cfg, decode_kernel=False))(
            stacked, caches)
        probs = jnp.mean(jax.nn.softmax(member.astype(jnp.float32), -1), 0)
        out.append(int(jnp.argmax(probs, -1)[0]))
    return out


def test_scheduler_matches_solo_decode_with_zero_steady_state_compiles():
    cfg = _tiny_cfg()
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab_size, rng.integers(3, 15)))
               for _ in range(5)]
    with _lm_pd(cfg) as pd:
        refs = [_ref_decode(pd, cfg, p, 6) for p in prompts]
        svc = serve_decode(pd, cfg, num_pages=32, page_size=8,
                           max_active=3, warmup_buckets=(4, 8, 16))
        try:
            cold = _cold()
            handles = [svc.generate_async(p, max_new=6) for p in prompts]
            gens = [h.result(300) for h in handles]
            # more sequences than rows: admission happened mid-decode,
            # and every sequence still matches its solo dense run exactly
            for g, r, p in zip(gens, refs, prompts):
                assert g.tokens == r
                assert g.prompt == [int(t) for t in p]
                assert len(g.logprobs) == len(g.entropy) == 6
                assert g.finish_reason == "length"
            assert _cold() == cold, "steady-state decode cold-compiled"
            st = svc.stats()
            assert st["retired"] == 5 and st["admitted"] >= 5
            assert st["steps"] > 0 and st["prefills"] >= 5
            # one H2D per decode step + one per prefill, by construction
            assert st["h2d_transfers"] == st["steps"] + st["prefills"]
            assert st["pool"]["used_pages"] == 0        # all reclaimed
            assert 0.0 < st["row_occupancy"] <= 1.0
            # the store's runtime stats surface the decode section
            dec = pd.stats()["decode"]
            assert dec["retired"] == 5
            assert dec["pool"]["num_pages"] == 32
        finally:
            svc.close()


def test_scheduler_preemption_is_deterministic():
    """A pool too small for the offered load forces preemptions; greedy
    replay makes the output token-identical to the solo run anyway."""
    cfg = _tiny_cfg()
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(1, cfg.vocab_size, 12)) for _ in range(3)]
    with _lm_pd(cfg) as pd:
        refs = [_ref_decode(pd, cfg, p, 8) for p in prompts]
        # 3 seqs x (12 + 8 = 20 tok -> 5 pages) vs 8 pages: can't all fit
        svc = serve_decode(pd, cfg, num_pages=8, page_size=4,
                           max_active=3, warmup=False)
        try:
            handles = [svc.generate_async(p, max_new=8) for p in prompts]
            gens = [h.result(300) for h in handles]
            for g, r in zip(gens, refs):
                assert g.tokens == r, (g.tokens, r, g.preemptions)
            st = svc.stats()
            assert st["preempted"] > 0, "pool sized to force preemption"
            assert sum(g.preemptions for g in gens) == st["preempted"]
            assert st["pool"]["used_pages"] == 0
        finally:
            svc.close()


def test_scheduler_eos_and_request_validation():
    cfg = _tiny_cfg()
    with _lm_pd(cfg, n=1) as pd:
        svc = serve_decode(pd, cfg, num_pages=16, page_size=8,
                           max_active=2, warmup=False)
        try:
            g = svc.generate([5, 9, 23], max_new=8)
            # greedy + eos on the first generated token: stops right there
            g2 = svc.generate([5, 9, 23], max_new=8, eos_id=g.tokens[0])
            assert g2.tokens == g.tokens[:1]
            assert g2.finish_reason == "eos"
            with pytest.raises(ValueError):
                svc.generate([], max_new=4)
            with pytest.raises(ValueError):
                svc.generate([1], max_new=0)
            with pytest.raises(ValueError):     # exceeds pool capacity
                svc.generate([1] * 100, max_new=100)
        finally:
            svc.close()


def test_scheduler_counts_live_kv_pages_per_step():
    """kv_pages_live / kv_page_slots grow, step by step, by the page
    slots the paged kernel walks: each active row's live pages from its
    seq_len, over max_active rows x n_pmax slots."""
    cfg = _tiny_cfg()
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(1, cfg.vocab_size, n)) for n in (3, 9, 5)]
    with _lm_pd(cfg, n=1) as pd:
        svc = serve_decode(pd, cfg, num_pages=16, page_size=4,
                           max_active=4, max_seq_pages=5, warmup=False)
        sched = svc.scheduler
        seen = []
        step = sched.engine.decode_step

        def spy(packed):
            seen.append((packed.copy(), sched.stats["kv_pages_live"],
                         sched.stats["kv_page_slots"]))
            return step(packed)

        sched.engine.decode_step = spy
        try:
            for h in [svc.generate_async(p, max_new=8) for p in prompts]:
                h.result(300)
            st = pd.stats()["decode"]
        finally:
            svc.close()
    assert len(seen) == st["steps"] > 0
    live = slots = 0
    for packed, live_now, slots_now in seen:
        sl, bt = packed[:, 1], packed[:, 2:]
        pages = [int(p) for b in np.flatnonzero(sl >= 0)
                 for p in bt[b, :sl[b] // 4 + 1]]
        assert len(set(pages)) == len(pages)     # one owner per live page
        assert live_now - live == len(pages)
        assert slots_now - slots == 4 * 5
        live, slots = live_now, slots_now
    assert (st["kv_pages_live"], st["kv_page_slots"]) == (live, slots)
    assert 0 < live < slots


@pytest.mark.parametrize("warmed,n_pf,want", [
    ((4, 8, 16), 1, 4),       # a tiny prompt reuses the smallest warmed
    ((4, 8, 16), 5, 8),
    ((512,), 5, 8),           # a large warmed bucket is not reused for it
    ((512,), 300, 512),
    ((1024,), 300, 1024),     # up to 2x the prompt's own bucket (512)
    ((2048,), 300, 512),      # beyond that: the prompt's own bucket
    ((), 3, 4),
])
def test_prefill_bucket_reuse_is_bounded(warmed, n_pf, want):
    from types import SimpleNamespace
    from repro.serve.batcher import DecodeScheduler
    sched = SimpleNamespace(_prefill_bufs=dict.fromkeys(warmed),
                            PREFILL_FREE_PAD=DecodeScheduler.PREFILL_FREE_PAD)
    assert DecodeScheduler._prefill_bucket(sched, n_pf) == want
