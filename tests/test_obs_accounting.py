"""The program's own account of where the host's time goes: the decode
step's phase spans and counters, replays after a preemption, per-token
stamps, the process's compile listener, the fused training call's edges,
the loader's span, and the device-side scope names."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.bdl import DeepEnsemble, MultiSWAG, SteinVGD
from repro.core import ParticleModule, PushDistribution, functional
from repro.data.loader import DataLoader
from repro.models import api
from repro.obs import clock, trace
from repro.optim import sgd
from repro.runtime import ProgramCache, ProgramSpec, compiles
from repro.serve import serve_decode

PHASES = ("decode.pack", "decode.dispatch", "decode.sync")
COUNTERS = ("pack_s", "dispatch_s", "sync_s", "emit_s", "prefill_s")


@pytest.fixture(autouse=True)
def _tracer_reset():
    yield
    trace.disable()
    trace.clear()


def _tiny_cfg():
    return configs.get("qwen1.5-0.5b").replace(
        n_units=2, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        d_ff=64, vocab_size=128, max_seq_len=128)


def _lm_pd(cfg, n=2):
    module = ParticleModule(
        init=lambda rng: api.init_params(rng, cfg),
        loss=lambda p, b: api.loss_fn(p, b, cfg),
        forward=lambda p, b: api.forward(p, b, cfg)[0], cfg=cfg)
    pd = PushDistribution(module, num_devices=1, seed=0)
    for _ in range(n):
        pd.p_create()
    return pd


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _within(inner, outer) -> bool:
    return (inner["tid"] == outer["tid"] and outer["t0"] <= inner["t0"]
            and inner["t1"] <= outer["t1"])


def _prompts(cfg, n, seed=0, lo=3, hi=15):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, cfg.vocab_size,
                                       int(rng.integers(lo, hi)))))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# serve scheduler
# ---------------------------------------------------------------------------

def test_decode_phase_spans_nest_in_step_and_counters_add_up():
    """pack, dispatch (the program's span inside it) and sync tile
    decode.step from its first pack to its last device_get; emit, admit
    and grow lie outside it. The always-on counters grow and add up to
    no more than the wall time they were taken in."""
    cfg = _tiny_cfg()
    with _lm_pd(cfg) as pd:
        svc = serve_decode(pd, cfg, num_pages=32, page_size=8, max_active=2,
                           warmup=False, decode_kernel=False)
        try:
            svc.generate([3, 5, 7], max_new=2)       # compiles, untraced
            st0 = svc.scheduler.snapshot_stats()
            t0 = clock.now()
            trace.enable()
            handles = [svc.generate_async(p, max_new=4)
                       for p in _prompts(cfg, 3)]
            for h in handles:
                h.result(300)
            trace.disable()
            t1 = clock.now()
            st1 = svc.scheduler.snapshot_stats()
        finally:
            svc.close()
    spans = trace.snapshot()
    steps = _named(spans, "decode.step")
    assert len(steps) == st1["steps"] - st0["steps"] > 0
    pack, disp, sync = (_named(spans, n) for n in PHASES)
    assert len(pack) == len(disp) == len(sync) == len(steps)
    for st, p, d, y in zip(steps, pack, disp, sync):
        assert _within(p, st) and _within(d, st) and _within(y, st)
        assert p["t1"] == d["t0"] and d["t1"] == y["t0"]
    for prog in _named(spans, "program.paged_decode_step"):
        assert any(_within(prog, d) for d in disp)
    # nothing but the phases and what nests in them lies inside a step
    for s in spans:
        if s["name"].startswith(("decode.emit", "decode.admit",
                                 "decode.grow", "decode.prefill")):
            assert not any(_within(s, st) for st in steps), s["name"]
    assert _named(spans, "decode.emit") and _named(spans, "decode.admit")
    assert _named(spans, "decode.grow")
    for prefill in _named(spans, "decode.prefill"):
        assert any(_within(prefill, a) for a in _named(spans,
                                                       "decode.admit"))
    deltas = {k: st1[k] - st0[k] for k in COUNTERS}
    assert all(v > 0 for v in deltas.values()), deltas
    assert sum(deltas.values()) <= t1 - t0
    assert st1["replay_prefills"] == st0["replay_prefills"] == 0


@pytest.mark.parametrize("speculative", [False, True])
def test_token_stamps_follow_admission_one_per_token(speculative):
    cfg = _tiny_cfg()
    with _lm_pd(cfg) as pd:
        svc = serve_decode(pd, cfg, num_pages=32, page_size=8, max_active=2,
                           warmup=False, decode_kernel=False,
                           speculative=speculative)
        try:
            handles = [svc.generate_async(p, max_new=6)
                       for p in _prompts(cfg, 3, seed=2)]
            gens = [h.result(300) for h in handles]
        finally:
            svc.close()
    for g in gens:
        assert len(g.token_times) == len(g.tokens) == 6
        assert g.t_enqueue <= g.t_admit <= g.token_times[0]
        assert all(a <= b for a, b in zip(g.token_times, g.token_times[1:]))


def test_preemption_replays_are_counted_and_spanned():
    """A pool too small for the load preempts; every re-admission's
    prefill is a replay, counted and spanned around its prefill, and the
    replayed sequences still carry one stamp per token."""
    cfg = _tiny_cfg()
    prompts = _prompts(cfg, 3, seed=1, lo=12, hi=13)
    with _lm_pd(cfg) as pd:
        svc = serve_decode(pd, cfg, num_pages=8, page_size=4, max_active=3,
                           warmup=False, decode_kernel=False)
        try:
            trace.enable()
            handles = [svc.generate_async(p, max_new=8) for p in prompts]
            gens = [h.result(300) for h in handles]
            trace.disable()
            st = svc.scheduler.snapshot_stats()
        finally:
            svc.close()
    assert st["preempted"] > 0, "pool sized to force preemption"
    assert st["replay_prefills"] == st["preempted"]
    assert st["prefills"] == len(prompts) + st["replay_prefills"]
    assert 0 < st["replay_prefill_s"] < st["prefill_s"]
    spans = trace.snapshot()
    replays = _named(spans, "decode.replay")
    assert len(replays) == st["replay_prefills"]
    prefills = _named(spans, "decode.prefill")
    for r in replays:
        assert sum(_within(p, r) for p in prefills) == 1
    for g in gens:
        assert len(g.token_times) == len(g.tokens) == 8


# ---------------------------------------------------------------------------
# compiles
# ---------------------------------------------------------------------------

_PROBE = itertools.count()


def test_compile_listener_counts_and_spans_first_calls_only():
    compiles.install()
    assert compiles.install() is compiles.install()      # one per process
    s0 = compiles.snapshot()
    f = jax.jit(lambda x: x * 3.0 + float(next(_PROBE)))
    jax.block_until_ready(f(jnp.ones((7,))))
    s1 = compiles.snapshot()
    assert s1["backend_compiles"] >= s0["backend_compiles"] + 1
    assert s1["compile_s"] > s0["compile_s"]
    cache = ProgramCache()
    snap = cache.snapshot_stats()
    assert snap["backend_compiles"] >= s1["backend_compiles"]
    assert snap["compile_s"] >= s1["compile_s"]

    spec = ProgramSpec(name="compile_probe", key=("probe", next(_PROBE)),
                       make=lambda ctx: (lambda x: jnp.sin(x) * 2.0),
                       in_kinds=("replicated",))
    x = jnp.ones((5,))
    prog = cache.program(spec, None, (x,))
    trace.enable()
    jax.block_until_ready(prog(x))
    first = trace.snapshot()
    trace.clear()
    jax.block_until_ready(prog(x))
    second = trace.snapshot()
    (call,) = _named(first, "program.compile_probe")
    comp = _named(first, "runtime.compile")
    assert {s["args"]["event"] for s in comp} >= {"trace", "backend"}
    assert all(_within(s, call) for s in comp)
    assert _named(second, "program.compile_probe")
    assert not _named(second, "runtime.compile")


def test_compile_union_counts_overlap_once():
    c = compiles.CompileClock()
    for lo, hi in ((0.0, 1.0), (5.0, 6.0), (0.5, 2.0), (2.0, 3.0),
                   (-1.0, 7.0), (10.0, 11.0), (8.0, 9.0)):
        c._add(lo, hi)
    assert c.compile_s == pytest.approx(8.0 + 1.0 + 1.0)
    assert c._merged == [(-1.0, 7.0), (8.0, 9.0), (10.0, 11.0)]


# ---------------------------------------------------------------------------
# fused training call and loader
# ---------------------------------------------------------------------------

def _linear_module():
    def init(rng):
        return {"w": jax.random.normal(rng, (3, 2))}

    def loss(p, b):
        return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2), {}

    return ParticleModule(init, loss, lambda p, b: b["x"] @ p["w"])


@pytest.mark.parametrize("algo,kw", [
    (DeepEnsemble, {"optimizer": sgd(0.1)}),
    (SteinVGD, {"lr": 0.05}),
    (MultiSWAG, {"optimizer": sgd(0.05), "max_rank": 2}),
], ids=["ensemble", "svgd", "swag"])
def test_fused_call_holds_epochs_and_loss_sync(algo, kw):
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 3))
    data = [{"x": x, "y": x @ jnp.ones((3, 2))}]
    trace.enable()
    with algo(_linear_module(), num_devices=1, backend="compiled") as inf:
        _, losses = inf.bayes_infer(data, epochs=2, num_particles=2, **kw)
    spans = trace.snapshot()
    assert len(losses) == 2
    (call,) = _named(spans, "bdl.fused_call")
    epochs = _named(spans, "bdl.epoch")
    assert len(epochs) == 2 and all(_within(e, call) for e in epochs)
    (wait,) = _named(spans, "bdl.device_wait")
    (sync,) = _named(spans, "bdl.loss_sync")
    assert _within(wait, call) and _within(sync, call)
    assert epochs[-1]["t1"] <= wait["t0"] and wait["t1"] <= sync["t0"]
    assert sync["args"] == {"particles": 2}


def test_loader_spans_each_batch():
    cfg = configs.get("vit-mnist").smoke()
    loader = DataLoader(cfg, batch_size=4, num_batches=3, seed=0)
    trace.enable()
    batches = list(loader)
    spans = _named(trace.snapshot(), "data.next")
    assert len(batches) == len(spans) == 3
    assert all(s["cat"] == "data" for s in spans)


# ---------------------------------------------------------------------------
# device-side scope names
# ---------------------------------------------------------------------------

def test_serving_and_training_programs_carry_scopes():
    """The paged programs and the fused ensemble step name their parts
    with jax.named_scope; the names reach the HLO's op metadata, where a
    device profile reads them."""
    cfg = _tiny_cfg()
    params = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    pages = jax.eval_shape(lambda: api.paged_cache_init(
        cfg, num_pages=4, page_size=8))
    i32 = jnp.int32
    dec = jax.jit(lambda p, t, pg, bt, sl: api.decode_step_paged(
        p, t, pg, bt, sl, cfg, decode_kernel=False)).lower(
        params, jax.ShapeDtypeStruct((2,), i32), pages,
        jax.ShapeDtypeStruct((2, 2), i32), jax.ShapeDtypeStruct((2,), i32))
    pre = jax.jit(lambda p, t, pg, bt, n: api.prefill_paged(
        p, t, pg, bt, n, cfg)).lower(
        params, jax.ShapeDtypeStruct((1, 8), i32), pages,
        jax.ShapeDtypeStruct((2,), i32), jax.ShapeDtypeStruct((), i32))
    for low in (dec, pre):
        text = low.as_text(debug_info=True)
        for scope in ("push.embed", "push.attention", "push.mlp",
                      "push.lm_head"):
            assert scope in text, scope
    mod = _linear_module()
    stacked = jax.vmap(mod.init)(jax.random.split(jax.random.PRNGKey(0), 2))
    opt = sgd(0.1)
    state = jax.vmap(opt.init)(stacked)
    batch = {"x": jnp.ones((4, 3)), "y": jnp.ones((4, 2))}
    text = jax.jit(functional.ensemble_step(mod.loss, opt)).lower(
        stacked, state, batch).as_text(debug_info=True)
    assert "push.loss_grad" in text and "push.optimizer" in text
