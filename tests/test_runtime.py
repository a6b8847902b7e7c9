"""repro.runtime — the shared plan/compile/execute layer (DESIGN.md §8).

Covers the cache-key anatomy (spec identity, placement, store generation,
bucketed shapes), cross-subsystem program reuse (train -> predict ->
serve over one store), the Runtime protocol seam, the unified
PushDistribution.stats() surface, and the AOT serialization hook.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bdl import DeepEnsemble
from repro.core import ParticleModule, Placement, PushDistribution
from repro.optim import sgd
from repro.runtime import (BACKENDS, CompiledRuntime, NelRuntime,
                           ProgramCache, ProgramSpec, Runtime, bucket_size,
                           global_cache, ident, jit_program, make_runtime,
                           pad_rows, specs)
from repro.serve import PredictiveEngine


def _module():
    def init(rng):
        k1, k2 = jax.random.split(rng)
        return {"w": jax.random.normal(k1, (3, 4)) * 0.5,
                "b": jnp.zeros((4,))}

    def loss(p, b):
        return jnp.mean((b[0] @ p["w"] + p["b"] - b[1]) ** 2), {}

    def fwd(p, batch):
        return batch["x"] @ p["w"] + p["b"]

    return ParticleModule(init, loss, fwd)


def _data(m=8):
    x = jax.random.normal(jax.random.PRNGKey(0), (m, 3))
    return [(x, x @ jnp.ones((3, 4)))], x


# ---------------------------------------------------------------------------
# ProgramCache key anatomy
# ---------------------------------------------------------------------------

def _double_spec(tag="double"):
    return ProgramSpec(name=tag, key=(tag,),
                       make=lambda ctx: lambda s, b: (s, b * 2.0),
                       in_kinds=("state", "replicated"))


def test_cache_hit_miss_cold_stats():
    cache = ProgramCache()
    spec = _double_spec()
    st = jnp.ones((2, 3))
    b = jnp.ones((4,))
    before = cache.snapshot_stats()
    cache.run(spec, st, b)
    s = cache.snapshot_stats()
    comp = {k: s.pop(k) for k in ("backend_compiles", "compile_s")}
    assert s == {
        "hits": 0, "misses": 1, "cold_compiles": 1, "evictions": 0,
        "programs": 1, "hit_rate": 0.0}
    # the process's compile listener heard the first call's XLA compile
    assert comp["backend_compiles"] >= before["backend_compiles"] + 1
    assert comp["compile_s"] > before["compile_s"]
    cache.run(spec, st, b)
    s = cache.snapshot_stats()
    assert s["hits"] == 1 and s["cold_compiles"] == 1
    assert s["hit_rate"] == 0.5


def test_cache_distinguishes_shapes_and_specs():
    cache = ProgramCache()
    spec = _double_spec()
    st = jnp.ones((2, 3))
    cache.run(spec, st, jnp.ones((4,)))
    cache.run(spec, st, jnp.ones((8,)))          # new shape: miss
    cache.run(_double_spec("other"), st, jnp.ones((4,)))  # new spec: miss
    assert cache.snapshot_stats()["cold_compiles"] == 3


def test_bucketed_batch_shapes_share_programs():
    """Mixed request sizes inside one power-of-two bucket compile once."""
    cache = ProgramCache()
    spec = _double_spec()
    st = jnp.ones((2, 3))
    for m in (3, 4, 2, 4):
        padded = pad_rows(jnp.ones((m, 5)), bucket_size(m))
        cache.run(spec, st, padded)
    s = cache.snapshot_stats()
    # buckets: 4, 4, 2, 4 -> programs for bucket 4 and bucket 2 only
    assert s["cold_compiles"] == 2 and s["hits"] == 2


def test_placement_change_invalidates():
    cache = ProgramCache()
    spec = _double_spec()
    st = jnp.ones((2, 3))
    b = jnp.ones((4,))
    cache.run(spec, st, b, placement=Placement())
    cache.run(spec, st, b, placement=Placement(particle_axis="other"))
    assert cache.snapshot_stats()["cold_compiles"] == 2
    cache.run(spec, st, b, placement=Placement())   # original still cached
    assert cache.snapshot_stats()["hits"] == 1


def test_replacement_same_2d_plan_is_100pct_warm_hit():
    """ISSUE-7 acceptance: the cache invalidates on mesh-shape / mode
    change and NOTHING else — re-placement onto the same 2D plan through
    a separately-built (but identical) mesh is a pure warm hit."""
    from repro.launch.mesh import make_bench_mesh
    n = len(jax.devices())
    m = 2 if n > 1 and n % 2 == 0 else 1
    cache = ProgramCache()
    spec = _double_spec()
    st, b = jnp.ones((2, 3)), jnp.ones((4,))
    cache.run(spec, st, b,
              placement=Placement(mesh=make_bench_mesh(n, model=m)))
    # same plan, rebuilt mesh object: 100% warm, zero recompiles
    for _ in range(3):
        cache.run(spec, st, b,
                  placement=Placement(mesh=make_bench_mesh(n, model=m)))
    s = cache.snapshot_stats()
    assert s["cold_compiles"] == 1 and s["hits"] == 3, s
    # mode change invalidates
    cache.run(spec, st, b, placement=Placement(
        mesh=make_bench_mesh(n, model=m), mode="fsdp_tp"))
    assert cache.snapshot_stats()["cold_compiles"] == 2
    if m == 2:  # mesh-shape change invalidates (needs >= 2 devices)
        cache.run(spec, st, b,
                  placement=Placement(mesh=make_bench_mesh(n, model=1)))
        assert cache.snapshot_stats()["cold_compiles"] == 3


def test_placement_plan_equality_and_hash():
    """Placement equality is by plan value, not mesh object identity."""
    from repro.launch.mesh import make_bench_mesh
    n = len(jax.devices())
    a = Placement(mesh=make_bench_mesh(n))
    b = Placement(mesh=make_bench_mesh(n))
    assert a == b and hash(a) == hash(b)
    assert a != Placement(mesh=make_bench_mesh(n), mode="fsdp_tp")
    assert a != Placement()                 # mesh vs no mesh
    assert Placement() == Placement() and hash(Placement()) == \
        hash(Placement())


def test_state_token_invalidates():
    cache = ProgramCache()
    spec = _double_spec()
    st, b = jnp.ones((2, 3)), jnp.ones((4,))
    cache.run(spec, st, b, state_token=1)
    cache.run(spec, st, b, state_token=1)
    cache.run(spec, st, b, state_token=2)
    s = cache.snapshot_stats()
    assert s["hits"] == 1 and s["cold_compiles"] == 2


def test_lru_eviction_bounds_cache():
    cache = ProgramCache(max_programs=2)
    spec = _double_spec()
    st = jnp.ones((2, 3))
    for m in (1, 2, 3):
        cache.run(spec, st, jnp.ones((m,)))
    s = cache.snapshot_stats()
    assert s["programs"] == 2 and s["evictions"] == 1
    cache.run(spec, st, jnp.ones((1,)))   # evicted: recompiles
    assert cache.snapshot_stats()["cold_compiles"] == 4


def test_donation_plan_is_part_of_key():
    """dataclasses.replace(spec, donate=()) variants must not collide
    with the donating program (compiled_ensemble_step vs epoch loop)."""
    import dataclasses
    cache = ProgramCache()
    spec = ProgramSpec(name="don", key=("don",),
                       make=lambda ctx: lambda s, b: (
                           jax.tree.map(lambda x: x + 1.0, s), b),
                       in_kinds=("state", "replicated"),
                       out_kinds=("in:0", "replicated"), donate=(0,))
    no_don = dataclasses.replace(spec, donate=())
    st = jnp.zeros((2, 3))
    cache.run(spec, st, jnp.ones((4,)))          # donates st
    out, _ = cache.run(no_don, st2 := jnp.zeros((2, 3)), jnp.ones((4,)))
    assert cache.snapshot_stats()["cold_compiles"] == 2
    # the non-donating program left its input alive
    assert not st2.is_deleted()
    np.testing.assert_allclose(np.asarray(out), 1.0)


def test_compile_helpers_share_runtime_programs_via_state_token():
    """functional.compile_* with state_token=store.generation() returns
    the exact program the Runtime's epoch loop lowered (a cache hit)."""
    from repro.core import functional
    mod = _module()
    data, x = _data()
    opt = sgd(0.05)
    with DeepEnsemble(mod, backend="compiled") as de:
        de.bayes_infer(data, 2, optimizer=opt, num_particles=4)
        hits0 = global_cache().snapshot_stats()["hits"]
        tok = de.store.generation()
        mask = de.store.active_mask()
        st = de.store.checkout("params", None)
        ost = de.store.checkout("opt_state", None)
        step = functional.compile_ensemble_step(
            mod.loss, opt, de.placement, st, ost, data[0], mask,
            state_token=tok)
        assert global_cache().snapshot_stats()["hits"] == hits0 + 1
        np_, no_, _ = step(st, ost, data[0], mask)
        de.store.commit("params", np_)
        de.store.commit("opt_state", no_)


def test_ident_is_stable_and_distinct():
    f, g = (lambda x: x), (lambda x: x)
    assert ident(f) == ident(f)
    assert ident(f) != ident(g)


# ---------------------------------------------------------------------------
# spec lowering semantics
# ---------------------------------------------------------------------------

def test_donated_state_round_trips():
    """donate + "in:0" round trip: the epoch-loop pattern — output can be
    fed straight back as the next input with the same cache key."""
    cache = ProgramCache()
    spec = ProgramSpec(name="inc", key=("inc",),
                       make=lambda ctx: lambda s, b: (
                           jax.tree.map(lambda x: x + b.sum(), s), b),
                       in_kinds=("state", "replicated"),
                       out_kinds=("in:0", "replicated"), donate=(0,))
    st = {"w": jnp.zeros((2, 3))}
    b = jnp.ones((4,))
    for _ in range(3):
        st, b = cache.run(spec, st, b)
    assert float(st["w"][0, 0]) == 12.0
    s = cache.snapshot_stats()
    assert s["cold_compiles"] == 1 and s["hits"] == 2


def test_bad_kinds_rejected():
    with pytest.raises(ValueError):
        ProgramSpec(name="x", key=("x",), make=lambda ctx: None,
                    in_kinds=("bogus",))
    with pytest.raises(ValueError):
        ProgramSpec(name="x", key=("x",), make=lambda ctx: None,
                    in_kinds=("state",), out_kinds=("bogus",))


def test_jit_program_shares_across_fresh_closures():
    """jit_program keys on (key, shapes) only — fresh closures per call
    (the baselines' and NEL's pattern) still share one program."""
    cache = global_cache()
    before = cache.snapshot_stats()["cold_compiles"]
    x = jnp.ones((3,))
    for i in range(3):
        out = jit_program("t_jp", ("t_jp", "stable"),
                          lambda a: a * 2.0, (x,))(x)
    assert np.allclose(np.asarray(out), 2.0)
    after = cache.snapshot_stats()["cold_compiles"]
    assert after - before == 1


# ---------------------------------------------------------------------------
# Runtime protocol seam
# ---------------------------------------------------------------------------

def test_make_runtime_selects_objects():
    mod = _module()
    with PushDistribution(mod, backend="nel") as pd:
        assert isinstance(pd.runtime, NelRuntime)
        assert isinstance(pd.runtime, Runtime)
        assert pd.backend == "nel"
    with PushDistribution(mod, backend="compiled") as pd:
        assert isinstance(pd.runtime, CompiledRuntime)
        assert pd.backend == "compiled"
    # a bad backend must raise BEFORE the NodeEventLoop spawns executor
    # threads (nothing would ever shut them down)
    import threading
    n0 = threading.active_count()
    with pytest.raises(ValueError):
        PushDistribution(mod, backend="bogus")
    assert threading.active_count() == n0
    assert BACKENDS == ("nel", "compiled")


def test_pd_stats_merges_executor_and_cache():
    mod = _module()
    data, x = _data()
    with DeepEnsemble(mod, backend="compiled") as de:
        de.bayes_infer(data, 2, optimizer=sgd(0.05), num_particles=2)
        st = de.push_dist.stats()
    assert st["backend"] == "compiled"
    assert "wait_time_s" in st["executor"] and "run_time_s" in st["executor"]
    for k in ("hits", "misses", "cold_compiles", "hit_rate"):
        assert k in st["program_cache"], k
    assert st["store"]["commits"] >= 1


def test_nel_particles_share_one_compiled_step():
    """Two NEL particles stepping the same module compile ONE program:
    the NEL backend's compiles go through the shared layer too."""
    mod = _module()
    data, x = _data()
    before = global_cache().snapshot_stats()["cold_compiles"]
    with DeepEnsemble(mod, backend="nel") as de:
        de.bayes_infer(data, 2, optimizer=sgd(0.05), num_particles=3)
    after = global_cache().snapshot_stats()["cold_compiles"]
    # one value_and_grad program total (optimizer update runs un-jitted
    # inside the dispatch, as before)
    assert after - before == 1


# ---------------------------------------------------------------------------
# cross-subsystem reuse over one store
# ---------------------------------------------------------------------------

def test_train_then_serve_zero_recompiles_when_version_unchanged():
    mod = _module()
    data, x = _data()
    with DeepEnsemble(mod, backend="compiled") as de:
        de.bayes_infer(data, 2, optimizer=sgd(0.05), num_particles=4)
        v = de.store.version("params")
        with de.posterior_predictive(kind="regress") as svc:
            svc.predict_batch({"x": x})          # cold compile here
        cold = global_cache().snapshot_stats()["cold_compiles"]
        # same store, same version, fresh engine: every program is a hit
        assert de.store.version("params") == v
        with de.posterior_predictive(kind="regress") as svc2:
            svc2.predict_batch({"x": x})
            svc2.predict_batch({"x": x[:5]})     # same bucket as 8: pad
        assert global_cache().snapshot_stats()["cold_compiles"] == cold


def test_train_more_epochs_reuses_program_across_calls():
    mod = _module()
    data, x = _data()
    opt = sgd(0.05)
    with DeepEnsemble(mod, backend="compiled") as de:
        de.bayes_infer(data, 2, optimizer=opt, num_particles=4)
        cold = global_cache().snapshot_stats()["cold_compiles"]
        pids = de.push_dist.particle_ids()
        de._fused_epochs(pids, data, 2, optimizer=opt)
        assert global_cache().snapshot_stats()["cold_compiles"] == cold


def test_particle_set_change_invalidates_predict():
    """p_create bumps the store generation: the fused predict program
    recompiles (new n), and stale-program reuse is impossible."""
    mod = _module()
    data, x = _data()
    with DeepEnsemble(mod, backend="compiled") as de:
        de.bayes_infer(data, 1, optimizer=sgd(0.05), num_particles=2)
        de.posterior_pred({"x": x})
        cold = global_cache().snapshot_stats()["cold_compiles"]
        de.push_dist.p_create(sgd(0.05))
        de.posterior_pred({"x": x})
        assert global_cache().snapshot_stats()["cold_compiles"] == cold + 1


def test_engine_private_cache_isolated():
    """cache= lets an engine run on a private ProgramCache (tests /
    multi-tenant isolation) without touching the process-wide one."""
    mod = _module()
    data, x = _data()
    cache = ProgramCache()
    with DeepEnsemble(mod, backend="compiled") as de:
        de.bayes_infer(data, 1, optimizer=sgd(0.05), num_particles=2)
        g_before = global_cache().snapshot_stats()["cold_compiles"]
        eng = PredictiveEngine(mod.forward, store=de.store, kind="regress",
                               cache=cache)
        eng.predict({"x": x})
        eng.predict({"x": x})
        s = cache.snapshot_stats()
        assert s["cold_compiles"] == 1 and s["hits"] == 1
        assert global_cache().snapshot_stats()["cold_compiles"] == g_before


def test_fused_predict_matches_nel_predict():
    """Same store, both runtimes: the fused predict program and the NEL
    per-particle average agree (the seam invariant, now object-based)."""
    mod = _module()
    data, x = _data()
    with DeepEnsemble(mod, backend="compiled") as de:
        de.bayes_infer(data, 2, optimizer=sgd(0.05), num_particles=3)
        pd = de.push_dist
        fused = np.asarray(pd.runtime.predict(pd, {"x": x}))
        nel = np.asarray(NelRuntime(pd).predict(pd, {"x": x}))
        assert np.abs(fused - nel).max() < 1e-5


# ---------------------------------------------------------------------------
# AOT serialization hook
# ---------------------------------------------------------------------------

def test_aot_dump_and_preload(tmp_path):
    jax_export = pytest.importorskip("jax.export")
    cache = ProgramCache()
    spec = _double_spec("aot")
    st, b = jnp.ones((2, 3)), jnp.arange(4.0)
    cache.run(spec, st, b)
    manifest = cache.aot_dump(str(tmp_path))
    assert manifest and all(v == "aot" for v in manifest.values())
    blobs = list(tmp_path.glob("*.jaxprog"))
    assert len(blobs) == len(manifest)

    fresh = ProgramCache()
    fresh.preload(spec, None, (st, b), blobs[0].read_bytes())
    _, out = fresh.run(spec, st, b)
    assert np.allclose(np.asarray(out), np.arange(4.0) * 2.0)
    s = fresh.snapshot_stats()
    # served from the preloaded artifact: a miss but NOT a cold compile
    assert s["misses"] == 1 and s["cold_compiles"] == 0
