"""repro.obs: golden schema for every pd.stats() section (the keys are
the repo's observability contract — renaming one breaks dashboards),
tracer semantics (nesting, ring bound, disabled no-op), the latency
Histogram, the Chrome/Perfetto + Prometheus exporters, per-Program cost
attribution, the latency-percentile dedup regression (service stats
keys byte-identical after the Histogram collapse), and the program's own
accounting of where the host's time goes: decode-step phases, replays,
token stamps, compiles, the fused training call's edges."""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ParticleModule, PushDistribution
from repro.obs import Obs, clock, export, metrics, summary, trace
from repro.optim import sgd
from repro.runtime import ProgramCache, specs
from repro.serve import serve


@pytest.fixture(autouse=True)
def _tracer_reset():
    """Tracing must never leak across tests (other suites assert
    counter deltas that instrumentation noise would perturb)."""
    yield
    trace.disable()
    trace.clear()


def _linear_module(out_dim: int = 4):
    def init(rng):
        return {"w": jax.random.normal(rng, (3, out_dim)),
                "b": jnp.zeros((out_dim,))}

    def loss(p, b):
        return jnp.mean((b["x"] @ p["w"] + p["b"] - b["y"]) ** 2), {}

    def fwd(p, b):
        return b["x"] @ p["w"] + p["b"]

    return ParticleModule(init, loss, fwd)


def _pd(n=3, backend="compiled"):
    pd = PushDistribution(_linear_module(), num_devices=1, backend=backend)
    for _ in range(n):
        pd.p_create(sgd(0.1))
    return pd


# ---------------------------------------------------------------------------
# golden schema: pd.stats() keys are the observability contract
# ---------------------------------------------------------------------------

GOLDEN = {
    "executor": {"dispatched", "completed", "pool_dispatched",
                 "queue_depths", "pool_depth", "max_queue_depth",
                 "wait_time_s", "run_time_s", "threads"},
    "dispatch": {"dispatches", "swaps_in", "swaps_out", "xdev_transfers"},
    "store": {"stacks", "unstacks", "row_flushes", "commits",
              "device_puts", "checkouts", "mask_invalidations",
              "capacity_growths", "slot_clones"},
    "program_cache": {"hits", "misses", "cold_compiles", "evictions",
                      "programs", "hit_rate", "backend_compiles",
                      "compile_s"},
    "lifecycle": {"capacity", "live", "free_slots", "generation",
                  "mask_invalidations", "capacity_growths", "clones",
                  "kills", "rebalances"},
    "placement": {"mesh_shape", "mode", "particle_axis", "model_axis",
                  "model_axis_size", "per_device_param_bytes", "reshards"},
    "obs": {"tracing_enabled", "spans_recorded", "spans_buffered",
            "spans_dropped", "ring", "clock"},
}


def test_stats_golden_schema():
    pd = _pd()
    try:
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 3))
        pd.p_predict({"x": x})
        st = pd.stats()
        assert st["backend"] == "compiled"
        for section, keys in GOLDEN.items():
            assert set(st[section]) == keys, \
                f"stats()[{section!r}] keys drifted"
        obs = st["obs"]
        assert obs["clock"] == "perf_counter"
        assert isinstance(obs["tracing_enabled"], bool)
        assert obs["ring"] >= 1
    finally:
        pd.cleanup()


def test_serve_stats_latency_keys_regression():
    """The three duplicated latency implementations collapsed onto one
    obs.metrics Histogram — every historical service stats key must
    survive, and the percentiles must equal np.percentile over the same
    ring the batcher reports via latencies_s()."""
    pd = _pd()
    try:
        with serve(pd, kind="regress", max_batch=4, max_wait_ms=1.0) as svc:
            xs = jax.random.normal(jax.random.PRNGKey(1), (9, 3))
            for i in range(9):
                svc.predict({"x": xs[i]})
            st = svc.stats()
            for k in ("latency_p50_ms", "latency_p95_ms", "latency_p99_ms",
                      "requests_per_s", "requests", "batches", "rows",
                      "padded_rows", "size_flushes", "deadline_flushes",
                      "close_flushes", "max_queue_depth", "errors",
                      "h2d_transfers", "queue_depth", "staging_builds",
                      "staging_reuses", "occupancy", "engine"):
                assert k in st, f"service stats lost {k!r}"
            lat = svc.batcher.latencies_s()
            assert len(lat) == 9
            for q, key in ((50, "latency_p50_ms"), (95, "latency_p95_ms"),
                           (99, "latency_p99_ms")):
                want = float(np.percentile(np.asarray(lat), q)) * 1e3
                assert st[key] == pytest.approx(want)
            assert st["latency_p99_ms"] >= st["latency_p50_ms"] > 0.0
    finally:
        pd.cleanup()


def test_decode_stats_golden_schema():
    """pd.stats() grows the decode section while a DecodeScheduler
    serves the store; its keys are part of the contract too."""
    from repro import configs
    from repro.models import api
    from repro.serve import serve_decode

    cfg = configs.get("qwen1.5-0.5b").replace(
        n_units=1, d_model=16, n_heads=2, n_kv_heads=1, head_dim=8,
        d_ff=32, vocab_size=64, max_seq_len=64)
    module = ParticleModule(
        init=lambda rng: api.init_params(rng, cfg),
        loss=lambda p, b: api.loss_fn(p, b, cfg),
        forward=lambda p, b: api.forward(p, b, cfg)[0], cfg=cfg)
    pd = PushDistribution(module, num_devices=1)
    pd.p_create()
    try:
        svc = serve_decode(pd, cfg, num_pages=16, page_size=8,
                           max_active=2, warmup=False, decode_kernel=False)
        try:
            g = svc.generate([3, 7, 11], max_new=3)
            assert len(g.tokens) == 3
            dec = pd.stats()["decode"]
            assert set(dec) == {
                "submitted", "admitted", "retired", "preempted", "steps",
                "prefills", "generated_tokens", "active_row_steps",
                "admission_blocked", "h2d_transfers", "errors",
                "max_queue_depth", "queue_depth", "active_seqs",
                "max_active", "row_occupancy", "pool", "kv_pages",
                "speculative", "pack_s", "dispatch_s", "sync_s", "emit_s",
                "prefill_s", "replay_prefill_s", "replay_prefills",
                "kv_pages_live", "kv_page_slots"}
            assert set(dec["kv_pages"]) == {"key", "dtypes",
                                            "per_device_bytes"}
            assert dec["kv_pages"]["key"] == "kv_pages"
            assert dec["speculative"] is None   # plain scheduler
            st = svc.stats()
            assert st["latency_p99_ms"] >= st["latency_p50_ms"] > 0.0
            assert st["tokens_per_s"] > 0.0
            lat = svc.scheduler.latencies_s()
            assert st["latency_p50_ms"] == pytest.approx(
                metrics.percentile(lat, 50) * 1e3)
        finally:
            svc.close()
    finally:
        pd.cleanup()


# ---------------------------------------------------------------------------
# tracer semantics
# ---------------------------------------------------------------------------

def test_span_nesting_records_on_exit():
    trace.clear()
    trace.enable()
    with trace.span("outer", "t"):
        with trace.span("inner", "t", k=1):
            pass
    spans = trace.snapshot()
    names = [s["name"] for s in spans]
    # inner exits first, and its interval nests inside outer's
    assert names == ["inner", "outer"]
    inner, outer = spans
    assert outer["t0"] <= inner["t0"] and inner["t1"] <= outer["t1"]
    assert inner["args"] == {"k": 1}
    assert inner["tid"] == outer["tid"] == threading.get_ident()


def test_ring_bound_and_drop_accounting():
    trace.clear()
    trace.enable(ring=8)
    try:
        for i in range(20):
            trace.instant(f"e{i}", "t")
        c = trace.TRACER.counts()
        assert c == {"recorded": 20, "buffered": 8, "dropped": 12}
        # the ring keeps the NEWEST spans
        assert [s["name"] for s in trace.snapshot()] == \
            [f"e{i}" for i in range(12, 20)]
    finally:
        trace.enable(ring=trace._DEFAULT_RING)
        trace.disable()
        trace.clear()


def test_disabled_path_is_noop():
    trace.disable()
    trace.clear()
    s = trace.span("x", "t")
    assert s is trace.span("y", "t")     # shared no-op singleton
    with s:
        pass
    trace.instant("z", "t")
    assert trace.snapshot() == []
    assert trace.TRACER.counts()["recorded"] == 0


def test_traced_decorator_and_track_names():
    trace.clear()
    trace.enable()

    @trace.traced(cat="fn")
    def work(a, b):
        return a + b

    assert work(2, 3) == 5
    spans = trace.snapshot()
    assert len(spans) == 1 and spans[0]["name"].endswith("work")
    trace.TRACER.name_track("my-track")
    assert trace.TRACER.track_names()[threading.get_ident()] == "my-track"


def test_runtime_spans_emitted():
    """A traced fused predict leaves runtime spans in the ring with the
    documented names (cold compile, then a hit, then the dispatch)."""
    trace.clear()
    trace.enable()
    pd = _pd()
    try:
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 3))
        pd.p_predict({"x": x})
        pd.p_predict({"x": x})       # second call: a cache.hit instant
        names = {s["name"] for s in trace.snapshot()}
        cats = {s["cat"] for s in trace.snapshot()}
        assert "store.generation_bump" in names     # the p_creates
        assert "runtime.lower" in names and "cache.hit" in names \
            and "cache.miss" in names
        assert "program.ensemble_predict" in names
        assert {"store", "runtime"} <= cats
    finally:
        pd.cleanup()


def test_executor_spans_emitted():
    """NEL dispatch: every work item gets an executor.run span carrying
    its queue + mailbox wait, on a named worker track."""
    trace.clear()
    trace.enable()
    pd = _pd(backend="nel")
    try:
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 3))
        pd.p_predict({"x": x})
        pd.drain(10.0)
        runs = [s for s in trace.snapshot() if s["name"] == "executor.run"]
        assert len(runs) >= 3                       # one forward/particle
        assert all(s["cat"] == "executor" for s in runs)
        assert all(s["args"]["wait_ms"] >= 0 for s in runs)
        tracks = trace.TRACER.track_names()
        assert any(n.startswith("push-dev") for n in
                   (tracks.get(s["tid"], "") for s in runs))
    finally:
        pd.cleanup()


def test_bdl_epoch_spans():
    from repro.bdl import DeepEnsemble
    trace.clear()
    trace.enable()
    mod = _linear_module()
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 3))
    data = [{"x": x, "y": x @ jnp.ones((3, 4))}]
    infer = DeepEnsemble(mod, num_devices=1, backend="compiled")
    try:
        infer.bayes_infer(data, epochs=3, optimizer=sgd(0.1),
                          num_particles=2)
        spans = trace.snapshot()
        epochs = [s for s in spans if s["name"] == "bdl.epoch"]
        assert len(epochs) == 3
        assert [s["args"]["epoch"] for s in epochs] == [0, 1, 2]
        assert all(s["args"]["algo"] == "ensemble" for s in epochs)
        # fused training goes through the store's checkout/commit window
        names = {s["name"] for s in spans}
        assert "store.checkout" in names and "store.commit" in names
    finally:
        infer.cleanup()


# ---------------------------------------------------------------------------
# latency histogram
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram():
    h = metrics.Histogram("lat", ring=4)
    for v in (1.0, 2.0, 3.0, 4.0, 5.0):
        h.observe(v)
    assert h.count == 5 and h.sum == 15.0
    assert h.values() == [2.0, 3.0, 4.0, 5.0]       # ring dropped 1.0
    snap = h.snapshot()
    assert snap["count"] == 5 and snap["p50"] == 3.5
    assert h.percentile(100) == 5.0


def test_percentile_matches_numpy_and_empty():
    xs = [0.3, 0.1, 0.9, 0.5, 0.7]
    for q in (0, 50, 95, 99, 100):
        assert metrics.percentile(xs, q) == pytest.approx(
            float(np.percentile(np.asarray(xs), q)))
    assert metrics.percentile([], 99) == 0.0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_chrome_trace_structure_and_roundtrip(tmp_path):
    trace.clear()
    trace.enable()
    trace.TRACER.name_track("main-test-track")
    with trace.span("work", "store", key="params"):
        pass
    trace.instant("mark", "decode", sid=7)
    doc = export.chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert any(e["args"]["name"] == "main-test-track" for e in meta)
    complete = [e for e in evs if e["ph"] == "X"]
    assert len(complete) == 1
    ev = complete[0]
    assert ev["name"] == "work" and ev["cat"] == "store"
    assert ev["dur"] >= 0 and ev["ts"] >= 0        # µs since clock.EPOCH
    assert ev["args"] == {"key": "params"}
    inst = [e for e in evs if e["ph"] == "i"]
    assert len(inst) == 1 and inst[0]["s"] == "t"
    assert inst[0]["args"] == {"sid": 7}

    path = export.dump_chrome_trace(str(tmp_path / "t.json"))
    with open(path) as f:
        loaded = json.load(f)                       # valid JSON on disk
    assert loaded["traceEvents"]


def test_clock_to_us():
    t = clock.now()
    assert clock.to_us(t) >= 0.0
    assert clock.to_us(clock.EPOCH + 1.0) - clock.to_us(clock.EPOCH) \
        == pytest.approx(1e6)


def test_prometheus_text():
    """The exporter renders the pd.stats() snapshot it is given: every
    numeric leaf (nested dicts, lists, bools) a gauge, names sanitized,
    strings and None dropped."""
    pd = _pd()
    try:
        pd.p_predict({"x": jax.random.normal(jax.random.PRNGKey(0), (4, 3))})
        st = pd.stats()
        text = export.prometheus_text(st)
        hits = st["program_cache"]["hits"]
        assert "# TYPE repro_program_cache_hits gauge" in text
        assert f"repro_program_cache_hits {float(hits)}" in text
        assert "repro_program_cache_compile_s " in text
        assert "repro_lifecycle_live 3.0" in text
        assert "repro_obs_tracing_enabled 0.0" in text      # a bool
        assert "perf_counter" not in text                   # a string
    finally:
        pd.cleanup()
    text = export.prometheus_text(
        {"serve": {"p99 (ms)": 1.5, "name": "drop-me", "gaps": [2, None]}})
    assert "repro_serve_p99__ms_ 1.5" in text       # sanitized name
    assert "repro_serve_gaps_0 2.0" in text
    assert "drop-me" not in text and "gaps_1" not in text


# ---------------------------------------------------------------------------
# per-Program cost attribution
# ---------------------------------------------------------------------------

def test_program_cost_attribution():
    """Every ProgramCache entry exposes FLOPs / bytes accessed /
    per-device param bytes (the ISSUE's acceptance bar). A private cache
    keeps this test independent of whatever the global cache holds."""
    cache = ProgramCache()
    mod = _linear_module()
    stacked = jax.vmap(mod.init)(
        jax.random.split(jax.random.PRNGKey(0), 3))
    batch = {"x": jnp.ones((4, 3))}
    mask = jnp.ones((3,), jnp.float32)
    spec = specs.ensemble_predict(mod.forward)
    prog = cache.program(spec, None, (stacked, batch, mask))

    entries = cache.program_costs()                 # lazy: nothing computed
    assert len(entries) == 1
    e = entries[0]
    assert e["name"] == spec.name and e["cost"] is None
    assert e["num_particles"] == 3
    assert len(e["fingerprint"]) == 16
    # params: 3 particles x (3x4 w + 4 b) x f32, one device
    assert e["param_bytes_per_device"] == 3 * (12 + 4) * 4

    cost = prog.cost()                              # one analysis compile
    assert cost["flops"] > 0 and cost["bytes_accessed"] > 0
    assert cost["param_bytes_per_device"] == e["param_bytes_per_device"]
    assert cost["memory"]["argument_bytes"] > 0
    assert cost["loop_aware"]["flops"] > 0
    assert prog.cost() is cost                      # memoized
    assert cache.program_costs()[0]["cost"] is cost
    assert cache.program_costs(compute=True)[0]["cost"] is cost


# ---------------------------------------------------------------------------
# the pd.obs() front-end
# ---------------------------------------------------------------------------

def test_obs_front_end(tmp_path):
    pd = _pd()
    try:
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 3))
        trace.enable()
        pd.p_predict({"x": x})
        obs = pd.obs()
        assert isinstance(obs, Obs)
        snap = obs.snapshot()
        assert set(snap) == {"stats", "devices", "store", "programs",
                             "trace"}
        assert snap["devices"] and "platform" in snap["devices"][0]
        sg = snap["store"]
        assert sg["live"] == 3 and sum(sg["live_mask"]) == 3
        assert sg["per_device_bytes"]["params"] > 0
        assert snap["trace"]["recorded"] > 0
        path = obs.dump_trace(str(tmp_path / "pd.json"))
        with open(path) as f:
            doc = json.load(f)
        assert any(e.get("ph") == "X" for e in doc["traceEvents"])
        text = obs.prometheus()
        assert "repro_program_cache_hits" in text
        s = summary()
        assert s["tracing_enabled"] and s["spans_recorded"] > 0
    finally:
        pd.cleanup()
