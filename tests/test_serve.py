"""repro.serve: micro-batcher semantics (flush triggers, padding,
backpressure), fused-BMA numerical parity, calibration metrics vs their
NumPy references, store-aware checkpoint round trips, the SWAG serving
handoff, and the sharded subprocess check (serving must read the store
without unsharding it)."""
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ParticleModule, ParticleStore, PushDistribution
from repro.optim import sgd
from repro.serve import (MicroBatcher, PredictiveEngine, bucket_size,
                         metrics, pad_rows, serve, uncertainty)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _linear_module(out_dim: int = 4):
    def init(rng):
        return {"w": jax.random.normal(rng, (3, out_dim)),
                "b": jnp.zeros((out_dim,))}

    def loss(p, b):
        return jnp.mean((b["x"] @ p["w"] + p["b"] - b["y"]) ** 2), {}

    def fwd(p, b):
        return b["x"] @ p["w"] + p["b"]

    return ParticleModule(init, loss, fwd)


def _pd(n=4, seed=0):
    pd = PushDistribution(_linear_module(), num_devices=1, seed=seed)
    for _ in range(n):
        pd.p_create(sgd(0.1))
    return pd


# ---------------------------------------------------------------------------
# micro-batcher
# ---------------------------------------------------------------------------

class _Recorder:
    """predict_fn double: records every padded batch it was handed."""

    def __init__(self, gate=None, fail=False):
        self.batches = []
        self.gate = gate
        self.fail = fail

    def __call__(self, batch):
        if self.gate is not None:
            assert self.gate.wait(10.0)
        if self.fail:
            raise RuntimeError("model exploded")
        self.batches.append(batch)
        return {"y": batch["x"] * 2.0}


def test_batcher_size_trigger_flushes_full_batch():
    rec = _Recorder()
    with MicroBatcher(rec, max_batch=4, max_wait_ms=60_000) as mb:
        futs = [mb.submit({"x": jnp.full((2,), float(i))}) for i in range(4)]
        outs = [f.wait(10.0) for f in futs]
    for i, o in enumerate(outs):
        assert float(o["y"][0]) == 2.0 * i
    st = mb.snapshot_stats()
    assert st["size_flushes"] == 1 and st["deadline_flushes"] == 0
    assert st["batches"] == 1 and st["requests"] == 4


def test_batcher_deadline_trigger():
    rec = _Recorder()
    with MicroBatcher(rec, max_batch=64, max_wait_ms=50) as mb:
        t0 = time.monotonic()
        f = mb.submit({"x": jnp.ones((2,))})
        out = f.wait(10.0)
        waited = time.monotonic() - t0
    assert float(out["y"][0]) == 2.0
    assert waited >= 0.04, "flushed before the deadline"
    st = mb.snapshot_stats()
    assert st["deadline_flushes"] == 1 and st["size_flushes"] == 0


def test_batcher_pads_to_bucket_and_slices_back():
    rec = _Recorder()
    with MicroBatcher(rec, max_batch=8, max_wait_ms=20) as mb:
        futs = [mb.submit({"x": jnp.full((2,), float(i))}) for i in range(3)]
        outs = [f.wait(10.0) for f in futs]
    # three requests ride one power-of-two padded batch ...
    (batch,) = rec.batches
    assert batch["x"].shape == (4, 2)
    assert float(batch["x"][3, 0]) == 2.0      # pad = repeat of last row
    # ... and each caller gets exactly its own row back
    for i, o in enumerate(outs):
        assert o["y"].shape == (2,) and float(o["y"][0]) == 2.0 * i
    assert mb.snapshot_stats()["padded_rows"] == 1


def test_batcher_backpressure_blocks_submitters():
    gate = threading.Event()
    rec = _Recorder(gate=gate)
    mb = MicroBatcher(rec, max_batch=1, max_wait_ms=0, max_queue=2)
    try:
        futs = [mb.submit({"x": jnp.zeros((1,))}) for _ in range(3)]
        # pump holds one request inside predict_fn; the queue is full
        done = threading.Event()

        def blocked_submit():
            futs.append(mb.submit({"x": jnp.zeros((1,))}))
            done.set()

        t = threading.Thread(target=blocked_submit, daemon=True)
        t.start()
        assert not done.wait(0.3), "submit did not block on a full queue"
        gate.set()                      # unblock the model; queue drains
        assert done.wait(10.0), "backpressured submit never admitted"
        for f in futs:
            f.wait(10.0)
    finally:
        gate.set()
        mb.close()
    assert mb.snapshot_stats()["max_queue_depth"] <= 2


def test_batcher_propagates_model_errors():
    rec = _Recorder(fail=True)
    with MicroBatcher(rec, max_batch=2, max_wait_ms=10) as mb:
        f = mb.submit({"x": jnp.zeros((1,))})
        with pytest.raises(RuntimeError, match="model exploded"):
            f.wait(10.0)
    assert mb.snapshot_stats()["errors"] == 1


def test_batcher_rejects_after_close():
    rec = _Recorder()
    mb = MicroBatcher(rec, max_batch=2, max_wait_ms=10)
    mb.close()
    with pytest.raises(RuntimeError):
        mb.submit({"x": jnp.zeros((1,))})


def test_batcher_staging_buffers_one_h2d_per_flush():
    """Flushes fill preallocated per-bucket staging buffers in place:
    exactly one H2D per flush (h2d_transfers == batches), and a repeated
    bucket reuses its buffer instead of allocating (np.stack) again."""
    rec = _Recorder()
    with MicroBatcher(rec, max_batch=4, max_wait_ms=5) as mb:
        for round_ in range(3):
            futs = [mb.submit({"x": jnp.full((2,), float(i))})
                    for i in range(4)]
            for f in futs:
                f.wait(10.0)
    st = mb.snapshot_stats()
    assert st["h2d_transfers"] == st["batches"] == 3
    assert st["staging_builds"] == 1          # one buffer set per bucket
    assert st["staging_reuses"] == 2
    # buffer reuse across flushes never leaked rows between batches
    for round_, batch in enumerate(rec.batches):
        assert np.allclose(np.asarray(batch["x"])[:, 0], [0, 1, 2, 3])


def test_decode_scheduler_stats_surface():
    """pd.stats() grows a 'decode' section while a DecodeScheduler serves
    the store: active/queue/page-pool occupancy and the admission/retire/
    preempt counters (asserted end-to-end in test_paged.py)."""
    from repro import configs
    from repro.models import api as models_api
    from repro.serve import serve_decode

    cfg = configs.get("qwen1.5-0.5b").replace(
        n_units=1, d_model=16, n_heads=2, n_kv_heads=1, head_dim=8,
        d_ff=32, vocab_size=64, max_seq_len=64)
    lm = ParticleModule(
        init=lambda rng: models_api.init_params(rng, cfg),
        loss=lambda p, b: models_api.loss_fn(p, b, cfg),
        forward=lambda p, b: models_api.forward(p, b, cfg)[0], cfg=cfg)
    with PushDistribution(lm, num_devices=1, seed=0) as pd:
        pd.p_create()
        assert "decode" not in pd.stats()      # no scheduler yet
        svc = serve_decode(pd, cfg, num_pages=8, page_size=8,
                           max_active=2, warmup=False)
        try:
            g = svc.generate([3, 5, 7], max_new=3)
            assert len(g.tokens) == 3
            dec = pd.stats()["decode"]
            for k in ("active_seqs", "queue_depth", "admitted", "retired",
                      "preempted", "steps", "prefills", "row_occupancy",
                      "h2d_transfers"):
                assert k in dec, k
            assert dec["admitted"] == dec["retired"] == 1
            assert dec["h2d_transfers"] == dec["steps"] + dec["prefills"]
            pool = dec["pool"]
            assert pool["free_pages"] == pool["num_pages"] == 8
            assert pool["peak_used"] >= 1
        finally:
            svc.close()
        # scheduler gone -> the section unregisters with it
        import gc
        del svc
        gc.collect()
        assert "decode" not in pd.stats()


def test_bucket_and_pad_helpers():
    assert [bucket_size(m) for m in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]
    t = {"a": jnp.arange(6.0).reshape(3, 2)}
    p = pad_rows(t, 8)
    assert p["a"].shape == (8, 2)
    assert jnp.array_equal(p["a"][3:], jnp.broadcast_to(t["a"][-1:], (5, 2)))
    assert pad_rows(t, 3) is t


# ---------------------------------------------------------------------------
# engine: fused BMA parity + bucketed compile cache + store versioning
# ---------------------------------------------------------------------------

def test_fused_bma_matches_per_particle_loop():
    """Acceptance bar: engine BMA == sequential per-particle forward +
    host-side average, < 1e-5, for both head kinds."""
    pd = _pd(4)
    try:
        x = jax.random.normal(jax.random.PRNGKey(1), (7, 3))
        member = [np.asarray(x @ pd.p_params(p)["w"] + pd.p_params(p)["b"])
                  for p in pd.particle_ids()]
        stacked = np.stack(member)

        reg = PredictiveEngine(pd.module.forward, store=pd.store,
                               kind="regress")
        heads = reg.predict({"x": x})
        assert np.abs(np.asarray(heads["mean"]) - stacked.mean(0)).max() < 1e-5
        assert np.abs(np.asarray(heads["variance"])
                      - stacked.var(0)).max() < 1e-5

        cls = PredictiveEngine(pd.module.forward, store=pd.store,
                               kind="classify")
        heads = cls.predict({"x": x})
        def softmax(z):
            e = np.exp(z - z.max(-1, keepdims=True))
            return e / e.sum(-1, keepdims=True)
        probs = np.mean([softmax(m) for m in member], 0)
        assert np.abs(np.asarray(heads["mean"]) - probs).max() < 1e-5
        # uncertainty identities against a literal NumPy transcription
        mem_probs = np.stack([softmax(m) for m in member])
        ent = -(probs * np.log(probs + 1e-12)).sum(-1)
        exp_ent = np.mean(-(mem_probs * np.log(mem_probs + 1e-12)).sum(-1), 0)
        assert np.abs(np.asarray(heads["entropy"]) - ent).max() < 1e-5
        assert np.abs(np.asarray(heads["mutual_info"])
                      - np.maximum(ent - exp_ent, 0)).max() < 1e-5
    finally:
        pd.cleanup()


def test_engine_bucketed_compile_cache():
    pd = _pd(2)
    try:
        eng = PredictiveEngine(pd.module.forward, store=pd.store,
                               kind="regress")
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 3))
        eng.predict({"x": x[:3]})          # bucket 4: compile
        eng.predict({"x": x[:4]})          # bucket 4: hit
        eng.predict({"x": x[:5]})          # bucket 8: compile
        eng.predict({"x": x[:8]})          # bucket 8: hit
        st = eng.snapshot_stats()
        assert st["compiles"] == 2 and st["bucket_hits"] == 2
        assert st["programs"] == 2
    finally:
        pd.cleanup()


def test_engine_sees_store_commits_via_version():
    pd = _pd(2)
    try:
        eng = PredictiveEngine(pd.module.forward, store=pd.store,
                               kind="regress")
        x = jnp.ones((2, 3))
        before = np.asarray(eng.predict({"x": x})["mean"])
        new = jax.tree.map(jnp.zeros_like, pd.store.stacked("params"))
        pd.store.commit("params", new)
        after = np.asarray(eng.predict({"x": x})["mean"])
        assert np.abs(after).max() == 0.0 and np.abs(before).max() > 0.0
        assert eng.snapshot_stats()["param_refreshes"] == 2
    finally:
        pd.cleanup()


def test_engine_stateful_step_matches_per_particle_loop():
    """The LM-decode shape without the LM: per-particle serving state
    rides the stacked axis across steps; heads are BMA over member
    outputs; one compiled program reused across steps."""
    pd = _pd(3)
    try:
        def fwd(p, state, batch):
            out = batch["x"] @ p["w"] + p["b"] + state["acc"]
            return out, {"acc": state["acc"] + 1.0}

        eng = PredictiveEngine(fwd, store=pd.store, kind="regress",
                               stateful=True)
        with pytest.raises(RuntimeError):
            eng.predict({"x": jnp.ones((1, 3))})     # wrong entry point
        state = eng.init_state(lambda p: {"acc": jnp.zeros(())})
        # serving state is born capacity-padded (3 live -> capacity 4);
        # dead rows ride along masked out
        assert jax.tree.leaves(state)[0].shape[0] == pd.store.capacity == 4
        x = jax.random.normal(jax.random.PRNGKey(6), (4, 3))
        member = np.stack(
            [np.asarray(x @ pd.p_params(p)["w"] + pd.p_params(p)["b"])
             for p in pd.particle_ids()])
        for step in range(3):
            heads, state = eng.step(state, {"x": x})
            want = (member + step).mean(0)
            assert np.abs(np.asarray(heads["mean"]) - want).max() < 1e-5
        assert float(state["acc"][0]) == 3.0
        st = eng.snapshot_stats()
        assert st["compiles"] == 1 and st["bucket_hits"] == 2
    finally:
        pd.cleanup()


def test_engine_rejects_bad_construction():
    pd = _pd(1)
    try:
        with pytest.raises(ValueError):
            PredictiveEngine(pd.module.forward)          # no source
        with pytest.raises(ValueError):
            PredictiveEngine(pd.module.forward, store=pd.store,
                             params=pd.store.stacked("params"))
        with pytest.raises(ValueError):
            PredictiveEngine(pd.module.forward, store=pd.store, kind="nope")
    finally:
        pd.cleanup()


# ---------------------------------------------------------------------------
# service front-end
# ---------------------------------------------------------------------------

def test_service_concurrent_requests_end_to_end():
    pd = _pd(3)
    try:
        x = jax.random.normal(jax.random.PRNGKey(2), (16, 3))
        member = [np.asarray(x @ pd.p_params(p)["w"] + pd.p_params(p)["b"])
                  for p in pd.particle_ids()]
        want = np.mean(member, 0)
        with serve(pd, kind="regress", max_batch=8, max_wait_ms=5.0) as svc:
            svc.predict_batch({"x": x})     # warm the bucket-8 program
            results = {}

            def client(i):
                results[i] = svc.predict({"x": x[i]}, timeout=30.0)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30.0)
            assert len(results) == 16
            for i, pred in results.items():
                assert np.abs(np.asarray(pred.mean) - want[i]).max() < 1e-5
            st = svc.stats()
            assert st["requests"] == 16
            assert st["batches"] < 16, "no coalescing happened"
            assert st["latency_p99_ms"] >= st["latency_p50_ms"] >= 0.0
    finally:
        pd.cleanup()


def test_infer_posterior_predictive_handoff():
    from repro.bdl import DeepEnsemble
    mod = _linear_module()
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 3))
    data = [{"x": x, "y": x @ jnp.ones((3, 4))}]
    with DeepEnsemble(mod, num_devices=1, seed=0, backend="compiled") as de:
        de.bayes_infer(data, 2, optimizer=sgd(0.05), num_particles=2)
        with de.posterior_predictive(kind="regress",
                                     max_wait_ms=1.0) as svc:
            pred = svc.predict({"x": x[0]})
            want = np.mean([np.asarray(x[:1] @ de.push_dist.p_params(p)["w"]
                                       + de.push_dist.p_params(p)["b"])
                            for p in de.push_dist.particle_ids()], 0)[0]
            assert np.abs(np.asarray(pred.mean) - want).max() < 1e-5


# ---------------------------------------------------------------------------
# calibration metrics vs NumPy references
# ---------------------------------------------------------------------------

def test_metrics_match_numpy_references():
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((64, 10)) * 2.0
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    labels = rng.integers(0, 10, 64)
    assert abs(float(metrics.nll(probs, labels))
               - metrics.nll_ref(probs, labels)) < 1e-5
    assert abs(float(metrics.brier(probs, labels))
               - metrics.brier_ref(probs, labels)) < 1e-5
    assert abs(float(metrics.accuracy(probs, labels))
               - metrics.accuracy_ref(probs, labels)) < 1e-6
    for n_bins in (5, 15):
        assert abs(float(metrics.ece(probs, labels, n_bins))
                   - metrics.ece_ref(probs, labels, n_bins)) < 1e-5


def test_metrics_calibrated_model_has_low_ece():
    """A perfectly calibrated synthetic predictor scores ~0 ECE; a
    systematically overconfident one scores high."""
    rng = np.random.default_rng(1)
    n, conf = 4096, 0.7
    probs = np.full((n, 2), 0.0)
    probs[:, 0], probs[:, 1] = conf, 1 - conf
    labels = (rng.random(n) > conf).astype(np.int64)   # P(correct)=conf
    assert float(metrics.ece(probs, labels)) < 0.05
    labels_wrong = (rng.random(n) > 0.2).astype(np.int64)
    assert float(metrics.ece(probs, labels_wrong)) > 0.3


def test_uncertainty_heads_degenerate_cases():
    # identical particles -> zero epistemic uncertainty
    logits = jnp.broadcast_to(jnp.array([2.0, 0.0, -1.0]), (4, 5, 3))
    h = uncertainty.predictive_heads(logits, "classify")
    assert float(jnp.max(h["mutual_info"])) < 1e-6
    assert float(jnp.max(h["variance"])) < 1e-12
    # regress: mean/variance are the particle moments
    outs = jnp.stack([jnp.zeros((5, 2)), jnp.ones((5, 2))])
    h = uncertainty.predictive_heads(outs, "regress")
    assert float(jnp.max(jnp.abs(h["mean"] - 0.5))) == 0.0
    assert float(jnp.max(jnp.abs(h["variance"] - 0.25))) == 0.0


# ---------------------------------------------------------------------------
# store-aware checkpointing
# ---------------------------------------------------------------------------

def test_store_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import restore_store, save_store
    pd = _pd(3, seed=1)
    try:
        x = jax.random.normal(jax.random.PRNGKey(4), (4, 3))
        batch = {"x": x, "y": x @ jnp.ones((3, 4))}
        for p in pd.particles.values():
            p.step(batch).wait()           # materialize opt_state + grads
        pd.drain()
        path = save_store(str(tmp_path), 7, pd.store)
        assert os.path.basename(path) == "store_00000007.npz"
        step, store2 = restore_store(str(tmp_path))
        assert step == 7 and store2.pids == pd.store.pids
        for key in ("params", "opt_state"):
            a, b = pd.store.stacked(key), store2.stacked(key)
            assert jax.tree.structure(a) == jax.tree.structure(b)
            for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                assert np.array_equal(np.asarray(u), np.asarray(v))
        # a served model loads without replaying inference
        eng = PredictiveEngine(pd.module.forward, store=store2,
                               kind="regress")
        want = PredictiveEngine(pd.module.forward, store=pd.store,
                                kind="regress").predict(batch)
        got = eng.predict(batch)
        assert np.array_equal(np.asarray(got["mean"]),
                              np.asarray(want["mean"]))
    finally:
        pd.cleanup()


def test_store_checkpoint_explicit_missing_key_raises(tmp_path):
    from repro.checkpoint import save_store
    store = ParticleStore()
    store.register(0)
    store.write("params", 0, {"w": jnp.ones((2,))})
    with pytest.raises(KeyError):
        save_store(str(tmp_path), 0, store, keys=["params", "nope"])


# ---------------------------------------------------------------------------
# SWAG serve-time sampling (platform-gated kernel path)
# ---------------------------------------------------------------------------

def test_swag_sample_kernel_matches_reference():
    from repro.bdl.swag import swag_sample, swag_state_init
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (5, 3)),
              "b": jnp.ones((3,))}
    st = swag_state_init(params, max_rank=4)
    # fake some trajectory moments
    st["mean"] = params
    st["sq_mean"] = jax.tree.map(lambda p: p * p + 0.1, params)
    st["n"] = jnp.asarray(3.0)
    st["rank"] = jnp.asarray(3, jnp.int32)
    k = jax.random.PRNGKey(9)
    ref = swag_sample(st, k, use_kernel=False)
    ker = swag_sample(st, k, use_kernel=True)
    for u, v in zip(jax.tree.leaves(ref), jax.tree.leaves(ker)):
        assert float(jnp.abs(u - v).max()) < 1e-5


def test_multiswag_posterior_predictive_serves_samples():
    from repro.bdl import MultiSWAG
    from repro.bdl.swag import swag_sample_stacked
    mod = _linear_module()
    x = jax.random.normal(jax.random.PRNGKey(5), (8, 3))
    data = [{"x": x, "y": x @ jnp.ones((3, 4))}]
    with MultiSWAG(mod, num_devices=1, seed=0, backend="compiled") as ms:
        ms.bayes_infer(data, 3, optimizer=sgd(0.05), num_particles=2,
                       max_rank=3)
        rng = jax.random.PRNGKey(0)
        with ms.posterior_predictive(samples_per_particle=3, rng=rng,
                                     kind="regress",
                                     max_wait_ms=1.0) as svc:
            heads = svc.predict_batch({"x": x})
            sampled = swag_sample_stacked(ms.store.stacked("swag"), rng, 3,
                                          use_kernel=True)
            outs = np.stack([np.asarray(
                x @ sampled["w"][i] + sampled["b"][i]) for i in range(6)])
            assert np.abs(outs.mean(0)
                          - np.asarray(heads["mean"])).max() < 1e-5
        # S=0 falls back to serving the live particle params
        with ms.posterior_predictive(kind="regress",
                                     max_wait_ms=1.0) as svc:
            assert svc.engine.num_particles == 2


# ---------------------------------------------------------------------------
# the sharded serving path (acceptance criterion): subprocess, 4 devices
# ---------------------------------------------------------------------------

def test_sharded_serving_reads_store_without_unsharding():
    """PredictiveService over a 4-device mesh: fused BMA parity < 1e-5 and
    zero per-request host transfers of stacked state (store stats flat,
    params still sharded over all 4 devices after serving)."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "_sharded_serve_check.py")],
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
             "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "OK" in out.stdout
