"""Sharded serving acceptance check (run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``; see
tests/test_serve.py and the CI sharded matrix job).

Asserts, for a PredictiveService over a 4-device mesh placement:
  1. fused BMA predict matches a sequential per-particle forward +
     host-side average to < 1e-5;
  2. serving reads the store WITHOUT unsharding it: across many
     requests, zero restacks / unstacks / device_puts / checkouts of
     stacked state (store.stats deltas are all zero) and the stacked
     params stay sharded over all 4 devices;
  3. the served heads are replicated outputs (safe to hand to any host
     thread) and finite;
  4. the runtime's process-wide ProgramCache dedupes across subsystems
     under the mesh too: repeated identical requests and a SECOND
     service over the same store trigger zero cold compiles while
     ``store.version()`` is unchanged;
  5. the precision ladder survives the mesh: a "mixed" store serves its
     bf16 copy with zero stacked-state traffic per request, zero cold
     compiles under clone/kill churn, and fp32 masters stay sharded.

When ``REPRO_TRACE_OUT`` is set, the whole run executes with obs tracing
enabled and dumps a Perfetto-loadable Chrome trace-event JSON to that
path on success (CI uploads it as an artifact from both sharded jobs).
"""
import json
import os
import sys

# forced host devices: pin the CPU backend so a chip on the host is
# never claimed by this check
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp
import numpy as np

from repro.bdl import DeepEnsemble
from repro.core import ParticleModule, Placement
from repro.launch.mesh import make_bench_mesh
from repro.optim import sgd

N_DEV = 4
N_PARTICLES = 4
FLAT_KEYS = ("stacks", "unstacks", "device_puts", "checkouts", "commits",
             "row_flushes")


def tiny_module():
    def init(rng):
        return {"w": jax.random.normal(rng, (3, 5)) * 0.5,
                "b": jnp.zeros((5,))}

    def loss(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2), {}

    def fwd(p, batch):
        return batch["x"] @ p["w"] + p["b"]

    return ParticleModule(init, loss, fwd)


def check_sharded(store, key):
    st = store.stacked(key)
    for path, leaf in jax.tree_util.tree_flatten_with_path(st)[0]:
        spec = leaf.sharding.spec
        assert spec and spec[0] == "data", \
            f"{key}{path}: particle axis not sharded, spec={spec}"
        devs = {s.device.id for s in leaf.addressable_shards}
        assert len(devs) == N_DEV, \
            f"{key}{path}: {len(devs)} devices hold shards, want {N_DEV}"


def main():
    assert len(jax.devices()) == N_DEV, \
        f"need {N_DEV} forced host devices, got {len(jax.devices())}"
    trace_out = os.environ.get("REPRO_TRACE_OUT")
    if trace_out:
        from repro.obs import trace
        trace.enable()
    placement = Placement(mesh=make_bench_mesh(N_DEV), particle_axis="data",
                          mode="tp")
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 3))
    batches = [((x, x @ jnp.ones((3, 5))))]
    train = [((b[0], b[1])) for b in batches]

    with DeepEnsemble(tiny_module(), num_devices=1, seed=0,
                      backend="compiled", placement=placement) as de:
        de.bayes_infer(train, 3, optimizer=sgd(0.05),
                       num_particles=N_PARTICLES)
        check_sharded(de.store, "params")

        # host-side reference BMA (reads views: do this BEFORE the
        # serving-era stats snapshot — view reads legitimately unstack)
        pids = de.push_dist.particle_ids()
        probe = {"x": x}
        member = [np.asarray(batches[0][0] @ de.push_dist.p_params(p)["w"]
                             + de.push_dist.p_params(p)["b"])
                  for p in pids]
        ref_mean = np.mean(np.stack(member), 0)

        with de.posterior_predictive(kind="regress", max_batch=8,
                                     max_wait_ms=1.0) as svc:
            heads = svc.predict_batch(probe)        # warmup + compile
            err = float(np.abs(np.asarray(heads["mean"]) - ref_mean).max())
            assert err < 1e-5, f"fused BMA vs per-particle loop: {err}"

            before = de.store.snapshot_stats()
            for i in range(8):
                pred = svc.predict({"x": np.asarray(x[i % 16])})
                assert np.isfinite(float(pred.entropy))
                assert np.all(np.isfinite(np.asarray(pred.mean)))
            svc.predict_batch(probe)
            after = de.store.snapshot_stats()
            delta = {k: after[k] - before[k] for k in FLAT_KEYS}
            assert all(v == 0 for v in delta.values()), \
                f"serving touched stacked state: {delta}"

            # the store is still sharded over all devices after serving
            check_sharded(de.store, "params")

            # heads come back replicated: any host thread may consume them
            spec = heads["mean"].sharding.spec if hasattr(
                heads["mean"], "sharding") else None
            assert not spec or all(s is None for s in spec), \
                f"heads not replicated: {spec}"

            st = svc.stats()
            assert st["requests"] == 8 and st["batches"] >= 1

            # runtime-layer hook: identical repeat requests are pure
            # cache hits — no cold compiles while version is unchanged
            from repro.runtime import global_cache
            v = de.store.version("params")
            cold0 = global_cache().snapshot_stats()["cold_compiles"]
            svc.predict_batch(probe)
            assert de.store.version("params") == v
            assert global_cache().snapshot_stats()["cold_compiles"] == cold0, \
                "repeat request cold-compiled under the mesh"

        # a fresh service over the same store compiles NOTHING: the
        # ProgramCache is process-wide and keyed on (spec, placement,
        # store generation, bucketed shapes) — not the engine instance
        from repro.runtime import global_cache
        cold0 = global_cache().snapshot_stats()["cold_compiles"]
        with de.posterior_predictive(kind="regress", max_batch=8,
                                     max_wait_ms=1.0) as svc2:
            heads2 = svc2.predict_batch(probe)
            err2 = float(np.abs(np.asarray(heads2["mean"]) - ref_mean).max())
            assert err2 < 1e-5, f"second service BMA: {err2}"
        assert global_cache().snapshot_stats()["cold_compiles"] == cold0, \
            "second service over the same store recompiled"

        # stateful serving under the mesh: per-particle serving state is
        # born sharded over the particle axis and stays there across steps
        from repro.serve import PredictiveEngine

        def step_fwd(p, state, batch):
            out = batch["x"] @ p["w"] + p["b"] + state["acc"]
            return out, {"acc": state["acc"] + 1.0}

        eng = PredictiveEngine(step_fwd, store=de.store, kind="regress",
                               stateful=True)
        state = eng.init_state(lambda p: {"acc": jnp.zeros(())})
        for step in range(2):
            heads, state = eng.step(state, probe)
            want = ref_mean + step
            serr = float(np.abs(np.asarray(heads["mean"]) - want).max())
            assert serr < 1e-5, f"stateful BMA step {step}: {serr}"
        spec = state["acc"].sharding.spec
        assert spec and spec[0] == "data", \
            f"serving state not particle-sharded: {spec}"

        # lifecycle churn under the mesh: clone+kill between requests
        # must cold-compile NOTHING (capacity, shapes and generation are
        # churn-invariant) while the served BMA tracks the live set and
        # params stay sharded over all 4 devices
        pd = de.push_dist
        eng2 = PredictiveEngine(pd.module.forward, store=de.store,
                                kind="regress")
        eng2.predict(probe)                       # shared-cache warm hit
        cold0 = global_cache().snapshot_stats()["cold_compiles"]
        gen0 = de.store.generation()
        puts0 = de.store.snapshot_stats()["device_puts"]
        for _ in range(3):
            victim = pd.particle_ids()[0]
            pd.p_kill(victim)
            pd.p_clone(pd.particle_ids()[0], jitter=0.01)
            heads2 = eng2.predict(probe)
            live = pd.particle_ids()
            ref2 = np.mean([np.asarray(x @ pd.p_params(p)["w"]
                                       + pd.p_params(p)["b"])
                            for p in live], 0)
            cerr = float(np.abs(np.asarray(heads2["mean"]) - ref2).max())
            assert cerr < 1e-5, f"churned BMA vs live reference: {cerr}"
        assert de.store.generation() == gen0, "churn bumped the generation"
        assert global_cache().snapshot_stats()["cold_compiles"] == cold0, \
            "clone/kill churn cold-compiled under the mesh"
        assert de.store.snapshot_stats()["device_puts"] == puts0, \
            "churn re-placed the stacked state"
        assert de.store.capacity == N_PARTICLES
        check_sharded(de.store, "params")
        lc = pd.stats()["lifecycle"]
        assert lc["clones"] == 3 and lc["kills"] == 3 and lc["live"] == 4

    # --- precision phase: a "mixed" store under the mesh. The bf16
    # serve copy is a version-memoized transformed view of the sharded
    # masters: serving still reads NO stacked store state per request,
    # clone/kill churn cold-compiles nothing (the serve-cast program
    # keys on padded shapes + the precision token, both churn-invariant),
    # and a second engine over the same store shares every program.
    from repro.core import PushDistribution
    from repro.runtime import global_cache

    with PushDistribution(tiny_module(), num_devices=1, seed=0,
                          backend="compiled", capacity=N_PARTICLES,
                          placement=placement, precision="mixed") as pdm:
        for _ in range(N_PARTICLES):
            pdm.p_create(sgd(0.05))
        probe = {"x": x}
        engb = PredictiveEngine(pdm.module.forward, store=pdm.store,
                                kind="regress")
        assert engb.precision.casts_serve
        engb.predict(probe)                        # warm (serve cast + BMA)
        # steady state: repeat requests reuse the memoized serve copy —
        # no stacked-state traffic at all (churn below legitimately
        # commits cloned rows, so the zero-delta window ends here)
        before = pdm.store.snapshot_stats()
        for _ in range(3):
            engb.predict(probe)
        after = pdm.store.snapshot_stats()
        bdelta = {k: after[k] - before[k] for k in FLAT_KEYS}
        assert all(v == 0 for v in bdelta.values()), \
            f"bf16 serving touched stacked state: {bdelta}"
        cold0 = global_cache().snapshot_stats()["cold_compiles"]
        for _ in range(3):
            victim = pdm.particle_ids()[0]
            pdm.p_kill(victim)
            pdm.p_clone(pdm.particle_ids()[0], jitter=0.01)
            headsb = engb.predict(probe)
            live = pdm.particle_ids()
            refb = np.mean([np.asarray(x @ pdm.p_params(p)["w"]
                                       + pdm.p_params(p)["b"])
                            for p in live], 0)
            berr = float(np.abs(np.asarray(headsb["mean"]) - refb).max())
            assert berr < 0.05, f"bf16 BMA vs fp32 masters: {berr}"
        assert global_cache().snapshot_stats()["cold_compiles"] == cold0, \
            "bf16 churn cold-compiled under the mesh"
        engb2 = PredictiveEngine(pdm.module.forward, store=pdm.store,
                                 kind="regress")
        engb2.predict(probe)
        assert global_cache().snapshot_stats()["cold_compiles"] == cold0, \
            "second bf16 engine over the same store recompiled"
        check_sharded(pdm.store, "params")         # masters stay sharded

    # --- decode phase: continuous-batching paged decode under the mesh.
    # The KV page pool is store state like params: born sharded over the
    # particle axis, still sharded after serving, and steady-state decode
    # steps (admission + retirement churn included) cold-compile NOTHING.
    from repro import configs
    from repro.core import PushDistribution
    from repro.models import api
    from repro.runtime import global_cache
    from repro.serve import serve_decode

    cfg = configs.get("qwen1.5-0.5b").replace(
        n_units=2, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        d_ff=64, vocab_size=128, max_seq_len=64)
    lm = ParticleModule(
        init=lambda rng: api.init_params(rng, cfg),
        loss=lambda p, b: api.loss_fn(p, b, cfg),
        forward=lambda p, b: api.forward(p, b, cfg)[0], cfg=cfg)
    with PushDistribution(lm, num_devices=1, seed=0,
                          placement=placement) as pd:
        for _ in range(N_PARTICLES):
            pd.p_create()
        svc = serve_decode(pd, cfg, num_pages=16, page_size=8,
                           max_active=2, decode_kernel=False,
                           warmup_buckets=(8,))
        try:
            check_sharded(pd.store, "kv_pages")
            cold0 = global_cache().snapshot_stats()["cold_compiles"]
            handles = [svc.generate_async([3 + i, 5, 7, 11, 13], max_new=4)
                       for i in range(3)]
            gens = [h.result(300) for h in handles]
            assert all(len(g.tokens) == 4 for g in gens)
            assert global_cache().snapshot_stats()["cold_compiles"] == cold0, \
                "steady-state decode cold-compiled under the mesh"
            check_sharded(pd.store, "kv_pages")    # pages still sharded
            check_sharded(pd.store, "params")
            dec = pd.stats()["decode"]
            assert dec["retired"] == 3, dec
            assert dec["pool"]["used_pages"] == 0, dec
        finally:
            svc.close()

    if trace_out:
        from repro.obs import export
        export.dump_chrome_trace(trace_out)
        with open(trace_out) as f:
            doc = json.load(f)
        evs = doc["traceEvents"]
        assert evs, "trace dump produced no events"
        assert any(e["ph"] == "X" for e in evs), "no duration spans in trace"
        cats = {e.get("cat") for e in evs if e["ph"] in ("X", "i")}
        assert {"runtime", "serve", "decode"} <= cats, \
            f"trace missing expected categories: {cats}"
        print(f"perfetto trace: {len(evs)} events -> {trace_out}")

    print(f"parity {err:.2e}, stacked state untouched across requests "
          f"({N_DEV} devices), heads replicated, stateful state sharded, "
          "churn cold-compiled nothing (fp32 AND bf16 serve copies), "
          "decode pages stayed sharded")
    print("OK")


if __name__ == "__main__":
    sys.exit(main())
