"""2D-placement acceptance check (run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``; see
tests/test_placement2d.py and the CI ``sharded-2x2`` matrix job).

Asserts, over a ``particle=2 x model=2`` mesh (the tentpole of ISSUE 7):
  1. fused DeepEnsemble / SteinVGD training matches the single-device
     compiled path to < 1e-4, with the particle axis on ``data`` AND the
     tensor-parallel trailing dims (``mlp/wi/w`` etc.) on ``model``;
  2. multi-epoch fused runs perform zero mid-run host transfers of
     stacked state (store stats deltas are zero inside the loop);
  3. serving matches single-device BMA, reads the store without
     unsharding it, and a SECOND service over the same store
     cold-compiles nothing;
  4. continuous-batching paged decode produces the same tokens as the
     single-device path, with ``kv_pages`` heads sharded over ``model``
     and zero steady-state cold compiles;
  5. a model-only ``1 x 4`` placement of a llama3-8b stand-in (same
     rule coverage: GQA attention + swiglu MLP + tied vocab ends) drops
     per-device parameter bytes ~4x vs replicated, reported through
     ``pd.stats()["placement"]``.
"""
import os
import sys

# forced host devices: pin the CPU backend so a chip on the host is
# never claimed by this check
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp
import numpy as np

from repro.bdl import DeepEnsemble, SteinVGD
from repro.core import ParticleModule, Placement, PushDistribution
from repro.launch.mesh import make_bench_mesh
from repro.optim import sgd

N_DEV = 4
N_PARTICLES = 4
FLAT_KEYS = ("stacks", "unstacks", "device_puts", "checkouts", "commits",
             "row_flushes")


def tiny_module():
    """Rule-matching paths (mlp/wi/w, mlp/wo/w) so the model axis
    actually engages — the store-check's flat {"w","b"} params match no
    tensor-parallel rule and would leave the model axis idle."""
    def init(rng):
        k1, k2 = jax.random.split(rng)
        return {"mlp": {"wi": {"w": jax.random.normal(k1, (3, 16)) * 0.5},
                        "wo": {"w": jax.random.normal(k2, (16, 2)) * 0.5}}}

    def apply(p, x):
        return jax.nn.gelu(x @ p["mlp"]["wi"]["w"]) @ p["mlp"]["wo"]["w"]

    def loss(p, batch):
        x, y = batch
        return jnp.mean((apply(p, x) - y) ** 2), {}

    def fwd(p, batch):
        x = batch["x"] if isinstance(batch, dict) else batch[0]
        return apply(p, x)

    return ParticleModule(init, loss, fwd)


def data():
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 3))
    return [(x, x @ jnp.ones((3, 2)))]


def check_2d_sharded(store, key, model_dims):
    """Every leaf: particle axis on `data`; the leaves named in
    ``model_dims`` ({path-substring: dim}) carry `model` at that dim."""
    st = store.stacked(key)
    seen = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(st)[0]:
        if leaf.ndim == 0:
            continue
        spec = leaf.sharding.spec
        assert spec and spec[0] == "data", \
            f"{key}{path}: particle axis not sharded, spec={spec}"
        from repro.sharding.rules import normalize_path
        pstr = normalize_path(path)
        for frag, dim in model_dims.items():
            if frag in pstr:
                seen.add(frag)
                got = spec[dim] if dim < len(spec) else None
                assert got == "model", \
                    f"{key}{pstr}: want model at dim {dim}, spec={spec}"
        devs = {s.device.id for s in leaf.addressable_shards}
        assert len(devs) == N_DEV, \
            f"{key}{pstr}: {len(devs)} devices hold shards, want {N_DEV}"
    assert seen == set(model_dims), \
        f"{key}: model-sharded leaves missing: {set(model_dims) - seen}"


def train_parity(placement):
    """Fused train on the 2x2 placement vs single-device compiled."""
    batches = data()
    for algo, kw in [
        (DeepEnsemble, dict(optimizer=sgd(0.05), num_particles=N_PARTICLES)),
        (SteinVGD, dict(num_particles=N_PARTICLES, lr=0.05, lengthscale=1.0)),
    ]:
        preds, params = {}, {}
        for tag, pl_ in (("single", None), ("2d", placement)):
            with algo(tiny_module(), num_devices=1, seed=0,
                      backend="compiled", placement=pl_) as a:
                pids, _ = a.bayes_infer(batches, 3, **kw)
                if tag == "2d":
                    check_2d_sharded(a.store, "params",
                                     {"mlp/wi/w": 2, "mlp/wo/w": 1})
                    before = a.store.snapshot_stats()
                    extra = (dict(optimizer=kw["optimizer"])
                             if "optimizer" in kw else
                             dict(lr=kw["lr"], lengthscale=kw["lengthscale"]))
                    a._fused_epochs(pids, batches, 5, **extra)
                    after = a.store.snapshot_stats()
                    for k in ("unstacks", "stacks", "device_puts"):
                        assert after[k] == before[k], \
                            f"fused epochs did host transfers: {k}"
                    # drive parity through the same total step count
                    with algo(tiny_module(), num_devices=1, seed=0,
                              backend="compiled") as ref:
                        rpids, _ = ref.bayes_infer(batches, 3, **kw)
                        ref._fused_epochs(rpids, batches, 5, **extra)
                        preds["single"] = ref.posterior_pred(batches[0])
                        params["single"] = [
                            ref.push_dist.p_params(p)["mlp"]["wi"]["w"]
                            for p in rpids]
                    preds["2d"] = a.posterior_pred(batches[0])
                    params["2d"] = [
                        a.push_dist.p_params(p)["mlp"]["wi"]["w"]
                        for p in pids]
        err = float(jnp.abs(preds["single"] - preds["2d"]).max())
        assert err < 1e-4, f"{algo.__name__}: pred mismatch {err}"
        for ps, p2 in zip(params["single"], params["2d"]):
            perr = float(jnp.abs(ps - p2).max())
            assert perr < 1e-4, f"{algo.__name__}: param mismatch {perr}"
        print(f"{algo.__name__}: 2x2 vs single-device parity {err:.2e}, "
              "model axis engaged, zero mid-run host transfers")


def serve_parity(placement):
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 3))
    train = data()
    probe = {"x": x}
    with DeepEnsemble(tiny_module(), num_devices=1, seed=0,
                      backend="compiled", placement=placement) as de:
        de.bayes_infer(train, 3, optimizer=sgd(0.05),
                       num_particles=N_PARTICLES)
        pids = de.push_dist.particle_ids()
        member = []
        for p in pids:
            pp = de.push_dist.p_params(p)
            member.append(np.asarray(
                jax.nn.gelu(x @ pp["mlp"]["wi"]["w"]) @ pp["mlp"]["wo"]["w"]))
        ref_mean = np.mean(np.stack(member), 0)

        from repro.runtime import global_cache
        with de.posterior_predictive(kind="regress", max_batch=8,
                                     max_wait_ms=1.0) as svc:
            heads = svc.predict_batch(probe)
            err = float(np.abs(np.asarray(heads["mean"]) - ref_mean).max())
            assert err < 1e-4, f"2x2 BMA vs per-particle reference: {err}"
            before = de.store.snapshot_stats()
            for i in range(4):
                svc.predict({"x": np.asarray(x[i % 16])})
            svc.predict_batch(probe)
            after = de.store.snapshot_stats()
            delta = {k: after[k] - before[k] for k in FLAT_KEYS}
            assert all(v == 0 for v in delta.values()), \
                f"serving touched stacked state: {delta}"
            check_2d_sharded(de.store, "params",
                             {"mlp/wi/w": 2, "mlp/wo/w": 1})
        cold0 = global_cache().snapshot_stats()["cold_compiles"]
        with de.posterior_predictive(kind="regress", max_batch=8,
                                     max_wait_ms=1.0) as svc2:
            heads2 = svc2.predict_batch(probe)
            err2 = float(np.abs(np.asarray(heads2["mean"]) - ref_mean).max())
            assert err2 < 1e-4, f"second service BMA: {err2}"
        assert global_cache().snapshot_stats()["cold_compiles"] == cold0, \
            "second service over the same store cold-compiled under 2x2"

        # the stats surface reports the plan every layer derived from
        pstats = de.push_dist.stats()["placement"]
        assert pstats["mesh_shape"] == {"data": 2, "model": 2}, pstats
        assert pstats["model_axis_size"] == 2 and pstats["mode"] == "tp"
        assert pstats["per_device_param_bytes"] > 0
        print(f"serve: 2x2 BMA parity {err:.2e}, store untouched, "
              "second service cold==0, placement stats reported")


def decode_parity(placement):
    """Paged decode over 2x2 must produce the single-device tokens, with
    kv page heads on the model axis and zero steady-state compiles."""
    from repro import configs
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
    prompts = [[3 + i, 5, 7, 11, 13] for i in range(3)]
    tokens = {}
    for tag, pl_ in (("single", None), ("2d", placement)):
        with PushDistribution(lm, num_devices=1, seed=0,
                              placement=pl_) as pd:
            for _ in range(N_PARTICLES):
                pd.p_create()
            svc = serve_decode(pd, cfg, num_pages=16, page_size=8,
                               max_active=2, decode_kernel=False,
                               warmup_buckets=(8,))
            try:
                cold0 = global_cache().snapshot_stats()["cold_compiles"]
                handles = [svc.generate_async(p, max_new=4) for p in prompts]
                tokens[tag] = [h.result(300).tokens for h in handles]
                if tag == "2d":
                    assert global_cache().snapshot_stats()["cold_compiles"] \
                        == cold0, "steady-state decode cold-compiled"
                    # kv page leaves: (cap, n_units, pages, page, KVH, hd)
                    # with KVH on the model axis (the /k, /v rule)
                    st = pd.store.stacked("kv_pages")
                    flat = jax.tree_util.tree_flatten_with_path(st)[0]
                    kv = [(jax.tree_util.keystr(pa), leaf)
                          for pa, leaf in flat
                          if jax.tree_util.keystr(pa).endswith(("'k']",
                                                                "'v']"))]
                    assert kv, "no k/v leaves in kv_pages"
                    for pstr, leaf in kv:
                        spec = leaf.sharding.spec
                        assert spec[0] == "data" and "model" in spec, \
                            f"kv_pages{pstr}: spec={spec}"
                        assert spec[leaf.ndim - 2] == "model", \
                            f"kv_pages{pstr}: heads not on model: {spec}"
                    dec = pd.stats()["decode"]
                    assert dec["retired"] == 3, dec
            finally:
                svc.close()
    assert tokens["2d"] == tokens["single"], \
        f"decode tokens diverged: {tokens}"
    print(f"decode: 2x2 tokens == single-device {tokens['2d']}, "
          "kv heads on model axis, steady state cold==0")


def model_only_footprint():
    """1 x model=4 placement of a llama3-8b stand-in: per-device param
    bytes drop ~4x vs replicated (the ensemble-of-models-that-don't-fit
    headline), visible through pd.stats()['placement']."""
    from repro import configs
    from repro.models import api

    cfg = configs.get("llama3-8b").replace(
        n_units=2, d_model=64, n_heads=8, n_kv_heads=4, head_dim=8,
        d_ff=128, vocab_size=256, max_seq_len=64)
    lm = ParticleModule(
        init=lambda rng: api.init_params(rng, cfg),
        loss=lambda p, b: api.loss_fn(p, b, cfg),
        forward=lambda p, b: api.forward(p, b, cfg)[0], cfg=cfg)
    byts = {}
    for tag, model in (("replicated", 1), ("model4", 4)):
        pl = Placement(mesh=make_bench_mesh(N_DEV, model=model))
        with PushDistribution(lm, num_devices=1, seed=0, placement=pl) as pd:
            pd.p_create()
            pd.store.stacked("params")          # place on the mesh
            st = pd.stats()["placement"]
            assert st["mesh_shape"] == {"data": N_DEV // model,
                                        "model": model}, st
            byts[tag] = st["per_device_param_bytes"]
    ratio = byts["replicated"] / max(byts["model4"], 1)
    assert ratio > 3.0, f"model-only placement footprint ratio {ratio:.2f} " \
        f"(replicated {byts['replicated']}, model4 {byts['model4']})"
    print(f"llama3-8b stand-in: per-device param bytes {byts['replicated']}"
          f" -> {byts['model4']} ({ratio:.2f}x drop on model=4)")


def main():
    assert len(jax.devices()) == N_DEV, \
        f"need {N_DEV} forced host devices, got {len(jax.devices())}"
    placement = Placement(mesh=make_bench_mesh(N_DEV, model=2),
                          particle_axis="data", mode="tp")
    assert placement.model_axis_size() == 2
    assert placement.particle_axis_size() == 2
    train_parity(placement)
    serve_parity(placement)
    decode_parity(placement)
    model_only_footprint()
    print("OK")


if __name__ == "__main__":
    sys.exit(main())
