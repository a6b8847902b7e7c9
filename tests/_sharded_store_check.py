"""Sharded-compiled acceptance check (run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``; see
tests/test_store.py and the CI sharded matrix job).

Asserts, for DeepEnsemble / MultiSWAG / SteinVGD under a 4-device mesh
placement:
  1. the fused path runs with the particle axis sharded across all 4
     devices (sharding inspection of the store's stacked state);
  2. the sharded compiled backend matches the NEL backend to < 1e-4;
  3. a multi-epoch fused run performs zero per-epoch host transfers of
     stacked state — one checkout before the loop, donated buffers inside
     it (the input buffer is consumed by XLA), one commit at the end.
"""
import os
import sys

# forced host devices: pin the CPU backend so a chip on the host is
# never claimed by this check
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp

from repro.bdl import DeepEnsemble, MultiSWAG, SteinVGD
from repro.core import ParticleModule, Placement
from repro.launch.mesh import make_bench_mesh
from repro.optim import sgd

N_DEV = 4
N_PARTICLES = 4


def tiny_module():
    def init(rng):
        return {"w": jax.random.normal(rng, (3, 2)) * 0.5,
                "b": jnp.zeros((2,))}

    def loss(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2), {}

    def fwd(p, batch):
        return batch[0] @ p["w"] + p["b"]

    return ParticleModule(init, loss, fwd)


def data():
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 3))
    return [(x, x @ jnp.ones((3, 2)))]


def check_particle_axis_sharded(store, key):
    st = store.stacked(key)
    for path, leaf in jax.tree_util.tree_flatten_with_path(st)[0]:
        if leaf.ndim == 0:
            continue
        spec = leaf.sharding.spec
        assert spec and spec[0] == "data", \
            f"{key}{path}: particle axis not sharded, spec={spec}"
        devs = {s.device.id for s in leaf.addressable_shards}
        assert len(devs) == N_DEV, \
            f"{key}{path}: {len(devs)} devices hold shards, want {N_DEV}"


def main():
    assert len(jax.devices()) == N_DEV, \
        f"need {N_DEV} forced host devices, got {len(jax.devices())}"
    mesh = make_bench_mesh(N_DEV)
    placement = Placement(mesh=mesh, particle_axis="data", mode="tp")

    batches = data()
    for algo, kw in [
        (DeepEnsemble, dict(optimizer=sgd(0.05), num_particles=N_PARTICLES)),
        (MultiSWAG, dict(optimizer=sgd(0.05), num_particles=N_PARTICLES,
                         max_rank=4)),
        (SteinVGD, dict(num_particles=N_PARTICLES, lr=0.05, lengthscale=1.0)),
    ]:
        preds, params = {}, {}
        for backend, pl_ in (("nel", None), ("compiled", placement)):
            with algo(tiny_module(), num_devices=1, seed=0, backend=backend,
                      placement=pl_) as a:
                pids, losses = a.bayes_infer(batches, 3, **kw)
                preds[backend] = a.posterior_pred(batches[0])
                params[backend] = [a.push_dist.p_params(p)["w"] for p in pids]
                if backend == "compiled":
                    check_particle_axis_sharded(a.store, "params")
                    before = a.store.snapshot_stats()
                    extra = (dict(optimizer=kw["optimizer"])
                             if "optimizer" in kw else
                             dict(lr=kw["lr"], lengthscale=kw["lengthscale"]))
                    a._fused_epochs(pids, batches, 5, **extra)
                    after = a.store.snapshot_stats()
                    assert after["unstacks"] == before["unstacks"], \
                        "fused epochs unstacked state mid-run"
                    assert after["stacks"] == before["stacks"], \
                        "fused epochs restacked state mid-run"
                    assert after["device_puts"] == before["device_puts"], \
                        "fused epochs re-placed state mid-run"
                    ncommit = after["commits"] - before["commits"]
                    nco = after["checkouts"] - before["checkouts"]
                    assert 1 <= ncommit <= 3 and ncommit == nco, (before, after)
                    # donation: a checked-out buffer is consumed by the
                    # step program, fetched from the shared ProgramCache
                    # through the compile_* delegators with the store's
                    # generation token — a true cache HIT of the exact
                    # program _fused_epochs lowered (asserted below)
                    from repro.bdl.svgd import compile_svgd_step
                    from repro.core import functional
                    from repro.runtime import global_cache
                    hits0 = global_cache().snapshot_stats()["hits"]
                    tok = a.store.generation()
                    mask = a.store.active_mask()
                    st = a.store.checkout("params", pids)
                    if "optimizer" in kw:
                        ost = a.store.checkout("opt_state", pids)
                        step = functional.compile_ensemble_step(
                            a.module.loss, kw["optimizer"], placement,
                            st, ost, batches[0], mask, state_token=tok)
                        np_, no_, _ = step(st, ost, batches[0], mask)
                        assert st["w"].is_deleted(), "params not donated"
                        a.store.commit("opt_state", no_, pids)
                    else:
                        step = compile_svgd_step(
                            a.module.loss, placement, st, batches[0], mask,
                            lr=kw["lr"], lengthscale=kw["lengthscale"],
                            state_token=tok)
                        np_, _ = step(st, batches[0], mask)
                        assert st["w"].is_deleted(), "params not donated"
                    a.store.commit("params", np_, pids)
                    assert global_cache().snapshot_stats()["hits"] \
                        == hits0 + 1, "compile_* did not share the " \
                        "Runtime's cached program"
        err = float(jnp.abs(preds["nel"] - preds["compiled"]).max())
        assert err < 1e-4, f"{algo.__name__}: pred mismatch {err}"
        for pn, pc in zip(params["nel"], params["compiled"]):
            perr = float(jnp.abs(pn - pc).max())
            assert perr < 1e-4, f"{algo.__name__}: param mismatch {perr}"
        print(f"{algo.__name__}: parity {err:.2e}, particle axis sharded "
              f"over {N_DEV} devices, zero mid-run host transfers, "
              "donation verified")
    print("OK")


if __name__ == "__main__":
    sys.exit(main())
