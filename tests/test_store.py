"""ParticleStore semantics: round-trips, laziness, dirty tracking, the
store as single source of truth for both backends, and the sharded
compiled path (subprocess with 4 forced host devices).

Property tests (hypothesis) assert the exact-inverse laws the refactor
relies on: stack_pytrees/unstack_pytree and store view/write-back."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ParticleModule, ParticleStore, Placement,
                        PushDistribution, functional)
from repro.optim import sgd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree(seed, shapes):
    rng = np.random.default_rng(seed)
    return {f"p{i}": jnp.asarray(rng.standard_normal(s, dtype=np.float32))
            for i, s in enumerate(shapes)}


def _eq(a, b) -> bool:
    return all(bool(jnp.array_equal(x, y)) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# unit tests (always run, no hypothesis needed)
# ---------------------------------------------------------------------------

def test_store_view_writeback_roundtrip():
    store = ParticleStore()
    trees = [_tree(i, [(3, 2), (4,)]) for i in range(3)]
    for pid, t in enumerate(trees):
        store.register(pid)
        store.write("params", pid, t)
    # canonical form is capacity-padded (3 live -> power-of-two 4); the
    # padding row is gated off by the active mask
    assert store.capacity == 4
    st = store.stacked("params")
    assert jax.tree.leaves(st)[0].shape == (4, 3, 2)
    assert np.allclose(np.asarray(store.active_mask()), [1, 1, 1, 0])
    for pid, t in enumerate(trees):
        assert _eq(store.read("params", pid), t)

    # write-back through a view is visible in the next stacked flush
    new_row = _tree(99, [(3, 2), (4,)])
    store.write("params", 1, new_row)
    st2 = store.stacked("params")
    assert _eq(jax.tree.map(lambda x: x[1], st2), new_row)
    assert _eq(jax.tree.map(lambda x: x[0], st2), trees[0])


def test_store_commit_invalidates_views_lazily():
    store = ParticleStore()
    for pid in range(2):
        store.register(pid)
        store.write("params", pid, _tree(pid, [(2, 2)]))
    store.stacked("params")
    _ = store.read("params", 0)              # populate the view cache
    fresh = functional.stack_pytrees([_tree(7, [(2, 2)]),
                                      _tree(8, [(2, 2)])])
    u0 = store.snapshot_stats()["unstacks"]
    store.commit("params", fresh)
    assert store.snapshot_stats()["unstacks"] == u0   # commit is lazy
    assert _eq(store.read("params", 0), _tree(7, [(2, 2)]))
    assert store.snapshot_stats()["unstacks"] == u0 + 1  # unstack-on-read


def test_store_checkout_transfers_ownership():
    store = ParticleStore()
    store.register(0)
    store.write("params", 0, _tree(0, [(2,)]))
    st = store.checkout("params")
    with pytest.raises(KeyError):
        store.read("params", 0)
    store.commit("params", st)
    assert _eq(store.read("params", 0), _tree(0, [(2,)]))


def test_store_grows_with_new_particles():
    store = ParticleStore()
    for pid in range(2):
        store.register(pid)
        store.write("params", pid, _tree(pid, [(2,)]))
    assert jax.tree.leaves(store.stacked("params"))[0].shape[0] == 2
    gen = store.generation()
    store.register(2)               # capacity 2 -> 4: a shape change
    store.write("params", 2, _tree(2, [(2,)]))
    st = store.stacked("params")
    assert store.generation() > gen
    assert jax.tree.leaves(st)[0].shape[0] == 4
    assert _eq(jax.tree.map(lambda x: x[2], st), _tree(2, [(2,)]))


def test_store_subset_roundtrip():
    """An ordered subset (any order) stacks/checks out/commits without
    disturbing the other particles — what a second bayes_infer on the same
    PD relies on."""
    store = ParticleStore()
    trees = {}
    for pid in range(4):
        store.register(pid)
        trees[pid] = _tree(pid, [(2, 3)])
        store.write("params", pid, trees[pid])
    store.stacked("params")                        # canonical full stack
    sub = store.stacked("params", [3, 1])          # reordered subset read
    assert _eq(jax.tree.map(lambda x: x[0], sub), trees[3])
    st = store.checkout("params", [2, 3])
    new = jax.tree.map(lambda x: x + 1.0, st)
    store.commit("params", new, [2, 3])
    assert _eq(store.read("params", 0), trees[0])  # untouched rows survive
    assert _eq(store.read("params", 2),
               jax.tree.map(lambda x: x + 1.0, trees[2]))
    full = store.stacked("params")
    assert _eq(jax.tree.map(lambda x: x[3], full),
               jax.tree.map(lambda x: x + 1.0, trees[3]))


def test_store_rejects_bad_pids_and_counts():
    store = ParticleStore()
    for pid in (0, 1):
        store.register(pid)
        store.write("params", pid, _tree(pid, [(2,)]))
    with pytest.raises(KeyError):
        store.stacked("params", [0, 7])            # unregistered pid
    with pytest.raises(ValueError):
        store.commit("params", functional.stack_pytrees(
            [_tree(0, [(2,)])]))  # wrong particle count


def _mod_and_data():
    def init(rng):
        return {"w": jax.random.normal(rng, (3, 2))}

    def loss(p, b):
        return jnp.mean((b[0] @ p["w"] - b[1]) ** 2), {}

    mod = ParticleModule(init, loss, lambda p, b: b[0] @ p["w"])
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 3))
    return mod, [(x, x @ jnp.ones((3, 2)))]


def test_repeated_bayes_infer_compiled_backend():
    """A second bayes_infer creates new particles -> the fused path must
    operate on that subset of the store (regression: full-set-only store
    ops made this raise)."""
    from repro.bdl import DeepEnsemble
    mod, data = _mod_and_data()
    preds = {}
    for backend in ("nel", "compiled"):
        with DeepEnsemble(mod, num_devices=1, seed=0, backend=backend) as de:
            de.bayes_infer(data, 2, optimizer=sgd(0.05), num_particles=2)
            pids2, _ = de.bayes_infer(data, 2, optimizer=sgd(0.05),
                                      num_particles=2)
            assert len(de.push_dist.particle_ids()) == 4
            preds[backend] = de.posterior_pred(data[0])
            assert all(bool(jnp.all(jnp.isfinite(
                de.push_dist.p_params(p)["w"]))) for p in pids2)
    assert float(jnp.abs(preds["nel"] - preds["compiled"]).max()) < 1e-4


def test_backend_parity_with_real_dataloader():
    """Fused-path compilation must not consume dataloader iterations:
    NEL and compiled must see identical epoch streams from a stateful
    DataLoader (regression: an eager first-batch peek shifted the rng
    epoch of the fused run)."""
    from repro import configs
    from repro.bdl import DeepEnsemble
    from repro.data.loader import DataLoader
    from repro.models import api

    cfg = configs.get("vit-mnist").smoke().replace(n_units=1, d_model=32,
                                                   n_heads=2, n_kv_heads=2,
                                                   head_dim=16, d_ff=64)
    mod = ParticleModule(init=lambda rng: api.init_params(rng, cfg),
                         loss=lambda p, b: api.loss_fn(p, b, cfg),
                         forward=lambda p, b: api.forward(p, b, cfg)[0],
                         cfg=cfg)
    preds = {}
    for backend in ("nel", "compiled"):
        dl = DataLoader(cfg, batch_size=4, num_batches=2, seed=0)
        probe = DataLoader(cfg, batch_size=4, num_batches=1, seed=123)
        with DeepEnsemble(mod, num_devices=1, seed=0, backend=backend) as de:
            de.bayes_infer(dl, 2, optimizer=sgd(0.01), num_particles=2)
            preds[backend] = de.posterior_pred(next(iter(probe)))
    assert float(jnp.abs(preds["nel"] - preds["compiled"]).max()) < 1e-4


def test_particle_state_is_store_backed():
    """Particle.state is a view of the PD's store — one source of truth."""
    def init(rng):
        return {"w": jax.random.normal(rng, (3, 2))}

    def loss(p, b):
        return jnp.mean((b[0] @ p["w"] - b[1]) ** 2), {}

    mod = ParticleModule(init, loss, lambda p, b: b[0] @ p["w"])
    with PushDistribution(mod, num_devices=1) as pd:
        pids = [pd.p_create(sgd(0.1)) for _ in range(2)]
        p0 = pd.particles[pids[0]]
        assert p0.state.store is pd.store
        assert "params" in p0.state and "grads" in p0.state
        # a write through the particle is visible in the stacked form
        w = {"w": jnp.ones((3, 2))}
        p0.state["params"] = w
        st = pd.store.stacked("params", pids)
        assert _eq(jax.tree.map(lambda x: x[0], st), w)
        # and a committed stacked form is visible through the particle
        new = functional.stack_pytrees([{"w": jnp.full((3, 2), 2.0)},
                                        {"w": jnp.full((3, 2), 3.0)}])
        pd.store.commit("params", new, pids)
        assert float(p0.state["params"]["w"][0, 0]) == 2.0
        assert float(pd.particles[pids[1]].state["params"]["w"][0, 0]) == 3.0


def test_p_stack_unstack_deprecated_compat():
    """The legacy bridge still round-trips but warns: one compat test
    until the delegates are removed (migrate to store.stacked/commit)."""
    import warnings as _w
    mod, _ = _mod_and_data()
    with PushDistribution(mod, num_devices=1) as pd:
        pids = [pd.p_create(sgd(0.1)) for _ in range(2)]
        with pytest.warns(DeprecationWarning):
            st = pd.p_stack(pids)
        new = jax.tree.map(lambda x: x + 1.0, st)
        with pytest.warns(DeprecationWarning):
            pd.p_unstack(pids, new)
        assert _eq(pd.store.stacked("params", pids), new)


def test_p_predict_compiled_is_one_fused_program():
    """Satellite: under backend="compiled", p_predict must not dispatch n
    sequential NEL forwards — and must match the NEL answer."""
    def init(rng):
        return {"w": jax.random.normal(rng, (3, 2))}

    def loss(p, b):
        return jnp.mean((b[0] @ p["w"] - b[1]) ** 2), {}

    def fwd(p, b):
        return b[0] @ p["w"]

    x = jax.random.normal(jax.random.PRNGKey(3), (8, 3))
    batch = (x, x @ jnp.ones((3, 2)))
    preds = {}
    for backend in ("nel", "compiled"):
        mod = ParticleModule(init, loss, fwd)
        with PushDistribution(mod, num_devices=1, seed=0,
                              backend=backend) as pd:
            for _ in range(3):
                pd.p_create(sgd(0.1))
            d0 = pd.nel.stats["dispatches"]
            preds[backend] = pd.p_predict(batch)
            nd = pd.nel.stats["dispatches"] - d0
            assert nd == (3 if backend == "nel" else 0), (backend, nd)
    assert float(jnp.abs(preds["nel"] - preds["compiled"]).max()) < 1e-4


# ---------------------------------------------------------------------------
# hypothesis property tests: exact-inverse laws
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    SET = dict(deadline=None, max_examples=20)
    shapes_st = st.lists(
        st.lists(st.integers(1, 4), min_size=1, max_size=3).map(tuple),
        min_size=1, max_size=3)

    @settings(**SET)
    @given(n=st.integers(1, 5), shapes=shapes_st, seed=st.integers(0, 100))
    def test_stack_unstack_exact_inverse(n, shapes, seed):
        trees = [_tree(seed + i, shapes) for i in range(n)]
        stacked = functional.stack_pytrees(trees)
        back = functional.unstack_pytree(stacked, n)
        assert all(_eq(a, b) for a, b in zip(trees, back))
        # and the other direction: unstack(stacked) restacks to stacked
        assert _eq(functional.stack_pytrees(back), stacked)

    @settings(**SET)
    @given(n=st.integers(1, 5), shapes=shapes_st, seed=st.integers(0, 100),
           writes=st.lists(st.integers(0, 4), max_size=4))
    def test_store_view_writeback_exact_inverse(n, shapes, seed, writes):
        """Any interleaving of view writes and stacked flushes preserves
        every particle's tree exactly (no float drift: pure data motion)."""
        store = ParticleStore()
        expect = {}
        for pid in range(n):
            store.register(pid)
            expect[pid] = _tree(seed + pid, shapes)
            store.write("params", pid, expect[pid])
        store.stacked("params")
        for w in writes:
            pid = w % n
            expect[pid] = _tree(seed + 1000 + w, shapes)
            store.write("params", pid, expect[pid])
            if w % 2 == 0:           # interleave flushes with writes
                store.stacked("params")
        st_ = store.stacked("params")
        for pid in range(n):
            assert _eq(store.read("params", pid), expect[pid])
            assert _eq(jax.tree.map(lambda x: x[pid], st_), expect[pid])

    @settings(**SET)
    @given(n=st.integers(1, 4), shapes=shapes_st, seed=st.integers(0, 100))
    def test_store_commit_read_exact_inverse(n, shapes, seed):
        store = ParticleStore()
        trees = [_tree(seed + i, shapes) for i in range(n)]
        for pid in range(n):
            store.register(pid)
            store.write("params", pid, jax.tree.map(jnp.zeros_like, trees[pid]))
        store.commit("params", functional.stack_pytrees(trees),
                     pids=list(range(n)))
        assert all(_eq(store.read("params", pid), trees[pid])
                   for pid in range(n))
else:  # keep a visible skip so the gap is auditable in CI output
    @pytest.mark.skip(reason="hypothesis not installed (pip install -e .[dev])")
    def test_store_property_laws():
        pass


# ---------------------------------------------------------------------------
# the sharded compiled path (acceptance criterion): subprocess, 4 devices
# ---------------------------------------------------------------------------

def test_sharded_compiled_matches_nel_across_4_devices():
    """DeepEnsemble/MultiSWAG/SteinVGD fused paths with the particle axis
    sharded across 4 forced host devices: parity with NEL < 1e-4, sharding
    inspection, zero per-epoch host transfers, donated buffers."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "_sharded_store_check.py")],
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
             "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "OK" in out.stdout
