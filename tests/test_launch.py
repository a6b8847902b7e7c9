"""Launch-layer unit tests that run on the single CPU device: plans, batch
specs, cache specs, and abstract step building (trace-only via eval_shape
on a 1x1 mesh — the full 512-device compile lives in test_system.py's slow
subprocess test)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.configs import INPUT_SHAPES
from repro.launch import steps as S
from repro.launch import mesh as mesh_compat
from repro.launch.plans import plan_for


def tiny_mesh():
    return mesh_compat.make_mesh((1, 1), ("data", "model"))


# ---------------------------------------------------------------------------
# mesh factory validation (ISSUE-7 satellite): bad axis sizes raise a
# clear ValueError, not a cryptic reshape/XLA error
# ---------------------------------------------------------------------------

def test_make_bench_mesh_rejects_non_divisible_model():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="does not divide"):
        mesh_compat.make_bench_mesh(n, model=n + 1)
    with pytest.raises(ValueError, match="positive"):
        mesh_compat.make_bench_mesh(n, model=0)


def test_make_mesh_rejects_oversized_shape():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="devices"):
        mesh_compat.make_mesh((n + 1,), ("data",))
    with pytest.raises(ValueError, match="disagree"):
        mesh_compat.make_mesh((1, 1), ("data",))
    # valid submesh shapes still build (the 1x1 trace mesh everywhere)
    assert tiny_mesh() is not None


def test_make_bench_mesh_2d_axes():
    n = len(jax.devices())
    mesh = mesh_compat.make_bench_mesh(n, model=1)
    assert tuple(mesh.axis_names) == ("data", "model")
    assert int(mesh.shape["data"]) == n and int(mesh.shape["model"]) == 1


def test_pick_model_axis_budget():
    # no memory info / no params -> particle-parallel (model=1)
    assert mesh_compat.pick_model_axis(0, 8) == 1
    assert mesh_compat.pick_model_axis(100, 8, device_memory_bytes=None) == 1
    # smallest divisor of n_devices whose shard fits fraction*memory
    assert mesh_compat.pick_model_axis(
        100, 8, device_memory_bytes=1000) == 1
    assert mesh_compat.pick_model_axis(
        1000, 8, device_memory_bytes=1000) == 2
    assert mesh_compat.pick_model_axis(
        2300, 8, device_memory_bytes=1000) == 4
    # never fits: best effort = every device
    assert mesh_compat.pick_model_axis(
        10**9, 8, device_memory_bytes=1000) == 8


@pytest.mark.parametrize("arch", sorted(configs.ARCHS))
@pytest.mark.parametrize("shape", sorted(INPUT_SHAPES))
def test_plans_are_coherent(arch, shape):
    cfg = configs.get(arch)
    shp = INPUT_SHAPES[shape]
    plan = plan_for(cfg, shp)
    assert plan.particles >= 1
    if shp.kind == "train":
        assert shp.global_batch % plan.microbatches == 0
    if plan.particle_axis is not None:
        assert plan.particles % 16 == 0  # must shard over data=16


def test_build_shapes_ensemble_train():
    cfg = configs.get("qwen1.5-0.5b").smoke()
    shp = INPUT_SHAPES["train_4k"]
    plan = plan_for(configs.get("qwen1.5-0.5b"), shp)
    import dataclasses
    plan = dataclasses.replace(plan, particles=2, microbatches=2)
    mesh = tiny_mesh()
    with jax.set_mesh(mesh):
        step, args, sh = S.build(cfg, dataclasses.replace(
            shp, seq_len=32, global_batch=4), plan, mesh)
        out = jax.eval_shape(step, *args)
    # params out matches params in (stacked particle axis preserved)
    assert jax.tree.structure(out[0]) == jax.tree.structure(args[0])
    assert out[2].shape == (2, )  # per-particle losses


def test_build_shapes_svgd_train():
    cfg = configs.get("qwen1.5-0.5b").smoke()
    import dataclasses
    shp = dataclasses.replace(INPUT_SHAPES["train_4k"], seq_len=32,
                              global_batch=4)
    plan = dataclasses.replace(plan_for(configs.get("qwen1.5-0.5b"),
                                        INPUT_SHAPES["train_4k"]),
                               particles=2, microbatches=2)
    mesh = tiny_mesh()
    with jax.set_mesh(mesh):
        step, args, sh = S.build(cfg, shp, plan, mesh, bdl="svgd")
        out = jax.eval_shape(step, *args)
    assert jax.tree.structure(out[0]) == jax.tree.structure(args[0])


def test_build_decode_cache_roundtrip():
    cfg = configs.get("gemma3-4b").smoke()
    import dataclasses
    shp = dataclasses.replace(INPUT_SHAPES["decode_32k"], seq_len=64,
                              global_batch=2)
    plan = dataclasses.replace(plan_for(configs.get("gemma3-4b"),
                                        INPUT_SHAPES["decode_32k"]),
                               particles=2)
    mesh = tiny_mesh()
    with jax.set_mesh(mesh):
        step, args, sh = S.build(cfg, shp, plan, mesh)
        logits, new_cache = jax.eval_shape(step, *args)
    assert logits.shape == (2, cfg.vocab_size)
    # cache structure is preserved (serve_step is iterable)
    assert jax.tree.structure(new_cache) == jax.tree.structure(args[2])


def test_hlo_cost_trip_counts():
    """hlo_cost multiplies scan-body costs by trip counts."""
    from repro.launch import hlo_cost as hc

    def f(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    x = jnp.ones((8, 8))
    w = jnp.ones((8, 8))
    txt = jax.jit(f).lower(x, w).compile().as_text()
    c = hc.cost(txt)
    expected = 2 * 8 * 8 * 8 * 7  # 7 iterations of an 8x8x8 matmul
    assert c["flops"] == pytest.approx(expected, rel=0.01), c["flops"]


def test_roofline_peaks_keyed_by_device_kind():
    from repro.launch import roofline
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    assert roofline.DRYRUN_DEVICE_KIND in roofline.PEAKS
    with pytest.raises(ValueError, match="no peak rates"):
        roofline.peaks("cpu")


def test_compile_cache_dir_from_env_else_checkout(monkeypatch, tmp_path):
    from repro.launch.compile_cache import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        env_dir = str(tmp_path / "from_env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert use_compile_cache(str(tmp_path)) == env_dir
        assert jax.config.jax_compilation_cache_dir == before  # left to JAX
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(tmp_path / ".jax_cache")
        assert use_compile_cache(str(tmp_path)) == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
