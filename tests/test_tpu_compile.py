"""The main path's Pallas kernels compile for a TPU v5e chip at full width.

No chip is needed: the TPU compiler compiles for a described, unattached
``v5e:2x2`` topology. Interpret-mode tests cannot see what this catches —
block shapes that break the (8, 128) tiling rule, reshapes Mosaic cannot
lower, more VMEM than a kernel may use. Widths are the smoke's: vit-mnist
(Push App. C.1, ~19.8M parameters per particle, d=320, 8 heads) and
qwen1.5-0.5b (16 heads of 64, page size 16); the paged kernel and the
paged decode step also at the benchmark's serving widths.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and a test worker that cannot
describe it skips these tests instead of failing collection everywhere.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import attention
from repro.kernels import decode_attention as dense_decode
from repro.kernels import paged_decode_attention as paged
from repro.kernels import svgd_rbf, swag_moments

VIT_PARAMS = 19_775_360          # vit-mnist parameters per particle
QWEN = dict(H=16, KVH=16, hd=64)  # qwen1.5-0.5b attention
B, C, NP, PS, NPMAX, W = 4, 2048, 256, 16, 64, 5
# bench/configs/qwen1.5-0.5b.json: 2 particles, 32 rows, 50 pages of 128,
# 10 pages a sequence
SERVE = dict(P=2, B=32, NP=50, PS=128, NPMAX=10)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # compiles for a described chip cannot be read back from a persistent
    # cache without one: keep the cache out of these tests
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _f32(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cases(s):
    """kernel name -> (function, abstract args) at full width."""
    H, KVH, hd = QWEN["H"], QWEN["KVH"], QWEN["hd"]
    D = VIT_PARAMS
    i32 = jnp.int32
    return {
        "swag_moments": (
            lambda m, q, p, n: swag_moments.moments_flat(
                m, q, p, n, interpret=False),
            (_f32(s, (D,)), _f32(s, (D,)), _f32(s, (D,)), _f32(s, ()))),
        "swag_diag_std": (
            lambda m, q: swag_moments.diag_std_flat(m, q, interpret=False),
            (_f32(s, (D,)), _f32(s, (D,)))),
        "svgd_sqdist": (
            lambda t: svgd_rbf.pairwise_sqdist(t, interpret=False),
            (_f32(s, (8, D)),)),
        "svgd_force": (
            lambda t, g, ell: svgd_rbf.svgd_force(t, g, ell,
                                                  interpret=False),
            (_f32(s, (8, D)), _f32(s, (8, D)), _f32(s, ()))),
        "decode_attention": (
            lambda q, k, v, pos: dense_decode.decode_attention(
                q, k, v, pos, interpret=False),
            (_f32(s, (B, 1, H, hd)), _f32(s, (B, C, KVH, hd)),
             _f32(s, (B, C, KVH, hd)), _f32(s, (B, C), i32))),
        "paged_decode_attention": (
            lambda q, k, v, bt, sl: paged.paged_decode_attention(
                q, k, v, bt, sl, interpret=False),
            (_f32(s, (B, 1, H, hd)), _f32(s, (NP, PS, KVH, hd)),
             _f32(s, (NP, PS, KVH, hd)), _f32(s, (B, NPMAX), i32),
             _f32(s, (B,), i32))),
        "paged_decode_window_attention": (
            lambda q, k, v, bt, sl: paged.paged_decode_window_attention(
                q, k, v, bt, sl, interpret=False),
            (_f32(s, (B, W, H, hd)), _f32(s, (NP, PS, KVH, hd)),
             _f32(s, (NP, PS, KVH, hd)), _f32(s, (B, NPMAX), i32),
             _f32(s, (B,), i32))),
        # vit-mnist: 4 patches + cls = 5 tokens, 8 heads of 40
        "flash_attention": (
            lambda q, k, v: attention.flash_attention(
                q, k, v, causal=False, interpret=False),
            (_f32(s, (64, 5, 8, 40)), _f32(s, (64, 5, 8, 40)),
             _f32(s, (64, 5, 8, 40)))),
    }


KERNELS = ("swag_moments", "swag_diag_std", "svgd_sqdist", "svgd_force",
           "decode_attention", "paged_decode_attention",
           "paged_decode_window_attention", "flash_attention")


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _cases(one_chip)[name]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls, f"{name}: no Mosaic kernel in the compiled program"
    assert any(re.search(rf'/{name}/pallas_call"', line) for line in calls)


def _pool_sized(text, n, *, scope=None):
    """Instructions other than parameters, bitcasts and Mosaic calls whose
    f32 result holds ``n`` elements (under op metadata ``scope``, if
    given)."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = f32\[([\d,]+)\]", line)
        if not m or any(t in line for t in (
                " parameter(", " bitcast(", " get-tuple-element(",
                'custom_call_target="tpu_custom_call"')):
            continue
        if scope is not None and scope not in line:
            continue
        if math.prod(int(d) for d in m.group(2).split(",")) == n:
            found.append(m.group(1))
    return found


def _mosaic_calls(text, name):
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and re.search(rf'{name}\)?/pallas_call"', line)]


def test_paged_kernel_reads_the_pool_in_place(one_chip):
    """At the serving widths, vmapped over the particles, the paged kernel
    is one Mosaic call and nothing else in the program makes a copy,
    transpose or fusion the size of the pool. The pool is laid out
    row-major, as the decode step holds each layer's pool."""
    from jax.experimental.layout import Format, Layout
    H, KVH, hd = QWEN["H"], QWEN["KVH"], QWEN["hd"]
    P, Bs, NPs, PSs, NPMAXs = (SERVE[k] for k in
                               ("P", "B", "NP", "PS", "NPMAX"))
    rows = Format(Layout(major_to_minor=tuple(range(5))), one_chip)
    pool = jax.ShapeDtypeStruct((P, NPs, PSs, KVH, hd), jnp.float32,
                                sharding=rows)
    fn = jax.vmap(lambda q, k, v, bt, sl: paged.paged_decode_attention(
        q, k, v, bt, sl, interpret=False), in_axes=(0, 0, 0, None, None))
    text = jax.jit(fn).lower(
        _f32(one_chip, (P, Bs, 1, H, hd)), pool, pool,
        _f32(one_chip, (Bs, NPMAXs), jnp.int32),
        _f32(one_chip, (Bs,), jnp.int32)).compile().as_text()
    assert len(_mosaic_calls(text, "paged_decode_attention")) == 1
    assert _pool_sized(text, P * NPs * PSs * KVH * hd) == []


def test_paged_decode_step_copies_no_pool_under_attention(one_chip,
                                                          monkeypatch):
    """The whole paged decode step (two layers at qwen1.5-0.5b's widths,
    the serving pool, particles vmapped, pages donated): under
    ``push.attention`` no copy or transpose has a layer's pool size, so
    the kernel reads the pages the token write (a scatter) leaves, as
    they lie."""
    from repro import configs
    from repro.kernels import ops
    from repro.models import api
    monkeypatch.setattr(ops, "paged_decode_attention",
                        lambda q, k, v, bt, sl: paged.paged_decode_attention(
                            q, k, v, bt, sl, interpret=False))
    cfg = configs.get("qwen1.5-0.5b").replace(n_units=2)
    P, Bs, NPs, PSs, NPMAXs = (SERVE[k] for k in
                               ("P", "B", "NP", "PS", "NPMAX"))

    def stacked(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            (P,) + a.shape, a.dtype, sharding=one_chip), tree)

    params = stacked(jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), cfg)))
    pages = stacked(jax.eval_shape(lambda: api.paged_cache_init(
        cfg, num_pages=NPs, page_size=PSs)))

    def step(params, pages, packed):
        tokens, sl, bt = packed[:, 0], packed[:, 1], packed[:, 2:]
        logits, pages = jax.vmap(lambda p, pg: api.decode_step_paged(
            p, tokens, pg, bt, sl, cfg))(params, pages)
        return logits.mean(0), pages

    text = jax.jit(step, donate_argnums=(1,)).lower(
        params, pages, _f32(one_chip, (Bs, 2 + NPMAXs), jnp.int32)
    ).compile().as_text()
    assert _mosaic_calls(text, "paged_decode_attention")
    layer_pool = P * NPs * PSs * cfg.n_kv_heads * cfg.hd
    moved = [name for name in _pool_sized(text, layer_pool,
                                          scope="push.attention")
             if "copy" in name or "transpose" in name]
    assert moved == []
