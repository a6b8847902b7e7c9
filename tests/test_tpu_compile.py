"""The main path's Pallas kernels compile for a TPU v5e chip at full width.

No chip is needed: the TPU compiler compiles for a described, unattached
``v5e:2x2`` topology. Interpret-mode tests cannot see what this catches —
block shapes that break the (8, 128) tiling rule, reshapes Mosaic cannot
lower, more VMEM than a kernel may use. Widths are the smoke's: vit-mnist
(Push App. C.1, ~19.8M parameters per particle, d=320, 8 heads) and
qwen1.5-0.5b (16 heads of 64, page size 16).

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and a test worker that cannot
describe it skips these tests instead of failing collection everywhere.
"""
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
