"""Pallas TPU kernel: fused SWAG running-moment update.

mean' = (mean*n + theta)/(n+1); sq' = (sq*n + theta^2)/(n+1), fused in one
pass over the flattened parameter vector (one HBM read of theta instead of
two, one kernel launch instead of a tree of elementwise HLOs). The vector
is padded to a multiple of 8 * 1024 and viewed as (rows, 1024); the grid
streams (8, 1024) f32 blocks (one full sublane x lane tile group) through
VMEM.

update_moments() is the pytree-level entry point used by repro.bdl.swag.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 1024
SUBLANES = 8
BLOCK = SUBLANES * LANES


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """Interpret mode is a platform property, not a call-site choice:
    compiled on TPU, interpreted everywhere else (None = auto)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _moments_kernel(n_ref, mean_ref, sq_ref, p_ref, out_mean_ref, out_sq_ref):
    n = n_ref[0, 0]
    inv = 1.0 / (n + 1.0)
    p = p_ref[...].astype(jnp.float32)
    out_mean_ref[...] = (mean_ref[...] * n + p) * inv
    out_sq_ref[...] = (sq_ref[...] * n + p * p) * inv


def moments_flat(mean, sq_mean, params, n, *,
                 interpret: Optional[bool] = None):
    """mean/sq_mean/params: (D,) f32. Returns (mean', sq')."""
    interpret = _resolve_interpret(interpret)
    D = mean.shape[0]
    nb = -(-D // BLOCK)
    pad = nb * BLOCK - D
    if pad:
        mean = jnp.pad(mean, (0, pad))
        sq_mean = jnp.pad(sq_mean, (0, pad))
        params = jnp.pad(params, (0, pad))
    shp = (nb * SUBLANES, LANES)
    tile = pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0))
    n_arr = jnp.asarray(n, jnp.float32).reshape(1, 1)
    out_mean, out_sq = pl.pallas_call(
        _moments_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)), tile, tile, tile],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct(shp, jnp.float32),
                   jax.ShapeDtypeStruct(shp, jnp.float32)],
        interpret=interpret,
        name="swag_moments",
    )(n_arr, mean.reshape(shp), sq_mean.reshape(shp), params.reshape(shp))
    return out_mean.reshape(-1)[:D], out_sq.reshape(-1)[:D]


def _diag_std_kernel(mean_ref, sq_ref, out_ref):
    m = mean_ref[...]
    out_ref[...] = jnp.sqrt(jnp.maximum(sq_ref[...] - m * m, 1e-30))


def diag_std_flat(mean, sq_mean, *, interpret: Optional[bool] = None):
    """sqrt(max(sq_mean - mean^2, eps)) fused over (D,) f32 — the SWAG
    diagonal scale read at serve-time sampling (one HBM pass instead of
    three elementwise HLOs). Same platform gating as the moment update:
    ``interpret=None`` resolves via ``_resolve_interpret``."""
    interpret = _resolve_interpret(interpret)
    D = mean.shape[0]
    nb = -(-D // BLOCK)
    pad = nb * BLOCK - D
    if pad:
        mean = jnp.pad(mean, (0, pad))
        sq_mean = jnp.pad(sq_mean, (0, pad), constant_values=1.0)
    shp = (nb * SUBLANES, LANES)
    tile = pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _diag_std_kernel,
        grid=(nb,),
        in_specs=[tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(shp, jnp.float32),
        interpret=interpret,
        name="swag_diag_std",
    )(mean.reshape(shp), sq_mean.reshape(shp))
    return out.reshape(-1)[:D]


def update_moments(mean, sq_mean, params, n, *,
                   interpret: Optional[bool] = None):
    """Pytree-level fused moment update (ravel -> kernel -> unravel)."""
    from jax.flatten_util import ravel_pytree
    m_flat, unravel = ravel_pytree(mean)
    s_flat, _ = ravel_pytree(sq_mean)
    p_flat, _ = ravel_pytree(params)
    nm, ns = moments_flat(m_flat.astype(jnp.float32), s_flat.astype(jnp.float32),
                          p_flat.astype(jnp.float32), n, interpret=interpret)
    return unravel(nm), unravel(ns)
