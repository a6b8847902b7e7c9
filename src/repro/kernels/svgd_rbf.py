"""Pallas TPU kernels for the SVGD hot spot: the pairwise RBF kernel matrix
and the driving force over flattened particle parameters.

The paper identifies the kernel-matrix computation as SVGD's fundamental
bottleneck (§5.1). On TPU the shape is extreme: n (particles) is tiny
(2..256) while D (flattened parameters) is huge (1e6..1e9). The TPU-native
blocking is therefore over D: stream (n, Db) tiles of theta/grads through
VMEM and accumulate the (n, n) Gram/distance matrix (which always fits
VMEM) across grid steps; the force pass re-streams D tiles against the
resident (n, n) kernel matrix. Both kernels are MXU-shaped: every grid
step is an (n x Db) @ (Db x n) or (n x n) @ (n x Db) matmul.

  pairwise_sqdist_kernel: grid (D // Db,), out (n, n) accumulated in place
  svgd_force_kernel:      grid (D // Db,), out (n, Db) tiles

ops.py wraps these with padding + jit; ref.py is the pure-jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_D = 2048


def _sqdist_kernel(theta_ref, out_ref):
    """One D-tile: accumulate ||theta_i - theta_j||^2 partial sums."""
    t = theta_ref[...].astype(jnp.float32)              # (n, Db)
    sq = jnp.sum(t * t, axis=1)                         # (n,)
    gram = jax.lax.dot_general(t, t, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    # clamp: each block's partial is a squared distance over a dim slice
    part = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gram, 0.0)
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
    out_ref[...] += part


def _force_kernel(ktn_ref, ksum_ref, inv_ell2_ref, theta_ref, grads_ref, out_ref):
    """One D-tile of phi = (K^T G + (ksum*theta - K^T theta) * inv_ell2)/n."""
    kt = ktn_ref[...]                                   # (n, n) = K^T / n
    t = theta_ref[...].astype(jnp.float32)              # (n, Db)
    g = grads_ref[...].astype(jnp.float32)
    ksum = ksum_ref[...]                                # (n, 1), sum_j k_ji / n
    inv_ell2 = inv_ell2_ref[0, 0]
    attract = jax.lax.dot_general(kt, g, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    ktt = jax.lax.dot_general(kt, t, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    out_ref[...] = attract - (ksum * t - ktt) * inv_ell2


def pairwise_sqdist(theta, *, block_d: int = DEFAULT_BLOCK_D,
                    interpret: bool = True):
    """theta: (n, D) -> (n, n) squared distances."""
    n, D = theta.shape
    block_d = min(block_d, D)
    nb = -(-D // block_d)
    pad = nb * block_d - D
    if pad:
        theta = jnp.pad(theta, ((0, 0), (0, pad)))      # zeros don't change d2
    return pl.pallas_call(
        _sqdist_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((n, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
        name="svgd_sqdist",
    )(theta)


def svgd_force(theta, grads, lengthscale, *, block_d: int = DEFAULT_BLOCK_D,
               interpret: bool = True):
    """theta, grads: (n, D) f32 -> phi (n, D): the SVGD descent direction."""
    n, D = theta.shape
    d2 = pairwise_sqdist(theta, block_d=block_d, interpret=interpret)
    if not isinstance(lengthscale, jnp.ndarray):
        lengthscale = jnp.asarray(lengthscale, jnp.float32)
    ell2 = lengthscale * lengthscale
    d2 = d2 * (1.0 - jnp.eye(n, dtype=d2.dtype))        # exact-zero diagonal
    K = jnp.exp(-0.5 * d2 / ell2)                       # (n, n) k_ji
    ktn = K.T / n                                       # rows: receiving i
    ksum = (K.sum(axis=0) / n)[:, None]                 # (n, 1)
    inv_ell2 = (1.0 / ell2).reshape(1, 1)

    block_d = min(block_d, D)
    nb = -(-D // block_d)
    pad = nb * block_d - D
    if pad:
        theta = jnp.pad(theta, ((0, 0), (0, pad)))
        grads = jnp.pad(grads, ((0, 0), (0, pad)))
    phi = pl.pallas_call(
        _force_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((n, block_d), lambda i: (0, i)),
            pl.BlockSpec((n, block_d), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, nb * block_d), jnp.float32),
        interpret=interpret,
        name="svgd_force",
    )(ktn, ksum, inv_ell2, theta, grads)
    return phi[:, :D]
