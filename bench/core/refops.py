"""Pieces the plain references share: weights keys from a run's seed and
rotary position embedding. Nothing here imports the program."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def particle_keys(seed: int, n: int):
    """One key per particle, from all the bits of the run's seed."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)
    return [jax.random.fold_in(base, i) for i in range(n)]


def rope(x, theta):
    """Rotary embedding, rotate-half convention: x is (..., S, H, hd) at
    positions 0..S-1."""
    s, hd = x.shape[-3], x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

