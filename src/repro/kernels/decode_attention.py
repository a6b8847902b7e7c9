"""Pallas TPU kernel: single-token decode attention over a KV cache.

The decode-shape hot spot (§Roofline: every decode pair is memory-bound on
reading the cache). One grid cell per (batch*kv_head); the cache streams
through VMEM in (c_block, hd) tiles with an online-softmax accumulator in
scratch — one HBM pass over the cache, no (C,) score materialization in
HBM. Invalid slots (pos < 0, ring-cache holes) are masked via the pos
tile; a ragged last tile (C % c_block != 0) is masked in-kernel with a
column iota rather than padding the caches in HBM — callers never copy.
GQA: the G query heads of a kv head ride in one (G, hd) tile.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, pos_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, n_cb: int, c_block: int, c_len: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32).reshape(-1, q_ref.shape[-1]) * scale
    k = k_ref[...].astype(jnp.float32).reshape(-1, k_ref.shape[-1])
    v = v_ref[...].astype(jnp.float32).reshape(-1, v_ref.shape[-1])
    pos = pos_ref[...].reshape(1, -1)                      # (1, cb)
    # Ragged tail: columns past the true cache length are out-of-bounds
    # reads (undefined contents) — mask them by index, not by pos.
    col = ci * c_block + jax.lax.broadcasted_iota(jnp.int32, (1, c_block), 1)
    valid = (pos >= 0) & (col < c_len)
    # the same tail bound along the sublane axis, for the (cb, hd) v tile
    row = ci * c_block + jax.lax.broadcasted_iota(jnp.int32, (c_block, 1), 0)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, cb)
    s = jnp.where(valid, s, NEG_INF)
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    # Zero masked weights explicitly: on an all-masked tile exp(0)=1, and
    # 0 * (undefined v) would still poison the accumulator with NaNs.
    # Empty slots (pos < 0) hold finite cache contents, so only the
    # out-of-bounds tail needs its v rows zeroed.
    p = jnp.where(valid, p, 0.0)
    v = jnp.where(row < c_len, v, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ci == n_cb - 1)
    def _finish():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, k_pos, *, c_block: int = 512,
                     interpret: bool = True):
    """q: (B, 1, H, hd); k/v_cache: (B, C, KVH, hd); k_pos: (B, C) i32
    (slot position, -1 = empty). Returns (B, 1, H, hd)."""
    B, _, H, hd = q.shape
    C, KVH = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)
    c_block = min(c_block, C)
    n_cb = -(-C // c_block)

    qr = q.reshape(B, KVH, G, hd).reshape(B * KVH, G, hd)
    kr = k_cache.transpose(0, 2, 1, 3).reshape(B * KVH, C, hd)
    vr = v_cache.transpose(0, 2, 1, 3).reshape(B * KVH, C, hd)
    # (B*KVH, 1, C): the unit sublane dim makes the (1, c_block) pos tile
    # a full-extent x lane-aligned block, which the TPU lowering accepts
    pr = jnp.repeat(k_pos, KVH, axis=0)[:, None, :]

    kernel = functools.partial(_decode_kernel, scale=scale, n_cb=n_cb,
                               c_block=c_block, c_len=C)
    out = pl.pallas_call(
        kernel,
        grid=(B * KVH, n_cb),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda b, ci: (b, 0, 0)),
            pl.BlockSpec((1, c_block, hd), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, c_block, hd), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, 1, c_block), lambda b, ci: (b, 0, ci)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda b, ci: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KVH, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(qr, kr, vr, pr)
    return out.reshape(B, KVH, G, hd).reshape(B, 1, H, hd)
