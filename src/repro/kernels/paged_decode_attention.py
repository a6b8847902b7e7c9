"""Pallas TPU kernel: paged single-token decode attention.

The continuous-batching hot spot. KV state lives in a fixed pool of
fixed-size pages — (NP, page_size, KVH, hd) per layer — and each active
sequence owns a row of a block table mapping its logical page index to a
physical page. The kernel never sees a dense per-sequence cache: the
block table and the per-row sequence lengths ride in as scalar-prefetch
operands, and the page index_map gathers exactly the pages a row needs,
one (page_size, hd) tile per grid step, into the same online-softmax
scratch accumulator ``decode_attention.py`` uses. One HBM pass over the
*live* pages only; dead pages are never read.

Row conventions (shared with serve.paging.PagePool):
  * ``seq_lens[b]`` is the index of the LAST valid position (the token
    being decoded attends to positions ``0..seq_lens[b]`` inclusive);
  * ``seq_lens[b] == -1`` marks an inactive row — its output is zeros
    and no page contents influence it;
  * block-table entries past the live page count are unread garbage as
    far as correctness goes, but schedulers keep them at 0 so the
    index_map stays in bounds.

The kernel vmaps over a leading particle axis (q and pages batched,
block table / seq_lens shared) — validated in interpret mode, which is
how serve stacks it over the ParticleStore capacity axis.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(bt_ref, sl_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, page_size: int,
                  n_pmax: int):
    b = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32).reshape(-1, q_ref.shape[-1]) * scale
    k = k_ref[...].astype(jnp.float32).reshape(page_size, -1)
    v = v_ref[...].astype(jnp.float32).reshape(page_size, -1)
    sl = sl_ref[b]
    col = pi * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    valid = (col <= sl) & (sl >= 0)
    # the same bound along the sublane axis, for the (ps, hd) v tile
    # (built from its own iota: a lane-to-sublane reshape does not lower)
    row = pi * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (page_size, 1), 0)
    v_valid = (row <= sl) & (sl >= 0)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, ps)
    s = jnp.where(valid, s, NEG_INF)
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    # Zero masked weights: on an all-masked page exp(0)=1, and the slots
    # past the sequence tail hold stale writes from a previous owner.
    p = jnp.where(valid, p, 0.0)
    v = jnp.where(v_valid, v, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(pi == n_pmax - 1)
    def _finish():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def _paged_window_kernel(bt_ref, sl_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, scale: float,
                         page_size: int, n_pmax: int, group: int):
    """Drafted-window variant: the q tile carries W queries per row
    (folded into the row dimension as w*G + g), each at absolute position
    ``sl + w`` — so every page is streamed from HBM ONCE for the whole
    window, and causality within the window falls out of the per-query
    position mask (window token w sits at column sl + w)."""
    b = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32).reshape(-1, q_ref.shape[-1]) * scale
    k = k_ref[...].astype(jnp.float32).reshape(page_size, -1)
    v = v_ref[...].astype(jnp.float32).reshape(page_size, -1)
    sl = sl_ref[b]
    wg = q.shape[0]                                 # W * G rows
    col = pi * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    w_idx = jax.lax.broadcasted_iota(jnp.int32, (wg, 1), 0) // group
    # query w may see columns 0..sl+w: causal within the drafted window,
    # the full prefix outside it; the sl >= 0 leg zeroes inactive rows
    valid = (col <= sl + w_idx) & (sl >= 0)         # (W*G, ps)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.where(valid, s, NEG_INF)
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(pi == n_pmax - 1)
    def _finish():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def paged_decode_window_attention(q, k_pages, v_pages, block_tables,
                                  seq_lens, *, interpret: bool = True):
    """Speculative-verify attention: q: (B, W, H, hd) — W drafted-window
    queries per row, query w at absolute position ``seq_lens[b] + w``;
    k/v_pages: (NP, page_size, KVH, hd); block_tables: (B, n_pmax) i32;
    seq_lens: (B,) i32 (position of query 0, -1 = inactive row).
    Returns (B, W, H, hd); inactive rows come back as zeros.

    Same scalar-prefetched page gather and triple masking as the
    single-token kernel; the grid stays (B, KVH, n_pmax) and the window
    rides inside the q tile so pages are read once per window, not once
    per drafted token."""
    B, W, H, hd = q.shape
    page_size, KVH = k_pages.shape[1], k_pages.shape[2]
    G = H // KVH
    n_pmax = block_tables.shape[1]
    scale = 1.0 / math.sqrt(hd)
    # row r = w*G + g: window-major so the kernel recovers w as r // G
    qr = q.reshape(B, W, KVH, G, hd).transpose(0, 2, 1, 3, 4) \
         .reshape(B, KVH, W * G, hd)
    kr = k_pages.transpose(2, 0, 1, 3)        # (KVH, NP, ps, hd)
    vr = v_pages.transpose(2, 0, 1, 3)

    kernel = functools.partial(_paged_window_kernel, scale=scale,
                               page_size=page_size, n_pmax=n_pmax, group=G)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KVH, n_pmax),
        in_specs=[
            pl.BlockSpec((1, 1, W * G, hd),
                         lambda b, h, pi, bt, sl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, h, pi, bt, sl: (h, bt[b, pi], 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, h, pi, bt, sl: (h, bt[b, pi], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, W * G, hd),
                               lambda b, h, pi, bt, sl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((W * G, 1), jnp.float32),
            pltpu.VMEM((W * G, 1), jnp.float32),
            pltpu.VMEM((W * G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, W * G, hd), q.dtype),
        interpret=interpret,
        name="paged_decode_window_attention",
    )(block_tables, seq_lens, qr, kr, vr)
    return out.reshape(B, KVH, W, G, hd).transpose(0, 2, 1, 3, 4) \
              .reshape(B, W, H, hd)


def paged_decode_attention(q, k_pages, v_pages, block_tables, seq_lens, *,
                           interpret: bool = True):
    """q: (B, 1, H, hd); k/v_pages: (NP, page_size, KVH, hd);
    block_tables: (B, n_pmax) i32; seq_lens: (B,) i32 (last valid
    position, -1 = inactive row). Returns (B, 1, H, hd); inactive rows
    come back as zeros."""
    B, _, H, hd = q.shape
    page_size, KVH = k_pages.shape[1], k_pages.shape[2]
    G = H // KVH
    n_pmax = block_tables.shape[1]
    scale = 1.0 / math.sqrt(hd)
    qr = q.reshape(B, KVH, G, hd)
    kr = k_pages.transpose(2, 0, 1, 3)        # (KVH, NP, ps, hd)
    vr = v_pages.transpose(2, 0, 1, 3)

    kernel = functools.partial(_paged_kernel, scale=scale,
                               page_size=page_size, n_pmax=n_pmax)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KVH, n_pmax),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, pi, bt, sl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, h, pi, bt, sl: (h, bt[b, pi], 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, h, pi, bt, sl: (h, bt[b, pi], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, pi, bt, sl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tables, seq_lens, qr, kr, vr)
    return out.reshape(B, 1, H, hd)
