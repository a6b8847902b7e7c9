"""Pallas TPU kernel: paged single-token decode attention.

The continuous-batching hot spot. KV state lives in a fixed pool of
fixed-size pages — (NP, page_size, KVH, hd) per layer — and each active
sequence owns a row of a block table mapping its logical page index to a
physical page. The kernel never sees a dense per-sequence cache: the
block table and the per-row sequence lengths ride in as scalar-prefetch
operands, and the page index_map gathers the pages a row needs into the
same online-softmax scratch accumulator ``decode_attention.py`` uses.

The grid is ``(B, n_pmax)``. One step copies one whole page of K and of
V, ``(page_size, KVH, hd)`` as the pool holds it, and attends every head
of the row to it. A page slot past the row's last live page (and every
slot of an inactive row) maps to the page the step before it read, so
the pipeline makes no copy for it, and its body is skipped: the walk
costs a copy only per live page, plus a fixed cost per grid step.

Row conventions (shared with serve.paging.PagePool):
  * ``seq_lens[b]`` is the index of the LAST valid position (the token
    being decoded attends to positions ``0..seq_lens[b]`` inclusive);
  * ``seq_lens[b] == -1`` marks an inactive row — its output is zeros
    and no page contents influence it;
  * block-table entries past the live page count are never read here;
    schedulers keep them at 0 for the window kernel below, which reads
    every slot.

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


def _walk_table(block_tables, seq_lens, page_size: int):
    """The page that grid step ``(b, pi)`` reads, flattened row-major.

    A live slot (``pi <= seq_lens[b] // page_size``) reads ``bt[b, pi]``.
    Every other slot, and every slot of an inactive row, repeats the page
    of the live slot before it in grid order (slots before the first live
    one take the first live page; with no live slot at all, every step
    reads page 0): the pipeline then sees an unchanged block index and
    makes no copy. Entries of ``block_tables`` past a row's live pages
    are never read."""
    B, n_pmax = block_tables.shape
    n_live = jnp.where(seq_lens >= 0, seq_lens // page_size + 1, 0)
    live = (jnp.arange(n_pmax, dtype=jnp.int32)[None, :]
            < n_live[:, None]).reshape(-1)
    step = jnp.arange(B * n_pmax, dtype=jnp.int32)
    src = jax.lax.cummax(jnp.where(live, step, -1), axis=0)
    src = jnp.where(src < 0, jnp.argmax(live).astype(jnp.int32), src)
    return jnp.where(live.any(), block_tables.reshape(-1)[src], 0)


def _paged_kernel(walk_ref, sl_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, page_size: int,
                  n_pmax: int, group: int):
    """One grid step: one page slot of one row, every head at once.

    The page ``(page_size, KVH, hd)`` is read as ``(page_size * KVH,
    hd)``: row ``r = t * KVH + h`` holds position ``t`` of KV head ``h``.
    Query ``j`` (of KV head ``j // group``) scores every row and keeps
    its own head's, so a page is two matmuls whatever KVH is."""
    b = pl.program_id(0)
    pi = pl.program_id(1)
    sl = sl_ref[b]

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # a slot past the row's last live page (every slot of an inactive
    # row) computes nothing; _walk_table spares it the copy too
    @pl.when(pi * page_size <= sl)
    def _page():
        H, hd = q_ref.shape[1], q_ref.shape[2]
        kvh = k_ref.shape[2]
        n = page_size * kvh
        q = q_ref[0].astype(jnp.float32) * scale               # (H, hd)
        k = k_ref[0].astype(jnp.float32).reshape(n, hd)
        v = v_ref[0].astype(jnp.float32).reshape(n, hd)
        # rows r < live hold positions pi * page_size .. sl
        live = (sl - pi * page_size + 1) * kvh
        col = jax.lax.broadcasted_iota(jnp.int32, (H, n), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (H, n), 0)
        if group > 1:
            head = head // group
        valid = (col < live) & (col % kvh == head)
        # the same bound along the sublane axis, for the (n, hd) v tile
        # (built from its own iota: a lane-to-sublane reshape does not lower)
        v_valid = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) < live
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (H, n)
        s = jnp.where(valid, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        # Zero masked weights: other heads' rows, and the slots past the
        # sequence tail, which hold stale writes from a previous owner
        p = jnp.where(valid, p, 0.0)
        v = jnp.where(v_valid, v, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    @pl.when(pi == n_pmax - 1)
    def _finish():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


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
    n_pmax = block_tables.shape[1]
    scale = 1.0 / math.sqrt(hd)
    walk = _walk_table(block_tables, seq_lens, page_size)

    def page(b, pi, walk, sl):
        return (walk[b * n_pmax + pi], 0, 0, 0)

    kernel = functools.partial(_paged_kernel, scale=scale,
                               page_size=page_size, n_pmax=n_pmax,
                               group=H // KVH)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pmax),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, pi, walk, sl: (b, 0, 0)),
            pl.BlockSpec((1, page_size, KVH, hd), page),
            pl.BlockSpec((1, page_size, KVH, hd), page),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, pi, walk, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(walk, seq_lens, q.reshape(B, H, hd), k_pages, v_pages)
    return out.reshape(B, 1, H, hd)
