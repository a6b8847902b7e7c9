"""Pallas TPU flash-attention (forward) kernel with GQA + causal masking.

Blocked online-softmax: grid (batch*kv_head, q_blocks, k_blocks), with the
(m, l, acc) running state held in VMEM scratch across the innermost k-block
loop. Block shapes are MXU-aligned (q_block x head_dim and k_block x
head_dim tiles; head_dim is padded to a multiple of 128 by ops.py if
needed). Used on the inference path (prefill); training uses the pure-jnp
flash in models.blocks (differentiable). Validated against ref.flash_attention
in interpret mode over shape/dtype sweeps (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, q_block: int, k_block: int,
                  n_kb: int, s_real: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        # skip blocks strictly above the diagonal
        run = (ki * k_block) <= (qi * q_block + q_block - 1)

    @pl.when(run if causal else True)
    def _body():
        q = q_ref[...].astype(jnp.float32).reshape(-1, q_ref.shape[-1]) * scale
        k = k_ref[...].astype(jnp.float32).reshape(-1, k_ref.shape[-1])
        v = v_ref[...].astype(jnp.float32).reshape(-1, v_ref.shape[-1])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        g_qb, kb = s.shape
        kpos = ki * k_block + jax.lax.broadcasted_iota(jnp.int32, (g_qb, kb), 1)
        mask = kpos < s_real                               # padded keys
        if causal:
            qpos = qi * q_block + (jax.lax.broadcasted_iota(
                jnp.int32, (g_qb, kb), 0) % q_block)
            mask = mask & (kpos <= qpos)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == n_kb - 1)
    def _finish():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, q_block: int = 128,
                    k_block: int = 128, interpret: bool = True):
    """q: (B, S, H, hd); k, v: (B, S, KVH, hd) -> (B, S, H, hd).

    GQA: queries are grouped per kv head; each grid cell handles one
    (batch, kv_head) pair with its G query heads folded into the q tile.
    """
    B, S, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)
    q_block = min(q_block, S)
    k_block = min(k_block, S)
    n_qb = -(-S // q_block)
    n_kb = -(-S // k_block)
    pad = n_qb * q_block - S
    if pad:  # pad sequence (padded q rows are discarded; padded k cols are
             # masked by causal or produce uniform attn rows we slice off)
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S_p = S + pad
    else:
        S_p = S

    # layout: (B*KVH, G, S, hd) for q; (B*KVH, S, hd) for k/v
    qr = q.reshape(B, S_p, KVH, G, hd).transpose(0, 2, 3, 1, 4)
    qr = qr.reshape(B * KVH, G, S_p, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KVH, S_p, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KVH, S_p, hd)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               q_block=q_block, k_block=k_block, n_kb=n_kb,
                               s_real=S)
    out = pl.pallas_call(
        kernel,
        grid=(B * KVH, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, G, q_block, hd), lambda b, qi, ki: (b, 0, qi, 0)),
            pl.BlockSpec((1, k_block, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, k_block, hd), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, q_block, hd), lambda b, qi, ki: (b, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KVH, G, S_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * q_block, 1), jnp.float32),
            pltpu.VMEM((G * q_block, 1), jnp.float32),
            pltpu.VMEM((G * q_block, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qr, kr, vr)
    out = out.reshape(B, KVH, G, S_p, hd).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, S_p, H, hd)[:, :S]
