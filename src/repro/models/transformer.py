"""Generic layer-stacked LM: layer-kind dispatch + lax.scan over pattern units.

The layer stack is cfg.head_layers + cfg.pattern * cfg.n_units +
cfg.tail_layers (see configs.base). Repeated pattern units are *scanned*
(stacked params, single traced body) so compile time and HLO size are
depth-independent — llama3-405b's 126 layers compile as one scanned unit.

`shared_attn` layers (zamba2) use a single parameter copy stored at
params["shared"]; the scan body closes over it (one copy, applied at every
occurrence — gradients accumulate across invocations via AD).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .blocks import (attn_apply_decode, attn_apply_fullseq, attn_apply_paged,
                     attn_apply_prefill_paged, attn_apply_window_paged,
                     attn_cache_init, attn_init, attn_pages_init, dense_apply,
                     dense_init, mlp_apply, mlp_init, norm_apply, norm_init)
from . import moe as moe_mod
from . import rwkv as rwkv_mod
from . import mamba as mamba_mod
from ..sharding.policy import maybe_shard

ATTN_KINDS = ("attn_mlp", "attn_moe", "local", "shared_attn", "enc_attn_mlp")
AUX_KEYS = ("lb_loss", "z_loss", "dropped_frac")


# --------------------------------------------------------------------------
# per-kind init / apply / cache
# --------------------------------------------------------------------------

def layer_init(kind: str, key, cfg):
    ks = jax.random.split(key, 4)
    if kind in ("attn_mlp", "local", "shared_attn", "enc_attn_mlp"):
        return {"ln1": norm_init(cfg.norm, cfg.d_model), "attn": attn_init(ks[0], cfg),
                "ln2": norm_init(cfg.norm, cfg.d_model), "mlp": mlp_init(ks[1], cfg)}
    if kind == "attn_moe":
        return {"ln1": norm_init(cfg.norm, cfg.d_model), "attn": attn_init(ks[0], cfg),
                "ln2": norm_init(cfg.norm, cfg.d_model), "moe": moe_mod.moe_init(ks[1], cfg)}
    if kind == "dec_attn_mlp":
        return {"ln1": norm_init(cfg.norm, cfg.d_model), "attn": attn_init(ks[0], cfg),
                "ln_x": norm_init(cfg.norm, cfg.d_model), "xattn": attn_init(ks[1], cfg),
                "ln2": norm_init(cfg.norm, cfg.d_model), "mlp": mlp_init(ks[2], cfg)}
    if kind == "rwkv":
        return rwkv_mod.rwkv_init(key, cfg)
    if kind == "mamba":
        return mamba_mod.mamba_init(key, cfg)
    raise ValueError(kind)


def _mask_kind(kind: str, cfg, ctx) -> Tuple[str, int, int]:
    if kind == "enc_attn_mlp":
        return "bidir", 0, 0
    if kind == "local":
        return "sliding", cfg.sliding_window, 0
    if cfg.prefix_lm:
        return "prefix", 0, ctx.get("prefix_len", cfg.n_prefix_tokens)
    return "causal", 0, 0


def layer_apply_full(kind: str, p, x, cfg, ctx):
    """Returns (x, aux, cache_entry)."""
    aux = {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}
    if kind == "rwkv":
        x, st = rwkv_mod.rwkv_block_full(p, x, cfg)
        return x, aux, st
    if kind == "mamba":
        x, st = mamba_mod.mamba_block_full(p, x, cfg)
        return x, aux, st
    if kind == "dec_attn_mlp":
        h, (k, v) = attn_apply_fullseq(p["attn"], norm_apply(p["ln1"], x), cfg, kind="causal")
        x = x + h
        enc = ctx["enc_out"]
        B, F = enc.shape[0], enc.shape[1]
        hd = cfg.hd
        ck = dense_apply(p["xattn"]["wk"], enc).reshape(B, F, cfg.n_kv_heads, hd)
        cv = dense_apply(p["xattn"]["wv"], enc).reshape(B, F, cfg.n_kv_heads, hd)
        h, _ = attn_apply_fullseq(p["xattn"], norm_apply(p["ln_x"], x), cfg, cross_kv=(ck, cv))
        x = x + h
        x = x + mlp_apply(p["mlp"], norm_apply(p["ln2"], x), cfg)
        if not ctx.get("want_cache", True):
            return x, aux, ()
        S = k.shape[1]
        C = ctx.get("cache_len", S)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        if C > S:
            k = jnp.pad(k, ((0, 0), (0, C - S), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, C - S), (0, 0), (0, 0)))
            pos = jnp.concatenate([pos, jnp.full((B, C - S), -1, jnp.int32)], 1)
        cache = {"self": {"k": k.astype(ctx["cache_dtype"]), "v": v.astype(ctx["cache_dtype"]),
                          "pos": pos},
                 "xk": ck.astype(ctx["cache_dtype"]), "xv": cv.astype(ctx["cache_dtype"])}
        return x, aux, cache
    # attention + (mlp|moe)
    mkind, window, prefix_len = _mask_kind(kind, cfg, ctx)
    h, (k, v) = attn_apply_fullseq(p["attn"], norm_apply(p["ln1"], x), cfg,
                                   kind=mkind, window=window, prefix_len=prefix_len)
    x = x + h
    if kind == "attn_moe":
        h, moe_aux = moe_mod.moe_apply(p["moe"], norm_apply(p["ln2"], x), cfg)
        aux = {**aux, **{k2: jnp.asarray(v2, jnp.float32) for k2, v2 in moe_aux.items()}}
    else:
        h = mlp_apply(p["mlp"], norm_apply(p["ln2"], x), cfg)
    x = x + h
    if kind == "enc_attn_mlp" or not ctx.get("want_cache", True):
        return x, aux, ()
    B, S = k.shape[0], k.shape[1]
    if window:
        # ring cache: the entry for position p must sit at slot p % W so the
        # decode path (slot = cur_pos % W) overwrites the oldest entry
        W = window
        if S >= W:
            k, v = k[:, S - W:], v[:, S - W:]
            pos = jnp.broadcast_to(jnp.arange(S - W, S, dtype=jnp.int32), (B, W))
            shift = (S - W) % W
            k, v = jnp.roll(k, shift, axis=1), jnp.roll(v, shift, axis=1)
            pos = jnp.roll(pos, shift, axis=1)
        else:  # prompt shorter than the window: slot p % W == p already
            pad = W - S
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            pos = jnp.concatenate(
                [jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S)),
                 jnp.full((B, pad), -1, jnp.int32)], axis=1)
    else:
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        C = ctx.get("cache_len", S)    # decode headroom (api.prefill max_len)
        if C > S:
            k = jnp.pad(k, ((0, 0), (0, C - S), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, C - S), (0, 0), (0, 0)))
            pos = jnp.concatenate([pos, jnp.full((B, C - S), -1, jnp.int32)], 1)
    cache = {"k": k.astype(ctx["cache_dtype"]), "v": v.astype(ctx["cache_dtype"]), "pos": pos}
    return x, aux, cache


def layer_apply_decode(kind: str, p, x, cfg, cache, ctx):
    """x: (B, 1, D). Returns (x, new_cache)."""
    cur = ctx["cur_pos"]
    if kind == "rwkv":
        return rwkv_mod.rwkv_block_decode(p, x, cfg, cache)
    if kind == "mamba":
        return mamba_mod.mamba_block_decode(p, x, cfg, cache)
    if kind == "dec_attn_mlp":
        h, sc = attn_apply_decode(p["attn"], norm_apply(p["ln1"], x), cfg,
                                  cache["self"], cur_pos=cur,
                                  use_kernel=ctx.get("decode_kernel", False))
        x = x + h
        from .blocks import decode_attention, rope
        B = x.shape[0]
        hd = cfg.hd
        q = dense_apply(p["xattn"]["wq"], norm_apply(p["ln_x"], x)).reshape(B, 1, cfg.n_heads, hd)
        F = cache["xk"].shape[1]
        kpos = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32), (B, F))
        h = decode_attention(q, cache["xk"], cache["xv"], k_pos=kpos, cur_pos=F)
        x = x + dense_apply(p["xattn"]["wo"], h.reshape(B, 1, -1))
        x = x + mlp_apply(p["mlp"], norm_apply(p["ln2"], x), cfg)
        return x, {"self": sc, "xk": cache["xk"], "xv": cache["xv"]}
    mkind, window, _ = _mask_kind(kind, cfg, ctx)
    h, cache = attn_apply_decode(p["attn"], norm_apply(p["ln1"], x), cfg, cache,
                                 cur_pos=cur, window=window,
                                 use_kernel=ctx.get("decode_kernel", False))
    x = x + h
    if kind == "attn_moe":
        h, _ = moe_mod.moe_apply(p["moe"], norm_apply(p["ln2"], x), cfg)
    else:
        h = mlp_apply(p["mlp"], norm_apply(p["ln2"], x), cfg)
    return x + h, cache


def layer_cache_init(kind: str, cfg, batch: int, seq_len: int, dtype):
    if kind == "rwkv":
        return rwkv_mod.rwkv_state_init(cfg, batch, dtype=dtype)
    if kind == "mamba":
        return mamba_mod.mamba_state_init(cfg, batch, dtype=dtype)
    if kind == "dec_attn_mlp":
        hd = cfg.hd
        return {"self": attn_cache_init(cfg, batch, seq_len, dtype=dtype),
                "xk": jnp.zeros((batch, cfg.n_frames, cfg.n_kv_heads, hd), dtype),
                "xv": jnp.zeros((batch, cfg.n_frames, cfg.n_kv_heads, hd), dtype)}
    window = cfg.sliding_window if kind == "local" else 0
    return attn_cache_init(cfg, batch, seq_len, window=window, dtype=dtype)


# --------------------------------------------------------------------------
# stack runner
# --------------------------------------------------------------------------

def _zero_aux():
    return {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}


def _acc_aux(a, b):
    return {k: a[k] + b[k] for k in AUX_KEYS}


def stack_init(key, cfg):
    params: Dict[str, Any] = {}
    n_pos = len(cfg.pattern)
    keys = jax.random.split(key, 4 + n_pos)
    params["head"] = tuple(
        layer_init(k, kk, cfg) for k, kk in
        zip(cfg.head_layers, jax.random.split(keys[0], max(len(cfg.head_layers), 1))))
    params["tail"] = tuple(
        layer_init(k, kk, cfg) for k, kk in
        zip(cfg.tail_layers, jax.random.split(keys[1], max(len(cfg.tail_layers), 1))))
    if any(k == "shared_attn" for k in cfg.pattern):
        params["shared"] = layer_init("shared_attn", keys[2], cfg)
    units = []
    for j, kind in enumerate(cfg.pattern):
        if kind == "shared_attn":
            units.append({})     # params live at params["shared"]
        else:
            per_unit = jax.vmap(lambda kk: layer_init(kind, kk, cfg))(
                jax.random.split(keys[3 + j], cfg.n_units))
            units.append(per_unit)
    params["units"] = tuple(units)
    return params


def stack_apply_full(params, x, cfg, ctx):
    """Returns (x, aux, caches dict)."""
    aux = _zero_aux()
    head_caches = []
    for kind, p in zip(cfg.head_layers, params["head"]):
        x, a, c = layer_apply_full(kind, p, x, cfg, ctx)
        aux = _acc_aux(aux, a)
        head_caches.append(c)

    unit_caches = ()
    if cfg.n_units:
        def body(carry, unit_params):
            x, aux = carry
            caches = []
            for j, kind in enumerate(cfg.pattern):
                p = params.get("shared") if kind == "shared_attn" else unit_params[j]
                x, a, c = layer_apply_full(kind, p, x, cfg, ctx)
                x = maybe_shard(x, "residual")
                aux = _acc_aux(aux, a)
                caches.append(c)
            return (x, aux), tuple(caches)

        # remat menu: a named jax.checkpoint policy beats the boolean
        # flag — "nothing_saveable" recomputes everything (min HBM),
        # "dots_saveable" keeps matmul outputs (cheapest recompute)
        if getattr(cfg, "remat_policy", None):
            from ..core.precision import checkpoint_policy
            body = jax.checkpoint(body,
                                  policy=checkpoint_policy(cfg.remat_policy))
        elif cfg.remat:
            body = jax.checkpoint(body)
        (x, aux), unit_caches = lax.scan(body, (x, aux), params["units"])

    tail_caches = []
    for kind, p in zip(cfg.tail_layers, params["tail"]):
        x, a, c = layer_apply_full(kind, p, x, cfg, ctx)
        aux = _acc_aux(aux, a)
        tail_caches.append(c)
    caches = {"head": tuple(head_caches), "units": unit_caches, "tail": tuple(tail_caches)}
    return x, aux, caches


def stack_apply_decode(params, x, cfg, caches, ctx):
    new_head = []
    for kind, p, c in zip(cfg.head_layers, params["head"], caches["head"]):
        x, c = layer_apply_decode(kind, p, x, cfg, c, ctx)
        new_head.append(c)

    new_units = caches["units"]
    if cfg.n_units:
        def body(x, scan_in):
            dt = x.dtype
            unit_params, unit_caches = scan_in
            new_caches = []
            for j, kind in enumerate(cfg.pattern):
                p = params.get("shared") if kind == "shared_attn" else unit_params[j]
                x, c = layer_apply_decode(kind, p, x, cfg, unit_caches[j], ctx)
                x = x.astype(dt)
                new_caches.append(c)
            return x, tuple(new_caches)

        x, new_units = lax.scan(body, x, (params["units"], caches["units"]))

    new_tail = []
    for kind, p, c in zip(cfg.tail_layers, params["tail"], caches["tail"]):
        x, c = layer_apply_decode(kind, p, x, cfg, c, ctx)
        new_tail.append(c)
    return x, {"head": tuple(new_head), "units": new_units, "tail": tuple(new_tail)}


def stack_cache_init(cfg, batch: int, seq_len: int, dtype=jnp.bfloat16):
    def stacked(kind):
        one = layer_cache_init(kind, cfg, batch, seq_len, dtype)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (cfg.n_units,) + a.shape), one)
    return {
        "head": tuple(layer_cache_init(k, cfg, batch, seq_len, dtype) for k in cfg.head_layers),
        "units": tuple(stacked(k) for k in cfg.pattern),
        "tail": tuple(layer_cache_init(k, cfg, batch, seq_len, dtype) for k in cfg.tail_layers),
    }


# --------------------------------------------------------------------------
# paged stack (continuous-batching decode): the KV state is a pool of
# fixed-size pages per layer, indexed by per-sequence block tables shared
# across all layers. Restricted to global-attention stacks — sliding
# windows, SSM/RWKV state and encoder-decoder caches have no paged form.
# --------------------------------------------------------------------------

PAGED_KINDS = ("attn_mlp", "attn_moe")


def paged_guard(cfg):
    kinds = tuple(cfg.head_layers) + tuple(cfg.pattern) + tuple(cfg.tail_layers)
    bad = sorted({k for k in kinds if k not in PAGED_KINDS})
    if bad:
        raise NotImplementedError(
            f"paged decode supports {PAGED_KINDS} stacks only, got {bad}")
    if cfg.prefix_lm:
        raise NotImplementedError("paged decode does not support prefix_lm")


# device-side names (jax.named_scope, in the HLO's op metadata): a
# profile splits each paged program's device time into push.attention
# (with its paged KV write) and push.mlp per layer


def _ffn_paged(kind, p, x, cfg):
    with jax.named_scope("push.mlp"):
        if kind == "attn_moe":
            h, _ = moe_mod.moe_apply(p["moe"], norm_apply(p["ln2"], x), cfg)
        else:
            h = mlp_apply(p["mlp"], norm_apply(p["ln2"], x), cfg)
    return x + h


def _layer_apply_paged(kind, p, x, cfg, pages, ctx):
    with jax.named_scope("push.attention"):
        h, pages = attn_apply_paged(
            p["attn"], norm_apply(p["ln1"], x), cfg, pages,
            block_tables=ctx["block_tables"], seq_lens=ctx["seq_lens"],
            use_kernel=ctx.get("decode_kernel", True))
    return _ffn_paged(kind, p, x + h, cfg), pages


def _layer_apply_window_paged(kind, p, x, cfg, pages, ctx):
    with jax.named_scope("push.attention"):
        h, pages = attn_apply_window_paged(
            p["attn"], norm_apply(p["ln1"], x), cfg, pages,
            block_tables=ctx["block_tables"], seq_lens=ctx["seq_lens"],
            win_lens=ctx["win_lens"],
            use_kernel=ctx.get("decode_kernel", True))
    return _ffn_paged(kind, p, x + h, cfg), pages


def _layer_apply_prefill_paged(kind, p, x, cfg, pages, ctx):
    with jax.named_scope("push.attention"):
        h, pages = attn_apply_prefill_paged(
            p["attn"], norm_apply(p["ln1"], x), cfg, pages,
            block_table_row=ctx["block_table_row"],
            n_tokens=ctx["n_tokens"])
    return _ffn_paged(kind, p, x + h, cfg), pages


def _stack_apply_paged_common(params, x, cfg, pages, ctx, layer_fn):
    new_head = []
    for kind, p, pg in zip(cfg.head_layers, params["head"], pages["head"]):
        x, pg = layer_fn(kind, p, x, cfg, pg, ctx)
        new_head.append(pg)

    new_units = pages["units"]
    if cfg.n_units:
        def body(x, scan_in):
            dt = x.dtype
            unit_params, unit_pages = scan_in
            new_pages = []
            for j, kind in enumerate(cfg.pattern):
                x, pg = layer_fn(kind, unit_params[j], x, cfg,
                                 unit_pages[j], ctx)
                x = x.astype(dt)
                new_pages.append(pg)
            return x, tuple(new_pages)

        x, new_units = lax.scan(body, x, (params["units"], pages["units"]))

    new_tail = []
    for kind, p, pg in zip(cfg.tail_layers, params["tail"], pages["tail"]):
        x, pg = layer_fn(kind, p, x, cfg, pg, ctx)
        new_tail.append(pg)
    return x, {"head": tuple(new_head), "units": new_units,
               "tail": tuple(new_tail)}


def stack_apply_paged(params, x, cfg, pages, ctx):
    """One decode step over the paged pool. x: (B, 1, D);
    ctx: block_tables (B, n_pmax), seq_lens (B,). Returns (x, pages)."""
    return _stack_apply_paged_common(params, x, cfg, pages, ctx,
                                     _layer_apply_paged)


def stack_apply_window_paged(params, x, cfg, pages, ctx):
    """Speculative-verify step over a drafted window. x: (B, W, D);
    ctx: block_tables (B, n_pmax), seq_lens (B,) (position of window
    token 0, -1 = inactive), win_lens (B,) real window tokens per row.
    Returns (x, pages)."""
    return _stack_apply_paged_common(params, x, cfg, pages, ctx,
                                     _layer_apply_window_paged)


def stack_apply_prefill_paged(params, x, cfg, pages, ctx):
    """Prompt prefill for one sequence into the pool. x: (1, Sp, D);
    ctx: block_table_row (n_pmax,), n_tokens scalar. Returns (x, pages)."""
    return _stack_apply_paged_common(params, x, cfg, pages, ctx,
                                     _layer_apply_prefill_paged)


def stack_paged_init(cfg, num_pages: int, page_size: int, dtype=jnp.bfloat16):
    paged_guard(cfg)

    def stacked():
        one = attn_pages_init(cfg, num_pages, page_size, dtype=dtype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_units,) + a.shape), one)

    return {
        "head": tuple(attn_pages_init(cfg, num_pages, page_size, dtype=dtype)
                      for _ in cfg.head_layers),
        "units": tuple(stacked() for _ in cfg.pattern),
        "tail": tuple(attn_pages_init(cfg, num_pages, page_size, dtype=dtype)
                      for _ in cfg.tail_layers),
    }
