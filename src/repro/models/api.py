"""Model facade: init / loss / prefill / decode for every architecture family.

Batch formats (all jnp arrays):
  LM (dense/moe/ssm/hybrid):  {"tokens": (B,S) i32, "labels": (B,S) i32}
  audio (whisper):            + {"frames": (B, n_frames, D) f32}   [conv stub]
  vlm (paligemma):            + {"patches": (B, n_prefix, D) f32}  [SigLIP stub]
  vision (vit):               {"images": (B,28,28,1), "labels": (B,) i32}
  pde (unet):                 {"u0": (B,L,1), "u1": (B,L,1)}

Losses: token cross-entropy (labels < 0 masked), class CE, MSE. MoE aux
losses are folded in with cfg.router_aux_coef. The LM head is computed in
sequence chunks under jax.checkpoint so (B, S, vocab) logits are never
materialized for the full sequence (vocab up to 262k).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from .blocks import dense_apply, dense_init, norm_apply, norm_init
from .transformer import (paged_guard, stack_apply_decode, stack_apply_full,
                          stack_apply_paged, stack_apply_prefill_paged,
                          stack_apply_window_paged, stack_cache_init,
                          stack_init, stack_paged_init)
from . import vit as vit_mod
from . import unet1d as unet_mod
from ..sharding.policy import maybe_shard

LOSS_CHUNK = 512


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_params(key, cfg):
    if cfg.family == "vision":
        return vit_mod.vit_init(key, cfg)
    if cfg.family == "pde":
        return unet_mod.unet_init(key, cfg)
    ks = jax.random.split(key, 5)
    params = {
        "embed": jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                   jnp.float32) * 0.02,
        "final_norm": norm_init(cfg.norm, cfg.d_model),
        **stack_init(ks[1], cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[2], cfg.d_model, cfg.vocab_size)
    if cfg.is_encoder_decoder:
        enc_cfg = cfg.replace(pattern=("enc_attn_mlp",), n_units=cfg.n_encoder_layers,
                              head_layers=(), tail_layers=())
        params["encoder"] = {**stack_init(ks[3], enc_cfg),
                             "final_norm": norm_init(cfg.norm, cfg.d_model)}
    return params


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def param_footprint(cfg, precision=None) -> int:
    """Per-particle parameter bytes under a precision policy, from
    ``jax.eval_shape`` (no FLOPs, no memory): float leaves count at the
    policy's master itemsize. The estimator ``Placement.auto`` and
    bench_precision size the model axis / HBM headline with."""
    from ..core.precision import tree_bytes
    shapes = jax.eval_shape(partial(init_params, cfg=cfg),
                            jax.random.PRNGKey(0))
    return tree_bytes(shapes, precision)


def _cache_dtype(cfg):
    return jnp.bfloat16 if jnp.dtype(cfg.dtype) == jnp.bfloat16 else jnp.float32


def _sinusoid(S: int, D: int, dtype):
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    dim = jnp.arange(D // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10_000.0 ** (2 * dim / D))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)[None]


def _embed(params, tokens, cfg, dtype):
    x = params["embed"].astype(dtype)[tokens]
    return x


def _lm_logits(params, x, cfg):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]["w"]
    return x @ w.astype(x.dtype)


def _encode(params, frames, cfg):
    enc_cfg = cfg.replace(pattern=("enc_attn_mlp",), n_units=cfg.n_encoder_layers,
                          head_layers=(), tail_layers=())
    x = frames + _sinusoid(frames.shape[1], cfg.d_model, frames.dtype)
    ctx = {"cache_dtype": jnp.bfloat16}
    x, _, _ = stack_apply_full(params["encoder"], x, enc_cfg, ctx)
    return norm_apply(params["encoder"]["final_norm"], x)


def _backbone_inputs(params, batch, cfg, dtype):
    """Returns (x, ctx, n_text_positions_offset)."""
    ctx: Dict[str, Any] = {"cache_dtype": _cache_dtype(cfg)}
    tokens = batch["tokens"]
    x = maybe_shard(_embed(params, tokens, cfg, dtype), "residual")
    offset = 0
    if cfg.family == "audio":
        ctx["enc_out"] = _encode(params, batch["frames"].astype(dtype), cfg)
        x = x + _sinusoid(x.shape[1], cfg.d_model, dtype)
    elif cfg.family == "vlm":
        x = jnp.concatenate([batch["patches"].astype(dtype), x], axis=1)
        ctx["prefix_len"] = cfg.n_prefix_tokens
        offset = cfg.n_prefix_tokens
    return x, ctx, offset


# --------------------------------------------------------------------------
# training forward/loss
# --------------------------------------------------------------------------

def _chunked_ce(params, x, labels, cfg):
    """Cross-entropy over sequence chunks; never a full (B,S,V) tensor."""
    B, S, D = x.shape
    C = min(LOSS_CHUNK, S)
    n = -(-S // C)
    pad = n * C - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    xc = x.reshape(B, n, C, D).swapaxes(0, 1)
    lc = labels.reshape(B, n, C).swapaxes(0, 1)

    @jax.checkpoint
    def one(xi, li):
        logits = _lm_logits(params, xi, cfg).astype(jnp.float32)
        logits = maybe_shard(logits, "logits")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(li, 0)[..., None],
                                   axis=-1)[..., 0]
        mask = (li >= 0).astype(jnp.float32)
        return jnp.sum((lse - gold) * mask), jnp.sum(mask)

    def body(acc, inp):
        l, m = one(*inp)
        return (acc[0] + l, acc[1] + m), None

    (tot, cnt), _ = lax.scan(body, (jnp.zeros(()), jnp.zeros(())), (xc, lc))
    return tot / jnp.maximum(cnt, 1.0)


def forward(params, batch, cfg):
    """Training-style full forward. Returns (per-task output, aux)."""
    if cfg.family == "vision":
        return vit_mod.vit_apply(params, batch["images"], cfg), {}
    if cfg.family == "pde":
        return unet_mod.unet_apply(params, batch["u0"], cfg), {}
    dtype = jnp.dtype(cfg.dtype)
    x, ctx, offset = _backbone_inputs(params, batch, cfg, dtype)
    ctx["want_cache"] = False
    x, aux, _ = stack_apply_full(params, x, cfg, ctx)
    x = norm_apply(params["final_norm"], x)
    if offset:
        x = x[:, offset:]
    return x, aux


def loss_fn(params, batch, cfg):
    """Returns (loss, metrics)."""
    out, aux = forward(params, batch, cfg)
    if cfg.family == "vision":
        logits = out.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, batch["labels"][:, None], axis=-1)[:, 0]
        loss = jnp.mean(lse - gold)
        acc = jnp.mean((jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32))
        return loss, {"loss": loss, "acc": acc}
    if cfg.family == "pde":
        loss = jnp.mean(jnp.square(out - batch["u1"]))
        return loss, {"loss": loss}
    loss = _chunked_ce(params, out, batch["labels"], cfg)
    metrics = {"loss": loss}
    if cfg.n_experts:
        loss = loss + cfg.router_aux_coef * (aux["lb_loss"] + aux["z_loss"])
        metrics.update({k: aux[k] for k in ("lb_loss", "z_loss", "dropped_frac")})
    return loss, metrics


# --------------------------------------------------------------------------
# serving: prefill + decode
# --------------------------------------------------------------------------

def prefill(params, batch, cfg, max_len=None):
    """Full-context pass. Returns (last-token logits, caches).

    max_len: allocate decode headroom in the returned caches (defaults to
    the prompt length -- pass prompt_len + decode_budget for generation).
    """
    dtype = jnp.dtype(cfg.dtype)
    x, ctx, _ = _backbone_inputs(params, batch, cfg, dtype)
    if max_len is not None:
        ctx["cache_len"] = max_len
    x, _, caches = stack_apply_full(params, x, cfg, ctx)
    x = norm_apply(params["final_norm"], x)
    logits = _lm_logits(params, x[:, -1:], cfg)
    return logits[:, 0], caches


def decode_step(params, token, caches, cur_pos, cfg, *,
                decode_kernel: bool = False):
    """token: (B,) i32; cur_pos: scalar i32. Returns (logits (B,V), caches).

    ``decode_kernel=True`` runs cache attention through the Pallas decode
    kernel (repro.serve's LM path sets this; platform-gated interpret)."""
    dtype = jnp.dtype(cfg.dtype)
    x = _embed(params, token[:, None], cfg, dtype)
    ctx: Dict[str, Any] = {"cache_dtype": _cache_dtype(cfg), "cur_pos": cur_pos,
                           "decode_kernel": decode_kernel}
    if cfg.family == "audio":
        D = cfg.d_model
        dim = jnp.arange(D // 2, dtype=jnp.float32)
        ang = jnp.asarray(cur_pos, jnp.float32) / (10_000.0 ** (2 * dim / D))
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])[None, None].astype(dtype)
        x = x + pe
    x, caches = stack_apply_decode(params, x, cfg, caches, ctx)
    x = norm_apply(params["final_norm"], x)
    logits = _lm_logits(params, x, cfg)
    return logits[:, 0], caches


def init_cache(cfg, batch: int, seq_len: int, dtype=None):
    return stack_cache_init(cfg, batch, seq_len, dtype or _cache_dtype(cfg))


# --------------------------------------------------------------------------
# serving: paged continuous-batching decode (repro.serve.DecodeScheduler)
# --------------------------------------------------------------------------

def paged_cache_init(cfg, *, num_pages: int, page_size: int, dtype=None):
    """The per-particle KV page pool: one (num_pages, page_size, KVH, hd)
    k/v pair per attention layer. Block tables are per-sequence and live
    with the scheduler, not here."""
    return stack_paged_init(cfg, num_pages, page_size,
                            dtype or _cache_dtype(cfg))


def decode_step_paged(params, tokens, pages, block_tables, seq_lens, cfg, *,
                      decode_kernel: bool = True):
    """One continuous-batching decode step.

    tokens: (B,) i32 (garbage ok on inactive rows); block_tables:
    (B, n_pmax) i32; seq_lens: (B,) i32 absolute position of each token
    (-1 = inactive row: no pool writes, logits garbage — mask downstream).
    Returns (logits (B, V), pages)."""
    paged_guard(cfg)
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("push.embed"):
        x = _embed(params, jnp.maximum(tokens, 0)[:, None], cfg, dtype)
    ctx: Dict[str, Any] = {"cache_dtype": _cache_dtype(cfg),
                           "block_tables": block_tables,
                           "seq_lens": seq_lens,
                           "decode_kernel": decode_kernel}
    x, pages = stack_apply_paged(params, x, cfg, pages, ctx)
    with jax.named_scope("push.lm_head"):
        x = norm_apply(params["final_norm"], x)
        logits = _lm_logits(params, x, cfg)
    return logits[:, 0], pages


def decode_window_paged(params, tokens, pages, block_tables, seq_lens,
                        win_lens, cfg, *, decode_kernel: bool = True):
    """Speculative verify: score a W-token drafted window in one pass.

    tokens: (B, W) i32 — token w of row b sits at absolute position
    ``seq_lens[b] + w`` (window token 0 is the last committed token, the
    rest are drafts); win_lens: (B,) i32 real window tokens per row
    (positions past win_lens are padding: not written to the pool, logits
    garbage — mask downstream); seq_lens: (B,) i32 (-1 = inactive row).
    Returns (logits (B, W, V), pages); logits[:, w] predicts the token
    AFTER window position w, so accepted drafts need no re-scoring."""
    paged_guard(cfg)
    dtype = jnp.dtype(cfg.dtype)
    x = _embed(params, jnp.maximum(tokens, 0), cfg, dtype)
    ctx: Dict[str, Any] = {"cache_dtype": _cache_dtype(cfg),
                           "block_tables": block_tables,
                           "seq_lens": seq_lens,
                           "win_lens": win_lens,
                           "decode_kernel": decode_kernel}
    x, pages = stack_apply_window_paged(params, x, cfg, pages, ctx)
    x = norm_apply(params["final_norm"], x)
    logits = _lm_logits(params, x, cfg)
    return logits, pages


def prefill_paged(params, tokens, pages, block_table_row, n_tokens, cfg):
    """Prompt prefill for ONE sequence into the page pool.

    tokens: (1, Sp) i32 padded to a shape bucket; block_table_row:
    (n_pmax,) i32; n_tokens: traced scalar count of real tokens.
    Returns (last-real-token logits (1, V), pages)."""
    paged_guard(cfg)
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("push.embed"):
        x = _embed(params, tokens, cfg, dtype)
    ctx: Dict[str, Any] = {"cache_dtype": _cache_dtype(cfg),
                           "block_table_row": block_table_row,
                           "n_tokens": n_tokens}
    x, pages = stack_apply_prefill_paged(params, x, cfg, pages, ctx)
    with jax.named_scope("push.lm_head"):
        x = norm_apply(params["final_norm"], x)
        last = lax.dynamic_slice_in_dim(x, jnp.maximum(n_tokens - 1, 0), 1,
                                        axis=1)
        logits = _lm_logits(params, last, cfg)
    return logits[:, 0], pages
