"""Plain reference of qwen1.5-0.5b (HF Qwen/Qwen1.5-0.5B): weights from a
seed, the causal forward pass in straightforward jnp at float32 with
"highest" matmuls, and the BMA heads of an ensemble.

Decoder layer: RMSNorm (eps 1e-6), attention with biased q/k/v
projections, rotary embedding (rotate-half, theta 1e6) on q and k, causal
softmax, output projection; RMSNorm, SwiGLU MLP (silu(x Wg) * (x Wi) Wo).
Final RMSNorm and the tied embedding as LM head. Nothing here imports the
program; ``program_config`` only names the program's config and states
its sizes.

It also counts, from the configuration's sizes alone, the parameters,
the K/V bytes and the FLOPs of prefill, of a decoded token and of the
paged attention kernel that the per-layer readers use.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.core.refops import rope

SEQ_BUCKET = 256      # prompt + served tokens, padded (causal: harmless)
POS_BUCKET = 64       # served positions read, padded


def program_config(spec):
    from repro import configs
    return configs.get(spec["program_config"]).replace(
        d_model=spec["hidden_size"], n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        d_ff=spec["intermediate_size"], vocab_size=spec["vocab_size"],
        n_units=spec["num_hidden_layers"], rope_theta=spec["rope_theta"],
        qkv_bias=True, tie_embeddings=spec["tie_word_embeddings"])


def init_params(key, spec):
    """One particle's weights in the program's tree layout."""
    d, f, v = spec["hidden_size"], spec["intermediate_size"], \
        spec["vocab_size"]
    L = spec["num_hidden_layers"]
    hd = d // spec["num_attention_heads"]
    q_out = spec["num_attention_heads"] * hd
    kv_out = spec["num_key_value_heads"] * hd
    ks = iter(jax.random.split(key, 32))

    def w(shape, fan_in):
        return jax.random.normal(next(ks), shape, jnp.float32) \
            / math.sqrt(fan_in)

    def small(shape):
        return jax.random.normal(next(ks), shape, jnp.float32) * 0.02

    unit = {
        "ln1": {"scale": 1.0 + small((L, d))},
        "attn": {"wq": {"w": w((L, d, q_out), d), "b": small((L, q_out))},
                 "wk": {"w": w((L, d, kv_out), d), "b": small((L, kv_out))},
                 "wv": {"w": w((L, d, kv_out), d), "b": small((L, kv_out))},
                 "wo": {"w": w((L, q_out, d), q_out)}},
        "ln2": {"scale": 1.0 + small((L, d))},
        "mlp": {"wi": {"w": w((L, d, f), d)}, "wg": {"w": w((L, d, f), d)},
                "wo": {"w": w((L, f, d), f)}},
    }
    out = {"embed": small((v, d)),
           "final_norm": {"scale": 1.0 + small((d,))},
           "head": (), "tail": (), "units": (unit,)}
    if not spec["tie_word_embeddings"]:
        out["lm_head"] = {"w": w((d, v), d)}
    return out


def _rms(p, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def _rounded(dtype):
    """Matmul with both operands rounded to ``dtype`` (None: exact)."""
    if dtype is None:
        return jnp.matmul, jnp.einsum

    def r(x):
        return x.astype(dtype).astype(jnp.float32)
    return (lambda a, b: jnp.matmul(r(a), r(b)),
            lambda eq, a, b: jnp.einsum(eq, r(a), r(b)))


def hidden(params, tokens, spec, operand_dtype=None):
    """Final hidden states of one sequence (S,) -> (S, d). With
    ``operand_dtype`` every matmul rounds its operands to that dtype
    first (the correctness control's lower precision)."""
    mm, es = _rounded(operand_dtype)
    d = spec["hidden_size"]
    h, kvh = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = d // h
    eps, theta = spec["rms_norm_eps"], spec["rope_theta"]
    s = tokens.shape[0]
    x = params["embed"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        y = _rms(p["ln1"], x, eps)
        a = p["attn"]
        q = rope((mm(y, a["wq"]["w"]) + a["wq"]["b"]).reshape(s, h, hd),
                 theta)
        k = rope((mm(y, a["wk"]["w"]) + a["wk"]["b"]).reshape(s, kvh, hd),
                 theta)
        v = (mm(y, a["wv"]["w"]) + a["wv"]["b"]).reshape(s, kvh, hd)
        k = jnp.repeat(k, h // kvh, axis=1)
        v = jnp.repeat(v, h // kvh, axis=1)
        sc = es("qhd,khd->hqk", q, k) / math.sqrt(hd)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = es("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
        x = x + mm(o.reshape(s, h * hd), a["wo"]["w"])
        y = _rms(p["ln2"], x, eps)
        m = p["mlp"]
        y = jax.nn.silu(mm(y, m["wg"]["w"])) * mm(y, m["wi"]["w"])
        return x + mm(y, m["wo"]["w"]), None

    x, _ = jax.lax.scan(layer, x, params["units"][0])
    return _rms(params["final_norm"], x, eps)


def _head(params, x, spec, operand_dtype=None):
    w = params["embed"].T if spec["tie_word_embeddings"] \
        else params["lm_head"]["w"]
    return _rounded(operand_dtype)[0](x, w)


def bma_heads_fn(spec, operand_dtype=None):
    """heads(stacked params, prompt, tokens, served=tokens) -> per token
    position: the reference's best log BMA probability and best token,
    the log BMA probability of ``served``, the predictive entropy and the
    mutual information. Position j predicts the token after
    prompt + tokens[:j]. ``operand_dtype`` rounds every matmul's operands
    (the correctness control)."""
    import numpy as np

    @jax.jit
    def run(params, seq, pos, served):
        def member(p):
            h = hidden(p, seq, spec, operand_dtype)[pos]
            return _head(p, h, spec, operand_dtype)             # (n, V)
        logits = jax.vmap(member)(params)                     # (P, n, V)
        logp = jax.nn.log_softmax(logits, -1)
        probs = jnp.exp(logp)
        mean = jnp.mean(probs, 0)
        log_mean = jnp.log(mean + 1e-12)
        ent = -jnp.sum(mean * jnp.log(mean + 1e-12), -1)
        exp_ent = jnp.mean(-jnp.sum(probs * logp, -1), 0)
        served_lp = jnp.take_along_axis(log_mean, served[:, None], -1)[:, 0]
        return (jnp.max(log_mean, -1), jnp.argmax(log_mean, -1), served_lp,
                ent, jnp.maximum(ent - exp_ent, 0.0))

    def heads(params, prompt, tokens, served=None):
        served = tokens if served is None else served
        seq = list(prompt) + list(tokens[:-1])
        n = len(tokens)
        s_pad = -(-len(seq) // SEQ_BUCKET) * SEQ_BUCKET
        n_pad = -(-n // POS_BUCKET) * POS_BUCKET
        seq_a = np.zeros(s_pad, np.int32)
        seq_a[:len(seq)] = seq
        pos = np.full(n_pad, len(prompt) - 1, np.int32)
        pos[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        srv = np.zeros(n_pad, np.int32)
        srv[:n] = served
        return [np.asarray(x)[:n] for x in run(params, seq_a, pos, srv)]

    return heads


# -- counts from shapes ----------------------------------------------------
# Matmul FLOPs count 2 per multiply-add; elementwise work (norms, rotary,
# softmax, activations) is not counted.

def _dims(spec):
    d = spec["hidden_size"]
    h = spec["num_attention_heads"]
    return (d, h, d // h, spec["num_key_value_heads"],
            spec["intermediate_size"], spec["vocab_size"],
            spec["num_hidden_layers"])


def param_count(spec) -> int:
    """Parameters of one particle."""
    d, h, hd, kvh, f, v, n = _dims(spec)
    kv = kvh * hd
    attn = (d * d + d) + 2 * (d * kv + kv) + d * d
    layer = d + attn + d + 3 * d * f
    head = 0 if spec["tie_word_embeddings"] else v * d
    return v * d + n * layer + d + head


def matmul_flops_per_token(spec) -> int:
    """Weight matmuls of one token through every layer (no LM head)."""
    d, h, hd, kvh, f, v, n = _dims(spec)
    return n * 2 * (d * h * hd + 2 * d * kvh * hd + h * hd * d + 3 * d * f)


def head_flops(spec) -> int:
    """The LM head of one token."""
    return 2 * spec["hidden_size"] * spec["vocab_size"]


def attn_flops(spec, ctx_total: int) -> int:
    """Scores and weighted sum over ``ctx_total`` attended positions
    (summed over the queries), every layer."""
    d, h, hd, kvh, f, v, n = _dims(spec)
    return n * 4 * h * hd * ctx_total


def decode_token_flops(spec, ctx: int) -> int:
    """One decoded token that attends to ``ctx`` positions (itself
    included): matmuls, attention and the LM head."""
    return matmul_flops_per_token(spec) + attn_flops(spec, ctx) \
        + head_flops(spec)


def prefill_flops(spec, n_tokens: int) -> int:
    """Causal prefill of ``n_tokens`` real tokens with logits for the last
    one only (padding is not useful work)."""
    causal = n_tokens * (n_tokens + 1) // 2
    return n_tokens * matmul_flops_per_token(spec) \
        + attn_flops(spec, causal) + head_flops(spec)


def kv_bytes_per_token(spec, itemsize: int = 4) -> int:
    """K and V of one token in every layer, one particle."""
    d, h, hd, kvh, f, v, n = _dims(spec)
    return n * 2 * kvh * hd * itemsize


def paged_attn_cost(spec, ctx_total: int, rows: int, itemsize: int = 4):
    """(flops, bytes) the paged decode attention kernel needs, every
    layer, one particle: ``rows`` queries attending to ``ctx_total``
    cached positions in all. Bytes are the K/V of the live positions plus
    each query and its output; pages that hold no live position are not
    counted."""
    d, h, hd, kvh, f, v, n = _dims(spec)
    return attn_flops(spec, ctx_total), \
        ctx_total * kv_bytes_per_token(spec, itemsize) \
        + n * rows * 2 * h * hd * itemsize
