"""Plain reference of vit-mnist (Push App. C.1): weights from a seed, the
forward pass and loss in straightforward jnp at float32 with "highest"
matmuls, and Adam.

It follows the program's model, which departs from a textbook ViT in
three places, all noted in the configuration file: rotary position
embedding on q and k in every encoder layer (theta 10,000, rotate-half)
on top of the learned position table, tanh-approximated GELU, LayerNorm
epsilon 1e-6. Nothing here imports the program; ``program_config`` only
names the program's config and states its sizes.

It also counts, from the configuration's sizes alone, the parameters and
the FLOPs of a training step that the per-layer readers use.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.core.refops import rope

HIGHEST = "highest"


def program_config(spec):
    """The program's ModelConfig for these sizes."""
    from repro import configs
    return configs.get(spec["program_config"]).replace(
        d_model=spec["hidden_size"], n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_attention_heads"],
        d_ff=spec["intermediate_size"], n_units=spec["num_hidden_layers"],
        vocab_size=spec["num_labels"], rope_theta=spec["rope_theta"])


def init_params(key, spec):
    """One particle's weights, in the program's tree layout: dense weights
    N(0, 1/d_in), biases and embeddings N(0, 0.02^2), norm scales
    1 + N(0, 0.02^2)."""
    d, f = spec["hidden_size"], spec["intermediate_size"]
    L = spec["num_hidden_layers"]
    g = spec["image_size"] // spec["patch_size"]
    pin = spec["patch_size"] ** 2 * spec["num_channels"]
    ks = iter(jax.random.split(key, 32))

    def w(shape, fan_in):
        return jax.random.normal(next(ks), shape, jnp.float32) \
            / math.sqrt(fan_in)

    def small(shape):
        return jax.random.normal(next(ks), shape, jnp.float32) * 0.02

    def scale(shape):
        return 1.0 + small(shape)

    return {
        "patch": {"w": w((pin, d), pin)},
        "cls": small((1, 1, d)),
        "pos": small((1, g * g + 1, d)),
        "units": {
            "ln1": {"scale": scale((L, d)), "bias": small((L, d))},
            "attn": {"wq": {"w": w((L, d, d), d)},
                     "wk": {"w": w((L, d, d), d)},
                     "wv": {"w": w((L, d, d), d)},
                     "wo": {"w": w((L, d, d), d)}},
            "ln2": {"scale": scale((L, d)), "bias": small((L, d))},
            "mlp": {"w1": {"w": w((L, d, f), d), "b": small((L, f))},
                    "w2": {"w": w((L, f, d), f), "b": small((L, d))}},
        },
        "final_norm": {"scale": scale((d,)), "bias": small((d,))},
        "head": {"w": w((d, spec["num_labels"]), d)},
    }


def _layernorm(p, x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def logits(params, images, spec):
    b = images.shape[0]
    ps, d = spec["patch_size"], spec["hidden_size"]
    g = spec["image_size"] // ps
    h = spec["num_attention_heads"]
    hd = d // h
    eps = spec["layer_norm_eps"]
    x = images.reshape(b, g, ps, g, ps).transpose(0, 1, 3, 2, 4)
    x = x.reshape(b, g * g, ps * ps) @ params["patch"]["w"]
    x = jnp.concatenate([jnp.broadcast_to(params["cls"], (b, 1, d)), x], 1)
    x = x + params["pos"]
    s = x.shape[1]

    def layer(x, p):
        y = _layernorm(p["ln1"], x, eps)
        q = rope((y @ p["attn"]["wq"]["w"]).reshape(b, s, h, hd),
                 spec["rope_theta"])
        k = rope((y @ p["attn"]["wk"]["w"]).reshape(b, s, h, hd),
                 spec["rope_theta"])
        v = (y @ p["attn"]["wv"]["w"]).reshape(b, s, h, hd)
        att = jax.nn.softmax(jnp.einsum("bqhd,bkhd->bhqk", q, k)
                             / math.sqrt(hd), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, d)
        x = x + o @ p["attn"]["wo"]["w"]
        y = _layernorm(p["ln2"], x, eps)
        y = _gelu_tanh(y @ p["mlp"]["w1"]["w"] + p["mlp"]["w1"]["b"])
        return x + y @ p["mlp"]["w2"]["w"] + p["mlp"]["w2"]["b"], None

    x, _ = jax.lax.scan(layer, x, params["units"])
    x = _layernorm(params["final_norm"], x, eps)
    return x[:, 0] @ params["head"]["w"]


def loss(params, batch, spec):
    """Mean cross-entropy of one batch."""
    z = logits(params, batch["images"], spec)
    lse = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, batch["labels"][:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def adam_update(params, grads, m, v, step, opt):
    """One Adam step (step counts from 1)."""
    b1, b2, lr, eps = opt["b1"], opt["b2"], opt["lr"], opt["eps"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + eps),
        params, m, v)
    return params, m, v


# -- counts from shapes ----------------------------------------------------
# Matmul FLOPs count 2 per multiply-add; a training step is forward +
# backward, three forwards (recomputation is not counted); elementwise
# work (norms, activations, softmax, the optimizer) is not counted.

def _dims(spec):
    g = spec["image_size"] // spec["patch_size"]
    return (spec["hidden_size"], spec["intermediate_size"], g * g,
            spec["patch_size"] ** 2 * spec["num_channels"])


def param_count(spec) -> int:
    """Parameters of one particle."""
    d, f, n_patch, patch_in = _dims(spec)
    layer = 2 * d + 4 * d * d + 2 * d + (d * f + f) + (f * d + d)
    return (patch_in * d + d + (n_patch + 1) * d
            + spec["num_hidden_layers"] * layer + 2 * d
            + d * spec["num_labels"])


def forward_flops(spec, batch: int) -> int:
    """One forward pass of ``batch`` images."""
    d, f, n_patch, patch_in = _dims(spec)
    s = n_patch + 1
    rows = batch * s
    per_layer = (2 * rows * d * d * 4            # q, k, v, o
                 + 2 * 2 * batch * s * s * d     # scores and weighted sum
                 + 2 * rows * d * f * 2)         # MLP in and out
    return (2 * batch * n_patch * patch_in * d
            + spec["num_hidden_layers"] * per_layer
            + 2 * batch * d * spec["num_labels"])


def train_flops(spec, batch: int) -> int:
    """One training step (forward + backward) of ``batch`` images."""
    return 3 * forward_flops(spec, batch)
