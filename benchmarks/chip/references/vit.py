"""Plain reference of a pre-LN Vision Transformer classifier (ViT / DeiT).

Written from the published description (Dosovitskiy et al., arXiv:2010.11929;
DeiT, arXiv:2012.12877) in straightforward ``jax.numpy`` and float32, with no
kernels and no batching tricks.  It imports nothing of the system under test.
Departures from the published model are those the configuration file states:
no bias on the fused QKV projection or on the attention output projection,
and the tanh form of GELU.

The benchmark makes the weights here, from the seed, for the system and for
this reference alike.  Parameter layout (a pytree):

  patch_embed (p*p*c, d)   rows ordered (row in patch, column in patch, channel)
  pos_embed   (1 + n_patches, d), position 0 is the class token's
  cls         (1, 1, d)
  blocks      list of {ln1_scale, ln1_bias, wqkv (d, 3d), wo (d, d),
              ln2_scale, ln2_bias, w1 (d, f), b1, w2 (f, d), b2}
  final_ln_scale, final_ln_bias, head {w (d, classes), b}
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int):
    """A JAX key from any whole-number seed (``jax.random.key`` keeps only
    the low 32 bits of a large seed)."""
    return jax.random.key(
        int(np.random.SeedSequence(int(seed)).generate_state(1)[0]))


def _sizes(cfg):
    d = cfg["hidden_size"]
    p, c = cfg["patch_size"], cfg["num_channels"]
    n_patches = (cfg["image_size"] // p) ** 2
    return d, cfg["intermediate_size"], p * p * c, n_patches


def _init(key, cfg, dtype):
    d, f, d_patch, n_patches = _sizes(cfg)
    keys = iter(jax.random.split(key, 4 + 4 * cfg["num_hidden_layers"]))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dtype)

    def ones(n):
        return jnp.ones((n,), dtype)

    def zeros(n):
        return jnp.zeros((n,), dtype)

    params = {
        "patch_embed": normal((d_patch, d), d_patch ** -0.5),
        "pos_embed": normal((n_patches + 1, d), 0.02),
        "cls": normal((1, 1, d), 0.02),
        "blocks": [],
        "final_ln_scale": ones(d),
        "final_ln_bias": zeros(d),
    }
    for _ in range(cfg["num_hidden_layers"]):
        params["blocks"].append({
            "ln1_scale": ones(d), "ln1_bias": zeros(d),
            "wqkv": normal((d, 3 * d), d ** -0.5),
            "wo": normal((d, d), d ** -0.5),
            "ln2_scale": ones(d), "ln2_bias": zeros(d),
            "w1": normal((d, f), d ** -0.5), "b1": zeros(f),
            "w2": normal((f, d), f ** -0.5), "b2": zeros(d),
        })
    params["head"] = {"w": normal((d, cfg["num_labels"]), d ** -0.5),
                      "b": zeros(cfg["num_labels"])}
    return params


def init_params(cfg, seed: int, dtype=jnp.float32):
    """The benchmark's weights, made on the device in one jitted call."""
    return jax.jit(lambda k: _init(k, cfg, dtype))(jax_key(seed))


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def logits(params, images, cfg):
    p = cfg["patch_size"]
    heads = cfg["num_attention_heads"]
    eps = cfg["layer_norm_eps"]
    b, h, w, c = images.shape
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = images.astype(jnp.float32).reshape(b, h // p, p, w // p, p, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (h // p) * (w // p),
                                              p * p * c)
    x = x @ params["patch_embed"]
    d = x.shape[-1]
    cls = jnp.broadcast_to(params["cls"].reshape(1, 1, d), (b, 1, d))
    x = jnp.concatenate([cls, x], axis=1) + params["pos_embed"][None]
    t, hd = x.shape[1], d // heads

    def block(x, blk):
        y = _layer_norm(x, blk["ln1_scale"], blk["ln1_bias"], eps)
        q, k, v = jnp.split(y @ blk["wqkv"], 3, axis=-1)
        q, k, v = (a.reshape(b, t, heads, hd) for a in (q, k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        att = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, t, d)
        x = x + o @ blk["wo"]
        y = _layer_norm(x, blk["ln2_scale"], blk["ln2_bias"], eps)
        x = x + _gelu_tanh(y @ blk["w1"] + blk["b1"]) @ blk["w2"] + blk["b2"]
        return x, None

    # one block after another; stacking them lets one scan body serve all
    blocks = jax.tree.map(lambda *a: jnp.stack(a), *params["blocks"])
    x, _ = jax.lax.scan(block, x, blocks)
    x = _layer_norm(x, params["final_ln_scale"], params["final_ln_bias"], eps)
    return x[:, 0] @ params["head"]["w"] + params["head"]["b"]


def loss(params, batch, cfg):
    """Mean cross-entropy of a batch ``{"x": images, "y": labels}``."""
    z = logits(params, batch["x"], cfg)
    logp = jax.nn.log_softmax(z, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=-1))


def preconditioned(path: tuple) -> bool:
    """Which weights SOAP preconditions: the hidden matrices of the blocks
    (QKV, attention output, both MLP matrices).  Patch and position
    embeddings, the class token, norms, biases and the head take AdamW."""
    return path[0] == "blocks" and path[-1] in ("wqkv", "wo", "w1", "w2")


def flops_per_sample(cfg, traffic) -> float:
    """Forward and backward FLOPs of one image (3x the forward's matrix
    products; norms, softmax and elementwise work are not counted).

    forward = L * [2 T (3 d^2 + d^2 + 2 d f) + 4 T^2 d]   blocks
            + 2 P (p^2 c) d                              patch embedding
            + 2 d classes                                 head, class token
    with T = P + 1 tokens; attention is counted over all T keys.
    """
    del traffic
    d, f, d_patch, n_patches = _sizes(cfg)
    t = n_patches + 1
    blocks = cfg["num_hidden_layers"] * (
        2 * t * (4 * d * d + 2 * d * f) + 4 * t * t * d)
    fwd = blocks + 2 * n_patches * d_patch * d + 2 * d * cfg["num_labels"]
    return 3.0 * fwd


def samples_per_step(traffic) -> int:
    return traffic["batch_size"]
