"""Plain reference of a Llama-architecture decoder (SmolLM, HF ``LlamaForCausalLM``).

Written from the published architecture in straightforward ``jax.numpy`` and
float32: RMSNorm before attention and MLP, grouped-query attention with
rotary position embeddings (half-split rotation, ``rope_theta``), a causal
softmax over all earlier positions, a SwiGLU MLP, a final RMSNorm and the
output head tied to the token embedding.  It imports nothing of the system
under test.

The benchmark makes the weights here, from the seed, for the system and for
this reference alike.  Parameter layout (a pytree; the decoder layers are
stacked on a leading axis of length ``num_hidden_layers``):

  embed      {tok (vocab, d)}
  blocks     [ {pre_norm {scale (L, d)},
                mixer {wq (L, d, h*hd), wk (L, d, kv*hd), wv (L, d, kv*hd),
                       wo (L, h*hd, d)},
                post_norm {scale (L, d)},
                mlp {w_gate (L, d, f), w_up (L, d, f), w_down (L, f, d)}} ]
  final_norm {scale (d,)}
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
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d, h, kv, d // h, cfg["intermediate_size"], cfg["vocab_size"]


def _init(key, cfg, dtype):
    d, h, kv, hd, f, vocab = _sizes(cfg)
    n = cfg["num_hidden_layers"]
    std = cfg["initializer_range"]
    keys = iter(jax.random.split(key, 8))

    def normal(shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dtype)

    return {
        "embed": {"tok": normal((vocab, d))},
        "blocks": [{
            "pre_norm": {"scale": jnp.ones((n, d), dtype)},
            "mixer": {"wq": normal((n, d, h * hd)),
                      "wk": normal((n, d, kv * hd)),
                      "wv": normal((n, d, kv * hd)),
                      "wo": normal((n, h * hd, d))},
            "post_norm": {"scale": jnp.ones((n, d), dtype)},
            "mlp": {"w_gate": normal((n, d, f)), "w_up": normal((n, d, f)),
                    "w_down": normal((n, f, d))},
        }],
        "final_norm": {"scale": jnp.ones((d,), dtype)},
    }


def init_params(cfg, seed: int, dtype=jnp.float32):
    """The benchmark's weights, made on the device in one jitted call."""
    return jax.jit(lambda k: _init(k, cfg, dtype))(jax_key(seed))


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _rope(x, theta):
    """x: (B, S, heads, hd); rotate the two halves of each head."""
    s, hd = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv_freq[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(params, tokens, cfg):
    d, h, kv, hd, f, vocab = _sizes(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    b, s = tokens.shape
    x = params["embed"]["tok"][tokens]
    causal = np.tril(np.ones((s, s), bool))

    def layer(x, lyr):
        y = _rms_norm(x, lyr["pre_norm"]["scale"], eps)
        q = (y @ lyr["mixer"]["wq"]).reshape(b, s, h, hd)
        k = (y @ lyr["mixer"]["wk"]).reshape(b, s, kv, hd)
        v = (y @ lyr["mixer"]["wv"]).reshape(b, s, kv, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        # query head j reads key/value head j // (h // kv)
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        scores = jnp.where(causal, scores, -jnp.inf)
        att = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, h * hd)
        x = x + o @ lyr["mixer"]["wo"]
        y = _rms_norm(x, lyr["post_norm"]["scale"], eps)
        g = y @ lyr["mlp"]["w_gate"]
        x = x + (jax.nn.silu(g) * (y @ lyr["mlp"]["w_up"])) \
            @ lyr["mlp"]["w_down"]
        return x, None

    # the layers are stacked on a leading axis: one after another
    x, _ = jax.lax.scan(layer, x, params["blocks"][0])
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
    return x @ params["embed"]["tok"].T


def loss(params, batch, cfg):
    """Mean next-token cross-entropy of ``{"tokens", "labels"}``."""
    z = logits(params, batch["tokens"], cfg)
    logp = jax.nn.log_softmax(z, axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, batch["labels"][..., None], axis=-1))


def preconditioned(path: tuple) -> bool:
    """Which weights SOAP preconditions: the attention and MLP matrices of
    the decoder layers.  The embedding (and so the tied head) and the norm
    scales take AdamW."""
    return path[0] == "blocks" and path[-2] in ("mixer", "mlp")


def flops_per_sample(cfg, traffic) -> float:
    """Forward and backward FLOPs of one token (PaLM appendix B convention):

      6 N + 12 L S d_attn

    with N the parameters of every matrix product a token passes through:
    per layer 2 d (h hd) + 2 d (kv hd) + 3 d f, plus the tied head d V (the
    embedding gather is not a product), S the sequence length, and
    d_attn = h hd; attention is counted over all S keys.
    """
    d, h, kv, hd, f, vocab = _sizes(cfg)
    n_layers = cfg["num_hidden_layers"]
    per_layer = 2 * d * h * hd + 2 * d * kv * hd + 3 * d * f
    n = n_layers * per_layer + d * vocab
    return 6.0 * n + 12.0 * n_layers * traffic["seq_len"] * h * hd


def samples_per_step(traffic) -> int:
    return traffic["batch_size"] * traffic["seq_len"]
