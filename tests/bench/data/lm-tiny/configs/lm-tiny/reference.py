"""Plain causal LM of the lm-tiny configuration, from the equations alone.

``num_hidden_layers`` pre-norm decoder blocks, then a final RMSNorm and an
untied unembedding to the vocabulary:

    h  = x + Attn(RMSNorm(x))
    x' = h + SwiGLU(RMSNorm(h))
    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g
    Attn(x)    = W_o concat_heads softmax(q k^T / sqrt(d_head) + causal) v,
                 q = RoPE(W_q x), k = RoPE(W_k x), v = W_v x, each key and
                 value head shared by num_attention_heads /
                 num_key_value_heads query heads (GQA)
    SwiGLU(x)  = W_o (silu(W_g x) * W_i x)

and the loss is the mean next-token cross-entropy over every position of
the batch. Everything is computed in the weights' dtype. Departures from
the usual statement, each a convention of the weights' layout or of the
traffic that the program shares:

* the RMSNorm gain is stored as g - 1 (zeros at the start), so the norm
  multiplies by 1 + the stored value;
* RoPE rotates dimension i of a head with dimension i + d_head / 2 (the two
  halves), not adjacent pairs, at angle position * theta^(-2i / d_head);
* a window's positions start at 0, and attention runs across the
  end-of-document ids inside a window: there is no document mask;
* the blocks' weights are stacked on a leading layer axis.

It imports nothing of the program. ``init_weights`` makes the run's weights
(normal with variance 1 / fan-in for projections, 0.02 for the embedding,
zero norm gains) in the tree layout the program's model takes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init_weights(cfg: dict, key: jax.Array) -> dict:
    d, dh, ff = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    ks = iter(jax.random.split(key, 9))

    def dense(n_in, n_out):
        w = jax.random.normal(next(ks), (n, n_in, n_out), jnp.float32)
        return {"w": w * n_in ** -0.5}

    layers = {
        "ln1": jnp.zeros((n, d), jnp.float32),
        "ln2": jnp.zeros((n, d), jnp.float32),
        "attn": {"wq": dense(d, h * dh), "wk": dense(d, kv * dh),
                 "wv": dense(d, kv * dh), "wo": dense(h * dh, d)},
        "mlp": {"wi": dense(d, ff), "wg": dense(d, ff), "wo": dense(ff, d)},
    }
    table = 0.02 * jax.random.normal(next(ks), (vocab, d), jnp.float32)
    unembed = jax.random.normal(next(ks), (d, vocab), jnp.float32) * d ** -0.5
    return {"embed": {"table": table}, "ln_f": jnp.zeros((d,), jnp.float32),
            "layers": layers, "unembed": {"w": unembed}}


def _rms_norm(x, gain, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * (1 + gain)


def _rope(x, theta):
    """``x [B, T, heads, d_head]`` rotated by position."""
    t, dh = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(cfg, p, x):
    b, t, _ = x.shape
    dh = cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = _rope((x @ p["wq"]["w"]).reshape(b, t, h, dh), cfg["rope_theta"])
    k = _rope((x @ p["wk"]["w"]).reshape(b, t, kv, dh), cfg["rope_theta"])
    v = (x @ p["wv"]["w"]).reshape(b, t, kv, dh)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.asarray(dh, x.dtype))
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(b, t, h * dh) @ p["wo"]["w"]


def _swiglu(p, x):
    return (jax.nn.silu(x @ p["wg"]["w"]) * (x @ p["wi"]["w"])) @ p["wo"]["w"]


def loss(cfg: dict, p: dict, batch: dict) -> jax.Array:
    """Mean next-token cross-entropy of ``batch["tokens"] [B, T]`` against
    ``batch["targets"] [B, T]``."""
    eps = cfg["rms_norm_eps"]
    x = p["embed"]["table"][batch["tokens"]]
    for i in range(cfg["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[i], p["layers"])
        x = x + _attention(cfg, lp["attn"], _rms_norm(x, lp["ln1"], eps))
        x = x + _swiglu(lp["mlp"], _rms_norm(x, lp["ln2"], eps))
    logits = _rms_norm(x, p["ln_f"], eps) @ p["unembed"]["w"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1)
    return jnp.mean(nll)
