"""Plain float32 dense decoder: the block equations, written out.

Pre-norm decoder layers ``x += attn(LN1(x)); x += mlp(LN2(x))`` with
LayerNorm (scale and bias), causal multi-head attention with rotary
embeddings over the whole head (NeoX halves), a GELU (tanh) or SwiGLU MLP
without biases, a final LayerNorm, an output head (the embedding,
transposed, when tied) and the mean token cross-entropy. Every matrix
product runs at ``Precision.HIGHEST``, so float32 means float32 on a TPU.
No chunks, offload, sharding, rematerialisation or kernels.

Parameters are dicts keyed by the canonical leaf names of ``weights.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def rope(x, theta):
    """x: (B, S, H, hd), positions 0..S-1."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(p, h, cfg):
    b, s, d = h.shape
    nh = cfg["num_attention_heads"]
    hd = d // nh
    q = rope(mm(h, p["attn.wq"]).reshape(b, s, nh, hd), cfg["rope_theta"])
    k = rope(mm(h, p["attn.wk"]).reshape(b, s, nh, hd), cfg["rope_theta"])
    v = mm(h, p["attn.wv"]).reshape(b, s, nh, hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HI).reshape(b, s, nh * hd)
    return mm(o, p["attn.wo"])


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def mlp(p, h, kind):
    if kind == "gelu_tanh":
        return mm(gelu_tanh(mm(h, p["mlp.w1"])), p["mlp.w2"])
    return mm(jax.nn.silu(mm(h, p["mlp.w1"])) * mm(h, p["mlp.w3"]), p["mlp.w2"])


def layer(p, x, cfg):
    eps = cfg["norm_eps"]
    x = x + attention(p, layernorm(x, p["norm1.scale"], p["norm1.bias"], eps), cfg)
    return x + mlp(p, layernorm(x, p["norm2.scale"], p["norm2.bias"], eps), cfg["mlp"])


def loss_sum(final_norm, w_out, x, labels, cfg):
    """Sum of token cross-entropies; ``w_out`` is (hidden, vocab)."""
    h = layernorm(x, final_norm["final_norm.scale"], final_norm["final_norm.bias"],
                  cfg["norm_eps"])
    logits = mm(h, w_out)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)
