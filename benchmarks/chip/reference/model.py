"""Plain float32 dense decoder: the block equations, written out.

Pre-norm decoder layers ``x += attn(LN1(x)); x += mlp(LN2(x))`` with
LayerNorm (scale and bias), causal multi-head attention with rotary
embeddings (NeoX halves) over the first ``rotary_fraction`` of each head,
a GELU (tanh) or SwiGLU MLP without biases, a final LayerNorm, an output
head (the embedding, transposed, when tied) and the mean token
cross-entropy. Every matrix product runs at ``Precision.HIGHEST``, so
float32 means float32 on a TPU. No chunks, offload, sharding or kernels.

Attention goes through one block of queries at a time: each block's
scores, causal mask, softmax over all keys and weighted sum, sized so that
its scores stay under ``SCORE_BYTES``. Each block is a ``jax.checkpoint``,
so a backward pass keeps one block's scores and probabilities at a time,
never the whole sequence's. The arithmetic of each query's row is the
same for any block size.

Parameters are dicts keyed by the canonical leaf names of ``weights.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# attention scores of one block of rows and queries stay under this many bytes
SCORE_BYTES = 1 << 30


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def rotary_fraction(cfg) -> float:
    """The share of each head that rotary embeddings rotate: 1.0 for
    ``rope_full_head``, the file's ``rotary_fraction`` for ``rope_partial``."""
    if cfg["positions"] == "rope_full_head":
        return 1.0
    if cfg["positions"] != "rope_partial":
        raise ValueError(f"unsupported positions {cfg['positions']!r}")
    fraction = cfg["rotary_fraction"]
    if not 0.0 < fraction <= 1.0:
        raise ValueError(f"rotary_fraction {fraction} is not in (0, 1]")
    return fraction


def rope(x, theta, fraction=1.0):
    """x: (B, S, H, hd), positions 0..S-1. Rotates the first
    ``int(fraction * hd)`` dims of each head (NeoX halves within them, the
    frequencies over those dims) and passes the rest through."""
    s, rd = x.shape[1], int(fraction * x.shape[-1])
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., : rd // 2], x[..., rd // 2: rd], x[..., rd:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def query_block(rows, heads, seq):
    """Queries per attention block: the most whose scores (rows x heads x
    queries x seq, float32) stay under ``SCORE_BYTES``, at least one."""
    return max(1, min(seq, SCORE_BYTES // (4 * rows * heads * seq)))


def _attend(q, k, v, start):
    """Causal attention of the queries at positions ``start + i`` over all
    keys: q (B, Q, H, hd), k and v (B, S, H, hd)."""
    nq, s = q.shape[1], k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / np.sqrt(q.shape[-1])
    causal = jnp.arange(s)[None, :] <= jnp.arange(start, start + nq)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HI)


def attention(p, h, cfg):
    b, s, d = h.shape
    nh = cfg["num_attention_heads"]
    hd = d // nh
    theta, fraction = cfg["rope_theta"], rotary_fraction(cfg)
    q = rope(mm(h, p["attn.wq"]).reshape(b, s, nh, hd), theta, fraction)
    k = rope(mm(h, p["attn.wk"]).reshape(b, s, nh, hd), theta, fraction)
    v = mm(h, p["attn.wv"]).reshape(b, s, nh, hd)
    qb = query_block(b, nh, s)
    attend = jax.checkpoint(_attend, static_argnums=3)
    o = jnp.concatenate([attend(q[:, i:i + qb], k, v, i) for i in range(0, s, qb)], axis=1)
    return mm(o.reshape(b, s, nh * hd), p["attn.wo"])


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
