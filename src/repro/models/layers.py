"""Core transformer layers: norms, RoPE, attention (naive + blockwise), MLPs.

Everything is functional: ``params`` are pytrees of jnp arrays, layers are pure
functions. Parameter *definitions* (shape + init + sharding axis tags) live
next to the apply functions so model assembly stays in one place.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Sharding axis tags. dist/sharding.py maps these to mesh axes given a plan.
# ---------------------------------------------------------------------------
LAYER = "layer"  # stacked-layer leading axis (scanned over, never sharded)
ZERO = "zero"  # ZeRO-shardable dim (sharded over (pod, data) when non-persistent)
TP = "tp"  # tensor-parallel dim (sharded over model axis)
EXP = "exp"  # expert dim (expert-parallel over model axis)
NONE = "none"  # never sharded


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    axes: tuple[str, ...]
    init: str = "normal"  # normal | zeros | ones
    scale: float | None = None  # stddev; None -> 1/sqrt(fan_in)
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)

    def initialize(self, key: jax.Array) -> jax.Array:
        dt = jnp.dtype(self.dtype)
        if self.init == "zeros":
            return jnp.zeros(self.shape, dt)
        if self.init == "ones":
            return jnp.ones(self.shape, dt)
        fan_in = self.shape[-2] if len(self.shape) >= 2 else self.shape[-1]
        std = self.scale if self.scale is not None else 1.0 / np.sqrt(fan_in)
        return (jax.random.normal(key, self.shape, jnp.float32) * std).astype(dt)


def init_tree(defs, key: jax.Array):
    """Initialize a pytree of ParamDefs into a pytree of arrays."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [d.initialize(k) for d, k in zip(leaves, keys)])


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    # Statistics accumulate in fp32 via preferred_element_type without ever
    # materializing an fp32 copy of x — a bare convert as the first op of a
    # rematerialized block gets hoisted out of the backward loop by XLA and
    # stacks an fp32 copy of every saved boundary (2x activation memory).
    d = x.shape[-1]
    ms = jnp.einsum("...d,...d->...", x, x, preferred_element_type=jnp.float32) / d
    rs = jax.lax.rsqrt(ms + eps)[..., None].astype(x.dtype)
    return x * rs * scale


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-6):
    d = x.shape[-1]
    ones = jnp.ones((d,), x.dtype)
    mu = (jnp.einsum("...d,d->...", x, ones, preferred_element_type=jnp.float32) / d)
    ms = jnp.einsum("...d,...d->...", x, x, preferred_element_type=jnp.float32) / d
    var = ms - mu * mu
    rs = jax.lax.rsqrt(var + eps)[..., None].astype(x.dtype)
    mu = mu[..., None].astype(x.dtype)
    return (x - mu) * rs * scale + bias


def norm_defs(d: int, kind: str) -> dict:
    if kind == "rmsnorm":
        return {"scale": ParamDef((d,), (NONE,), init="ones")}
    return {
        "scale": ParamDef((d,), (NONE,), init="ones"),
        "bias": ParamDef((d,), (NONE,), init="zeros"),
    }


def apply_norm(params: dict, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"], eps)
    return layernorm(x, params["scale"], params["bias"], eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponents)  # (hd/2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               fraction: float = 1.0) -> jax.Array:
    """x: (..., S, H, hd); positions: (..., S) int32.

    Rotates the first ``int(fraction * hd)`` dims of each head (NeoX halves
    within them, frequencies over those dims, as StableLM's partial rotary)
    and passes the rest through unchanged."""
    hd = x.shape[-1]
    rd = int(fraction * hd)
    if rd < hd:
        rot = apply_rope(x[..., :rd], positions, theta)
        return jnp.concatenate([rot, x[..., rd:]], axis=-1)
    freqs = rope_frequencies(hd, theta)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
NEG_INF = -1e30


def naive_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int | jax.Array = 0,
) -> jax.Array:
    """Reference attention. q: (B,Sq,Hq,hd); k,v: (B,Sk,Hkv,hd). GQA broadcast.

    ``q_offset`` is the absolute position of q[0] (for decode with a cache).
    """
    b, sq, hq, hd = q.shape
    _, sk, hkv, _ = k.shape
    groups = hq // hkv
    qh = q.reshape(b, sq, hkv, groups, hd)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qh.astype(jnp.float32), k.astype(jnp.float32))
    logits *= 1.0 / np.sqrt(hd)
    qpos = jnp.arange(sq) + q_offset  # (Sq,)
    kpos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, hq, hd)


def _attn_bias(sq, block_kv, blk_idx, sk, causal, window, q_offset):
    """Additive (sq, block_kv) fp32 bias: 0 where attendable, NEG_INF where
    masked. Additive form keeps the mask a small 2-D tensor — a boolean
    ``where`` at logits shape gets materialized (and stacked per block) by
    XLA at ~1 GB a pop."""
    kpos = blk_idx * block_kv + jnp.arange(block_kv)
    qpos = jnp.arange(sq) + q_offset
    mask = (kpos[None, :] < sk) & jnp.ones((sq, 1), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    return jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)  # (sq, block_kv)


def _mea_forward(q, k, v, sk, causal, window, q_offset, block_kv):
    """Online-softmax forward. Returns (out fp32, lse fp32). Matmuls stay in
    the input dtype with fp32 accumulation (preferred_element_type)."""
    b, sq, hkv, g, hd = q.shape
    nblk = k.shape[1] // block_kv
    kb = k.reshape(b, nblk, block_kv, hkv, hd)
    vb = v.reshape(b, nblk, block_kv, hkv, hd)
    scale = 1.0 / np.sqrt(hd)

    def body(carry, inp):
        acc, m, denom = carry
        kblk, vblk, blk_idx = inp
        logits = jnp.einsum(
            "bqkgd,bskd->bqkgs", q, kblk, preferred_element_type=jnp.float32
        ) * scale
        bias = _attn_bias(sq, block_kv, blk_idx, sk, causal, window, q_offset)
        logits = logits + bias[None, :, None, None, :]
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        scale_old = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        denom_new = denom * scale_old + jnp.sum(p, axis=-1)
        acc_new = acc * scale_old[..., None] + jnp.einsum(
            "bqkgs,bskd->bqkgd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32,
        )
        return (acc_new, m_new, denom_new), None

    acc0 = jnp.zeros((b, sq, hkv, g, hd), jnp.float32)
    m0 = jnp.full((b, sq, hkv, g), NEG_INF, jnp.float32)
    d0 = jnp.zeros((b, sq, hkv, g), jnp.float32)
    (acc, m, denom), _ = jax.lax.scan(
        body, (acc0, m0, d0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nblk)),
    )
    denom = jnp.maximum(denom, 1e-30)
    out = acc / denom[..., None]
    lse = m + jnp.log(denom)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _mea(q, k, v, sk, causal, window, q_offset, block_kv):
    out, _ = _mea_forward(q, k, v, sk, causal, window, q_offset, block_kv)
    return out.astype(q.dtype)


def _mea_fwd(q, k, v, sk, causal, window, q_offset, block_kv):
    out, lse = _mea_forward(q, k, v, sk, causal, window, q_offset, block_kv)
    out = out.astype(q.dtype)
    return out, (q, k, v, out, lse)


def _mea_bwd(sk, causal, window, q_offset, block_kv, res, dout):
    """FlashAttention-style backward: recompute p per KV block from saved lse;
    O(Sq * block_kv) live memory, no quadratic residuals."""
    q, k, v, out, lse = res
    b, sq, hkv, g, hd = q.shape
    nblk = k.shape[1] // block_kv
    kb = jnp.moveaxis(k.reshape(b, nblk, block_kv, hkv, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nblk, block_kv, hkv, hd), 1, 0)
    scale = 1.0 / np.sqrt(hd)
    doutf = dout.astype(jnp.float32)
    delta = jnp.sum(doutf * out.astype(jnp.float32), axis=-1)  # (b,sq,hkv,g)

    def body(dq_acc, inp):
        kblk, vblk, blk_idx = inp
        logits = jnp.einsum(
            "bqkgd,bskd->bqkgs", q, kblk, preferred_element_type=jnp.float32
        ) * scale
        bias = _attn_bias(sq, block_kv, blk_idx, sk, causal, window, q_offset)
        logits = logits + bias[None, :, None, None, :]
        p = jnp.exp(logits - lse[..., None])  # (b,sq,hkv,g,s)
        pd = p.astype(dout.dtype)
        dv = jnp.einsum("bqkgs,bqkgd->bskd", pd, dout, preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqkgd,bskd->bqkgs", dout, vblk, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None]) * scale
        dsd = ds.astype(q.dtype)
        dq_blk = jnp.einsum("bqkgs,bskd->bqkgd", dsd, kblk, preferred_element_type=jnp.float32)
        dk = jnp.einsum("bqkgs,bqkgd->bskd", dsd, q, preferred_element_type=jnp.float32)
        return dq_acc + dq_blk, (dk, dv)

    dq, (dks, dvs) = jax.lax.scan(
        body, jnp.zeros(q.shape, jnp.float32),
        (kb, vb, jnp.arange(nblk)),
    )
    dk = jnp.moveaxis(dks, 0, 1).reshape(k.shape).astype(k.dtype)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(v.shape).astype(v.dtype)
    return dq.astype(q.dtype), dk, dv


_mea.defvjp(_mea_fwd, _mea_bwd)


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int | jax.Array = 0,
    block_kv: int = 1024,
) -> jax.Array:
    """Memory-efficient online-softmax attention (Rabe–Staats / FlashAttention
    algorithm) as pure-jnp ``lax.scan`` over KV blocks with a custom VJP.

    Never materializes the (Sq, Sk) matrix in either pass: the backward
    recomputes per-block probabilities from the saved logsumexp. Residuals are
    O(B·S·H·hd) (q, k, v, out, lse) — this is the compile-anywhere analogue of
    kernels/flash_attention.py and the path used for long-context shapes.
    """
    b, sq, hq, hd = q.shape
    _, sk, hkv, _ = k.shape
    groups = hq // hkv
    block_kv = min(block_kv, max(128, sk))
    if sk % block_kv:
        pad = block_kv - sk % block_kv
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qh = q.reshape(b, sq, hkv, groups, hd)
    out = _mea(qh, k, v, sk, causal, window, q_offset, block_kv)
    return out.reshape(b, sq, hq, hd)


def attention_defs(cfg) -> dict:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    return {
        "wq": ParamDef((d, nq), (ZERO, TP)),
        "wk": ParamDef((d, nkv), (ZERO, TP)),
        "wv": ParamDef((d, nkv), (ZERO, TP)),
        "wo": ParamDef((nq, d), (TP, ZERO)),
    }


def attention_block(
    params: dict,
    x: jax.Array,
    cfg,
    *,
    positions: jax.Array | None = None,
    impl: str = "blockwise",
    block_kv: int = 1024,
) -> jax.Array:
    """Full self-attention over x: (B,S,D) -> (B,S,D)."""
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ params["wq"]).reshape(b, s, cfg.num_heads, hd)
    k = (x @ params["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
    v = (x @ params["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
    if positions is None:
        positions = jnp.arange(s)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_fraction)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_fraction)
    fn = blockwise_attention if impl == "blockwise" else naive_attention
    kwargs = dict(causal=True, window=cfg.sliding_window)
    if impl == "blockwise":
        kwargs["block_kv"] = min(block_kv, max(s, 128))
    out = fn(q, k, v, **kwargs)
    return out.reshape(b, s, cfg.num_heads * hd) @ params["wo"]


def cross_attention_defs(cfg) -> dict:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    return {
        "wq": ParamDef((d, nq), (ZERO, TP)),
        "wk": ParamDef((d, nkv), (ZERO, TP)),
        "wv": ParamDef((d, nkv), (ZERO, TP)),
        "wo": ParamDef((nq, d), (TP, ZERO)),
    }


def cross_attention_block(params, x, memory, cfg) -> jax.Array:
    """x: (B,Sq,D) attends over encoder memory (B,Sk,D)."""
    b, sq, _ = x.shape
    sk = memory.shape[1]
    hd = cfg.resolved_head_dim
    q = (x @ params["wq"]).reshape(b, sq, cfg.num_heads, hd)
    k = (memory @ params["wk"]).reshape(b, sk, cfg.num_kv_heads, hd)
    v = (memory @ params["wv"]).reshape(b, sk, cfg.num_kv_heads, hd)
    out = blockwise_attention(q, k, v, causal=False, block_kv=min(1024, sk))
    return out.reshape(b, sq, cfg.num_heads * hd) @ params["wo"]


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def mlp_defs(cfg, d_ff: int | None = None) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp in ("swiglu", "geglu"):
        return {
            "w1": ParamDef((d, ff), (ZERO, TP)),
            "w3": ParamDef((d, ff), (ZERO, TP)),
            "w2": ParamDef((ff, d), (TP, ZERO)),
        }
    return {
        "w1": ParamDef((d, ff), (ZERO, TP)),
        "w2": ParamDef((ff, d), (TP, ZERO)),
    }


def apply_mlp(params: dict, x: jax.Array, kind: str) -> jax.Array:
    h = x @ params["w1"]
    if kind == "swiglu":
        h = jax.nn.silu(h) * (x @ params["w3"])
    elif kind == "geglu":
        h = jax.nn.gelu(h) * (x @ params["w3"])
    elif kind == "gelu":
        h = jax.nn.gelu(h)
    elif kind == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        raise ValueError(kind)
    return h @ params["w2"]
