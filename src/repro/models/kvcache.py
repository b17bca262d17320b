"""Decode-time caches and the single-token decode forward.

Cache layout mirrors the superblock structure: per superblock position there
is a stack over repeats — attention positions carry (k, v) of shape
(R, B, S_max, n_kv, hd); mamba positions carry (conv_state, ssm_state). The
decode step scans over repeats, consuming and re-emitting cache slices, so the
HLO stays depth-independent just like training.

Sub-quadratic handling for ``long_500k``: mamba positions are O(1)-state;
attention positions with a sliding window only allocate a window-sized ring
cache (mixtral); full-attention caches are allocated at S_max.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models.model import (
    gather_weights,
    num_repeats,
    shard_act,
    superblock_period,
)


def cache_len(cfg: ModelConfig, seq_len: int) -> int:
    """Per-attention-layer cache length (ring-buffered for SWA)."""
    if cfg.sliding_window:
        return min(cfg.sliding_window, seq_len)
    return seq_len


def cache_specs(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    """ShapeDtypeStruct pytree for the decode cache (no allocation)."""
    p = superblock_period(cfg)
    r = num_repeats(cfg)
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    s_kv = cache_len(cfg, seq_len)
    out: dict[str, Any] = {}
    for j in range(p):
        if cfg.mixer_at(j) == "attention":
            kv = jax.ShapeDtypeStruct((r, batch, s_kv, cfg.num_kv_heads, hd), dt)
            out[f"pos{j}"] = {"k": kv, "v": kv}
        else:
            conv, ssm = M2.mamba2_state_defs(cfg, batch)
            out[f"pos{j}"] = {
                "conv": jax.ShapeDtypeStruct((r,) + conv.shape, conv.dtype),
                "ssm": jax.ShapeDtypeStruct((r,) + ssm.shape, ssm.dtype),
            }
    if cfg.kind == "encdec":
        # precomputed cross-attention K/V over the encoded source
        xkv = jax.ShapeDtypeStruct((r, batch, seq_len, cfg.num_kv_heads, hd), dt)
        for j in range(p):
            out[f"pos{j}"]["xk"] = xkv
            out[f"pos{j}"]["xv"] = xkv
    return out


def init_cache(cfg: ModelConfig, batch: int, seq_len: int):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        cache_specs(cfg, batch, seq_len),
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


def rope_positions(pos: jax.Array) -> jax.Array:
    """RoPE positions for the decoded token: (1,) for a shared scalar ``pos``,
    (B, 1) for per-slot positions (continuous batching)."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        return jnp.full((1,), pos, jnp.int32)
    return pos[:, None]


def decode_mask(pos: jax.Array, s_kv: int, sliding: bool) -> jax.Array:
    """Additive attention mask over cache slots at decode position ``pos``.

    Returns (S_kv,) for scalar ``pos`` or (B, S_kv) for per-slot positions.
    Sliding-window caches are ring buffers: every slot is valid once the ring
    has wrapped (pos >= s_kv); before that, validity follows slot order.
    """
    kpos = jnp.arange(s_kv)
    if jnp.ndim(pos):
        kpos = kpos[None, :]
        pos = pos[:, None]
    if sliding:
        valid = (pos >= s_kv) | (kpos <= pos)
    else:
        valid = kpos <= pos
    return jnp.where(valid, 0.0, L.NEG_INF)


def write_slot(buf: jax.Array, val: jax.Array, slot: jax.Array,
               mask: jax.Array | None = None) -> jax.Array:
    """Write one decoded token into a (B, S, ...) cache at ``slot``.

    Scalar ``slot`` keeps the resident fast path (dynamic_update_slice);
    per-slot (B,) writes use a one-hot select over the slot axis — every batch
    row lands at its own position (continuous batching). ``mask`` (B,) bool,
    per-slot only: rows of masked-off slots are left untouched (chunked
    prefill advances a subset of slots while the rest keep their cache).
    """
    val = val.astype(buf.dtype)
    if jnp.ndim(slot) == 0:
        assert mask is None, "write masking requires per-slot positions"
        return jax.lax.dynamic_update_slice_in_dim(buf, val, slot, axis=1)
    rows = jnp.arange(buf.shape[1])[None, :] == slot[:, None]  # (B, S)
    if mask is not None:
        rows = rows & mask[:, None]
    rows = rows.reshape(rows.shape + (1,) * (buf.ndim - 2))
    return jnp.where(rows, val, buf)


class ResidentKV:
    """Default decode cache I/O: the whole (B, S, kv, hd) cache lives in HBM.

    ``update_and_fetch`` is the seam the paged serving subsystem replaces
    (repro.serve.paging.PagedKV): write the decoded token, return the full
    key/value views attention runs over plus the new cache entry — the same
    hook pattern as ``Run.lazy_gather`` for training-weight gathers.
    ``entry_keys`` names the cache leaves the hook consumes per attention
    position (the paged layout splits each of k/v into a hot ring + cold
    pages).
    """

    entry_keys = ("k", "v")

    def update_and_fetch(self, entry: dict, k: jax.Array, v: jax.Array,
                         pos: jax.Array, cfg: ModelConfig,
                         active: jax.Array | None = None):
        s_kv = entry["k"].shape[1]
        slot = pos % s_kv if cfg.sliding_window else pos
        new_k = write_slot(entry["k"], k, slot, mask=active)
        new_v = write_slot(entry["v"], v, slot, mask=active)
        mask = decode_mask(pos, s_kv, bool(cfg.sliding_window))
        return new_k, new_v, mask, {"k": new_k, "v": new_v}


RESIDENT_KV = ResidentKV()


def _decode_attention(ap: dict, h: jax.Array, cache: dict, pos: jax.Array,
                      cfg: ModelConfig, kv_io=None, active=None):
    """h: (B,1,D). Returns (out (B,1,D), new_cache)."""
    b = h.shape[0]
    hd = cfg.resolved_head_dim
    q = (h @ ap["wq"]).reshape(b, 1, cfg.num_heads, hd)
    k = (h @ ap["wk"]).reshape(b, 1, cfg.num_kv_heads, hd)
    v = (h @ ap["wv"]).reshape(b, 1, cfg.num_kv_heads, hd)
    positions = rope_positions(pos)
    q = L.apply_rope(q, positions, cfg.rope_theta, cfg.rotary_fraction)
    k = L.apply_rope(k, positions, cfg.rope_theta, cfg.rotary_fraction)
    kv_io = kv_io or RESIDENT_KV
    attend = getattr(kv_io, "attend", None)
    if attend is not None:
        # fused path: the kv_io owns the whole write+attend (the paged
        # Pallas kernel consumes hot ring + cold pages directly, skipping
        # the gathered full-cache materialization); falls back internally
        # to update_and_fetch + _masked_decode_attn when no kernel applies
        out, new_cache = attend(cache, q, k, v, pos, cfg, active=active)
    else:
        full_k, full_v, logits_mask, new_cache = kv_io.update_and_fetch(
            cache, k, v, pos, cfg, active=active)
        out = _masked_decode_attn(q, full_k, full_v, logits_mask)
    return out.reshape(b, 1, -1) @ ap["wo"], new_cache


def _masked_decode_attn(q, k, v, logits_mask):
    """Single-query attention over the whole cache. q: (B,1,Hq,hd).
    ``logits_mask``: (S_kv,) shared, or (B, S_kv) per-slot (continuous
    batching decodes every batch row at its own position)."""
    b, _, hq, hd = q.shape
    s_kv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qh = (q.astype(jnp.float32) / jnp.sqrt(jnp.float32(hd))).reshape(b, hkv, g, hd)
    logits = jnp.einsum("bkgd,bskd->bkgs", qh, k.astype(jnp.float32))
    if logits_mask.ndim == 2:
        logits = logits + logits_mask[:, None, None, :]
    else:
        logits = logits + logits_mask[None, None, None, :]
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, 1, hq, hd).astype(q.dtype)


def _decode_cross_attention(ap: dict, h: jax.Array, xk: jax.Array, xv: jax.Array, cfg):
    b = h.shape[0]
    hd = cfg.resolved_head_dim
    q = (h @ ap["wq"]).reshape(b, 1, cfg.num_heads, hd)
    out = _masked_decode_attn(q, xk, xv, jnp.zeros((xk.shape[1],), jnp.float32))
    return out.reshape(b, 1, -1) @ ap["wo"]


def decode_position(pparams: dict, x: jax.Array, pcache: dict, pos: jax.Array,
                    cfg: ModelConfig, kv_io=None, active=None):
    """One layer, one token. x: (B,1,D). ``active`` (B,) bool masks cache
    writes for slots not participating in this step (chunked prefill)."""
    h = L.apply_norm(pparams["norm1"], x, cfg.norm, cfg.norm_eps)
    new_cache = dict(pcache)
    if "attn" in pparams:
        keys = (kv_io or RESIDENT_KV).entry_keys
        sub = {name: pcache[name] for name in keys}
        mix, upd = _decode_attention(pparams["attn"], h, sub, pos, cfg,
                                     kv_io=kv_io, active=active)
        new_cache.update(upd)
    else:
        state = (pcache["conv"], pcache["ssm"])
        mix, (conv, ssm) = M2.apply_mamba2(pparams["mamba"], h, cfg, state=state, return_state=True)
        if active is not None:
            m = active.reshape((-1,) + (1,) * (conv.ndim - 1))
            conv = jnp.where(m, conv, pcache["conv"])
            m = active.reshape((-1,) + (1,) * (ssm.ndim - 1))
            ssm = jnp.where(m, ssm, pcache["ssm"])
        new_cache.update({"conv": conv, "ssm": ssm})
    x = x + mix
    if "xattn" in pparams:
        hx = L.apply_norm(pparams["norm_x"], x, cfg.norm, cfg.norm_eps)
        x = x + _decode_cross_attention(pparams["xattn"], hx, pcache["xk"], pcache["xv"], cfg)
    if "moe" in pparams:
        from repro.models.moe import apply_moe

        h2 = L.apply_norm(pparams["norm2"], x, cfg.norm, cfg.norm_eps)
        out, _ = apply_moe(pparams["moe"], h2, cfg)
        x = x + out
    elif "mlp" in pparams:
        h2 = L.apply_norm(pparams["norm2"], x, cfg.norm, cfg.norm_eps)
        x = x + L.apply_mlp(pparams["mlp"], h2, cfg.mlp)
    return shard_act(x, "bsd"), new_cache


def decode_step(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # (B, 1) int32 — the token decoded last step
    pos: jax.Array,  # () int32 shared, or (B,) per-slot (continuous batching)
    cfg: ModelConfig,
    *,
    gather_specs=None,
    kv_io=None,
    active=None,  # (B,) bool or None — mask cache writes per slot
) -> tuple[jax.Array, dict]:
    """One decode step across the whole model. Returns (logits (B,V), cache).

    ``kv_io`` swaps the attention-cache storage strategy per position (default
    ``RESIDENT_KV``); the paged serving path passes ``serve.paging.PagedKV``,
    whose cold pages live in host memory and are fetched page-wise inside this
    same repeat scan — mirroring how ``Run.lazy_gather`` threads per-chunk
    weight gathers through the training scan.
    """
    from repro.models.model import embed_tokens, lm_head

    x = embed_tokens(params, tokens, cfg)
    p = superblock_period(cfg)

    def body(x, slices):
        new_slices = {}
        for j in range(p):
            specs = None if gather_specs is None else gather_specs[f"pos{j}"]
            pp = gather_weights(slices[f"pos{j}"]["params"], specs)
            x, nc = decode_position(pp, x, slices[f"pos{j}"]["cache"], pos, cfg,
                                    kv_io=kv_io, active=active)
            new_slices[f"pos{j}"] = nc
        return x, new_slices

    xs = {
        f"pos{j}": {"params": params["blocks"][f"pos{j}"], "cache": cache[f"pos{j}"]}
        for j in range(p)
    }
    x, new_cache = jax.lax.scan(body, x, xs)
    logits = lm_head(params, x, cfg)
    return logits[:, 0], new_cache
