"""Pallas decode-attention kernel over the paged KV-cache layout.

The serving subsystem (repro/serve/paging.py) splits each attention layer's
(B, S, n_kv, hd) decode cache into a hot HBM ring of the last
``hot_window = page_size * n_hot`` slots plus a canonical cold store. The
plain-lax decode path reconstructs the full cache page by page (``jnp.where``
selects between ring slice and cold tile), materializes the concatenation in
HBM, and only then runs single-query attention over it — a gather-then-attend
memory round trip on every token, for every attention layer (the pre-PR-8
"rebuilds the cache in plain lax ops" known limit).

This kernel consumes the paged layout directly. The grid walks
``(B*Hkv, n_pages)``; each KV step streams one page as a pair of K/V blocks —
the hot-ring slice at ring page ``j % n_hot`` and the cold tile at page
``j`` — selects the canonical rows with the precomputed per-row residency
mask (``PagedKV`` flush semantics), and accumulates that page's attention
logits into a VMEM scratch row. The gathered cache never exists in HBM: one
streamed pass replaces the rebuild's read-write-read.

Block layout per (batch*kv-head, page) grid step::

      q        (1, G, hd)    fixed block, G = Hq // Hkv query heads
      k_hot    (1, P, hd)    ring page  j % n_hot   ─┐ per-row select
      k_cold   (1, P, hd)    cold page  j           ─┘ (sel block)
      sel      (1, P, 1)     per-row residency (int32, rows on sublanes)
      mask     (1, 1, P)     additive NEG_INF decode mask (lanes, as logits)
      scratch  logits (G, S) fp32, v (S, hd) fp32   accumulated across pages
      out      (1, G, hd)    written on the final page

Exactness contract (the PR-5 bitwise guarantee must survive): the decoded
logits are **bit-identical** to the lax rebuild path. Two deliberate choices
make that hold rather than merely approximate:

  * masking is additive ``NEG_INF`` exactly as ``kvcache.decode_mask``
    emits it, so a masked (stale ring) row's softmax weight underflows to
    exactly 0.0 in fp32 — residency choices on masked rows are invisible;
  * the softmax runs **once over the full streamed logits row** (decode is
    single-query, so the row fits VMEM: G x S fp32). An online-softmax
    rescaling chain (exp(x - m_j) * exp(m_j - m_{j+1}) ...) reassociates the
    reduction and drifts from ``jax.nn.softmax`` by ulps, which would break
    the bitwise parity tests; with the row resident, max / exp / sum /
    divide / PV-dot are the exact op sequence of ``_masked_decode_attn``.
    Multi-query prefill, where rows do not fit, keeps the flash-style
    online pass in ``kernels/flash_attention.py``.

VMEM bound: logits (G, S) + gathered V (S, hd) fp32 — ~2.2 MB for G=16,
S=32k, hd=128-ary V at S=4k; long-context decode needs a KV-split grid
(follow-up, priced by the cost model's ``paged_attn`` calibration key).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, kh_ref, kc_ref, vh_ref, vc_ref, sel_ref, mask_ref,
            o_ref, logits_ref, v_ref, *, n_pages: int, hd: int):
    j = pl.program_id(1)
    psz = kh_ref.shape[1]
    # per-row residency select: True -> hot ring holds the canonical value
    sel = sel_ref[0] != 0  # (P, 1)
    k = jnp.where(sel, kh_ref[0], kc_ref[0]).astype(jnp.float32)
    v = jnp.where(sel, vh_ref[0], vc_ref[0]).astype(jnp.float32)
    # same scaling op sequence as _masked_decode_attn: fp32 cast, / sqrt(hd)
    qf = q_ref[0].astype(jnp.float32) / jnp.sqrt(jnp.float32(hd))
    logits = jax.lax.dot_general(qf, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    logits_ref[:, pl.ds(j * psz, psz)] = logits + mask_ref[0]
    v_ref[pl.ds(j * psz, psz), :] = v

    @pl.when(j == n_pages - 1)
    def _finalize():
        full = logits_ref[...]
        m = jnp.max(full, axis=-1, keepdims=True)
        p = jnp.exp(full - m)
        probs = p / jnp.sum(p, axis=-1, keepdims=True)
        out = jax.lax.dot_general(probs, v_ref[...], (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_hot", "interpret"))
def paged_attention(
    q: jax.Array,       # (B, 1, Hq, hd) post-RoPE query, model dtype
    k_hot: jax.Array,   # (B, W, Hkv, hd) hot ring, W = page_size * n_hot
    v_hot: jax.Array,   # (B, W, Hkv, hd)
    k_cold: jax.Array,  # (B, S, Hkv, hd) canonical cold store
    v_cold: jax.Array,  # (B, S, Hkv, hd)
    sel: jax.Array,     # (B, S) bool — True where the ring row is canonical
    mask: jax.Array,    # (B, S) fp32 additive decode mask (0 / NEG_INF)
    *,
    n_hot: int,
    interpret: bool = False,
) -> jax.Array:
    """Single-token decode attention over hot ring + cold pages.

    Returns (B, 1, Hq, hd) in q's dtype — bit-identical to
    ``_masked_decode_attn(q, gather(k), gather(v), mask)`` where ``gather``
    is ``PagedKV._gather``'s page-wise reconstruction.
    """
    b, _, hq, hd = q.shape
    s_kv, hkv = k_cold.shape[1], k_cold.shape[2]
    w = k_hot.shape[1]
    assert w % n_hot == 0, (w, n_hot)
    psz = w // n_hot
    assert s_kv % psz == 0, (s_kv, psz)
    n_pages = s_kv // psz
    g = hq // hkv

    # fold (B, Hkv) into one grid axis; move heads ahead of the slot axis
    qf = q.reshape(b, hkv, g, hd).reshape(b * hkv, g, hd)

    def fold(x):
        return jnp.moveaxis(x, 2, 1).reshape(b * hkv, x.shape[1], hd)

    grid = (b * hkv, n_pages)
    out = pl.pallas_call(
        functools.partial(_kernel, n_pages=n_pages, hd=hd),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, g, hd), lambda h, j: (h, 0, 0)),
            pl.BlockSpec((1, psz, hd), lambda h, j: (h, j % n_hot, 0)),
            pl.BlockSpec((1, psz, hd), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, psz, hd), lambda h, j: (h, j % n_hot, 0)),
            pl.BlockSpec((1, psz, hd), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, psz, 1), lambda h, j: (h // hkv, j, 0)),
            pl.BlockSpec((1, 1, psz), lambda h, j: (h // hkv, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, g, hd), lambda h, j: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, s_kv), jnp.float32),
            pltpu.VMEM((s_kv, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qf, fold(k_hot), fold(k_cold), fold(v_hot), fold(v_cold),
      sel.astype(jnp.int32)[:, :, None], mask[:, None, :])
    return out.reshape(b, hkv, g, hd).reshape(b, 1, hq, hd)
