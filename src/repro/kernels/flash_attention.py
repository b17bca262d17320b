"""FlashAttention forward as a Pallas TPU kernel.

TPU adaptation notes (vs. the CUDA original): tiling is chosen for VMEM and
the MXU — the (block_q x hd) @ (hd x block_k) products keep every matmul dim a
multiple of 128 (MXU-aligned for hd >= 128; zero-padded otherwise by Mosaic),
online-softmax statistics live in fp32 VMEM scratch across the arbitrary-
ordered KV grid dimension, and fully-masked KV tiles are skipped via the grid
rather than warp-level early exit. GQA is handled in the index maps (a KV
head is revisited by ``group`` consecutive Q heads) so K/V tiles are fetched
once per group from HBM.

Grid: (batch*heads, Sq/block_q, Sk/block_k) with
dimension_semantics=(parallel, parallel, arbitrary) — the KV axis is the
sequential accumulation axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fa_kernel(
    q_ref, k_ref, v_ref,  # inputs
    o_ref,  # output
    acc_ref, m_ref, l_ref,  # VMEM scratch
    *,
    scale: float,
    block_q: int,
    block_k: int,
    causal: bool,
    window: int,
    sk: int,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]  # (block_q, hd)
    k = k_ref[0]  # (block_k, hd)
    v = v_ref[0]

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (block_q, block_k)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    kpos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    mask = kpos < sk
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_ref[...]  # (block_q, 1)
    m_cur = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(kj == nk - 1)
    def finalize():
        o_ref[0, ...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, Hq, Sq, hd)
    k: jax.Array,  # (B, Hkv, Sk, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, hq, sq, hd = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    scale = 1.0 / np.sqrt(hd)

    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    # pad sequence dims to block multiples (masked out by `kpos < sk`)
    sq_p = (sq + block_q - 1) // block_q * block_q
    sk_p = (sk + block_k - 1) // block_k * block_k
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))

    qf = q.reshape(b * hq, sq_p, hd)
    kf = k.reshape(b * hkv, sk_p, hd)
    vf = v.reshape(b * hkv, sk_p, hd)
    grid = (b * hq, sq_p // block_q, sk_p // block_k)

    def q_index(h, i, j):
        return (h, i, 0)

    def kv_index(h, i, j):
        # GQA: query head h belongs to kv head (h % hq) // group of batch h // hq
        bidx = h // hq
        kvh = (h % hq) // group
        return (bidx * hkv + kvh, j, 0)

    out = pl.pallas_call(
        functools.partial(
            _fa_kernel, scale=scale, block_q=block_q, block_k=block_k,
            causal=causal, window=window, sk=sk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_index),
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_index),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),  # acc
            pltpu.VMEM((block_q, 1), jnp.float32),  # m (running max)
            pltpu.VMEM((block_q, 1), jnp.float32),  # l (running denom)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, sq_p, hd)[:, :, :sq]
