"""Fused int8 absmax quantize + pack (Pallas), with the error-feedback
residual of the owned chunk.

The manual-sync wire path (dist/collectives.manual_int8_ef_reduce_scatter)
and the compressed activation saves (models/model._quantize_rows) quantize
``z`` chunks, each with its own absmax scale. The three-op sequence they
replace reads the fp32 input once for the abs/max reduction and again for
the divide/round/clip/s8 pass; this kernel streams each chunk through VMEM
in tiles, accumulating the absmax in a scratch on a first pass over the
chunk's tiles and emitting the s8 payload and the fp32 scale on the second.
A chunk that fits one tile is fetched once: the two passes visit the same
block index, so the pipeline does not re-read it.

Layouts (every block tile-aligned for the TPU compiler):
  * ``(z, d)`` rows — activation saves, 1-D shards: blocks of ``bz`` whole
    rows, scale block ``(bz, 1)``;
  * ``(z, *shard)`` with a 2-D or larger shard — wire chunks: viewed as
    ``(z, R, L)`` with ``L`` the shard's last dim, blocks ``(1, tile, L)``
    over a ``(z, 2, R / tile)`` grid, scale block ``(1, 1, 1)``. A ragged
    last tile is masked out of the absmax; its out-of-range rows are never
    written back.

The owned chunk's residual ``ch[me] - q[me] * scale[me]`` is one
elementwise pass over 1/z of the data and stays in XLA. Every op is the
same elementwise / exact-reduction op the three-op sequence runs, so the
payload, scales and residual are bit-identical to it under interpret mode
(tests/test_paged_attention_kernel.py). The collective itself (all_to_all
of s8 + scales) stays outside: Pallas kernels cannot contain collectives.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# fp32 input bytes per block: with double buffering and the s8 output this
# keeps a step well inside the default scoped VMEM limit
_BLOCK_BYTES = 2 << 20
# sublane multiple that tiles both fp32 (8) and int8 (32) blocks
_SUBLANES = 32


def _kernel(ch_ref, q_ref, scale_ref, amax_ref, *, rows: int, tile: int):
    p, t = pl.program_id(1), pl.program_id(2)
    x = ch_ref[...]

    @pl.when((p == 0) & (t == 0))
    def _init():
        amax_ref[...] = jnp.zeros_like(amax_ref)

    @pl.when(p == 0)
    def _absmax():
        a = jnp.abs(x)
        if rows % tile:  # ragged last tile: rows past the end hold garbage
            r = t * tile + jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
            a = jnp.where(r < rows, a, 0.0)
        for ax in range(a.ndim - 1, 0, -1):
            a = jnp.max(a, axis=ax, keepdims=True)
        amax_ref[...] = jnp.maximum(amax_ref[...], a)

    @pl.when(p == 1)
    def _emit():
        scale = jnp.maximum(amax_ref[...], 1e-30) / 127.0
        q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        scale_ref[...] = scale


def _rows_per_block(n_rows: int, row_bytes: int) -> int:
    fit = max(_SUBLANES, _BLOCK_BYTES // row_bytes // _SUBLANES * _SUBLANES)
    return n_rows if n_rows <= fit else fit


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_quantize_ef(
    ch: jax.Array,  # (z, *shard) fp32 chunked tensor, EF already added at [me]
    me: jax.Array,  # () int32 — this device's chunk index (lax.axis_index)
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Absmax int8 quantize of ``z`` chunks.

    Returns ``(q, scales, new_err)``: s8 payload shaped like ``ch``, (z,)
    fp32 per-chunk scales, and the owned chunk's fp32 EF residual shaped
    like ``ch[0]`` — bit-identical to the three-op sequence.
    """
    z, shard = ch.shape[0], ch.shape[1:]
    x = ch.astype(jnp.float32)
    if len(shard) == 1:
        n = shard[0]
        bz = _rows_per_block(z, 4 * n)
        view, block = (z, n), (bz, n)
        grid = (pl.cdiv(z, bz), 2, 1)
        rows = tile = bz  # chunks are whole rows: nothing to mask
        x_map = lambda i, p, t: (i, 0)  # noqa: E731
        q_map = x_map
        s_shape, s_block = (z, 1), (bz, 1)
        s_map = x_map
    else:
        rows, lanes = 1, shard[-1]
        for d in shard[:-1]:
            rows *= d
        tile = _rows_per_block(rows, 4 * lanes)
        view, block = (z, rows, lanes), (1, tile, lanes)
        grid = (z, 2, pl.cdiv(rows, tile))
        x_map = lambda i, p, t: (i, t, 0)  # noqa: E731
        # pass 0 parks the output on tile 0, which pass 1 writes first: no
        # block is written back before pass 1 has filled it
        q_map = lambda i, p, t: (i, t * p, 0)  # noqa: E731
        s_shape, s_block = (z, 1, 1), (1, 1, 1)
        s_map = lambda i, p, t: (i, 0, 0)  # noqa: E731
    q, scale = pl.pallas_call(
        functools.partial(_kernel, rows=rows, tile=tile),
        grid=grid,
        in_specs=[pl.BlockSpec(block, x_map)],
        out_specs=[pl.BlockSpec(block, q_map), pl.BlockSpec(s_block, s_map)],
        out_shape=[
            jax.ShapeDtypeStruct(view, jnp.int8),
            jax.ShapeDtypeStruct(s_shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM(s_block, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(x.reshape(view))
    q = q.reshape(ch.shape)
    scale = scale.reshape(z)
    new_err = (jnp.take(x, me, axis=0)
               - jnp.take(q, me, axis=0).astype(jnp.float32) * jnp.take(scale, me))
    return q, scale, new_err
