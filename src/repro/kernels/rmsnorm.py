"""Fused RMSNorm as a Pallas TPU kernel: one HBM pass computing fp32 row
statistics and the scaled output (vs. separate reduce + normalize + scale
kernels). Rows tile over the grid; the full feature dim stays resident in
VMEM (d_model * 4B — up to ~18k features fits comfortably in 64 MB VMEM
alongside double buffering)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (block_rows, D)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * s_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "eps", "interpret"))
def rmsnorm(
    x: jax.Array,  # (..., D)
    scale: jax.Array,  # (D,)
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    shape = x.shape
    d = shape[-1]
    rows = x.size // d
    xf = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    rows_p = (rows + block_rows - 1) // block_rows * block_rows
    if rows_p != rows:
        xf = jnp.pad(xf, ((0, rows_p - rows), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows_p // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, d), x.dtype),
        interpret=interpret,
    )(xf, scale.reshape(1, d))
    return out[:rows].reshape(shape)
