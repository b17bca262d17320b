"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels run compiled; on the CPU test backend they run in
interpret mode, which executes the kernel body op-by-op — bit-for-bit the
same math, so tests validate the kernel logic against the ref.py oracles
without TPU hardware. Any other backend raises
(``compat.pallas_interpret_required``, resolved once per process).

``assert_ref_agreement`` is the one shared kernel-vs-oracle structure
checker (dtype + shape over arbitrary output pytrees) used by the kernel
tests and ``benchmarks/kernel_bench.py`` — per-op copies of the same
asserts are gone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.compat import pallas_interpret_required as interpret_mode
from repro.kernels import flash_attention as _flash
from repro.kernels import fused_adam as _fa
from repro.kernels import fused_quant as _fq
from repro.kernels import paged_attention as _pa
from repro.kernels import rmsnorm as _rn


def assert_ref_agreement(kernel_out, ref_out) -> None:
    """Assert kernel and oracle outputs agree structurally (dtype + shape).

    One checker for every op: outputs may be a single array or any pytree
    of arrays (the fused quantizer returns a triple). Value comparison is
    the caller's job — tolerance is per-op, structure is not.
    """
    k_leaves, k_def = jax.tree.flatten(kernel_out)
    r_leaves, r_def = jax.tree.flatten(ref_out)
    assert k_def == r_def, f"kernel/ref structure mismatch: {k_def} vs {r_def}"
    for kl, rl in zip(k_leaves, r_leaves):
        assert kl.shape == rl.shape, f"shape mismatch: {kl.shape} vs {rl.shape}"
        assert kl.dtype == rl.dtype, f"dtype mismatch: {kl.dtype} vs {rl.dtype}"


def flash_attention(q, k, v, *, causal=True, window=0, block_q=128, block_k=128):
    return _flash.flash_attention(
        q, k, v, causal=causal, window=window, block_q=block_q, block_k=block_k,
        interpret=interpret_mode(),
    )


def fused_adam_update(p, g, master, m, v, *, lr, b1, b2, eps, weight_decay, bc1, bc2):
    """Signature-compatible with optim.adam._update_leaf's fused branch."""
    scal = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.asarray(b1, jnp.float32),
        jnp.asarray(b2, jnp.float32), jnp.asarray(eps, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32), jnp.asarray(bc1, jnp.float32),
        jnp.asarray(bc2, jnp.float32), jnp.zeros((), jnp.float32),
    ])
    return _fa.fused_adam(p, g, master, m, v, scal, interpret=interpret_mode())


def rmsnorm(x, scale, *, eps: float = 1e-6):
    return _rn.rmsnorm(x, scale, eps=eps, interpret=interpret_mode())


def decode_paged_attention(q, k_hot, v_hot, k_cold, v_cold, sel, mask, *, n_hot):
    """Fused single-token decode attention over the paged cache layout
    (serve/paging.PagedKV) — bit-identical to the lax gather-then-attend
    path; see kernels/paged_attention.py for the block layout."""
    return _pa.paged_attention(q, k_hot, v_hot, k_cold, v_cold, sel, mask,
                               n_hot=n_hot, interpret=interpret_mode())


def fused_quantize_ef(ch, me):
    """One-pass int8 absmax quantize + pack + EF residual update for the
    manual-sync wire path (dist/collectives) — bit-identical to the three-op
    sequence it replaces; see kernels/fused_quant.py."""
    return _fq.fused_quantize_ef(ch, me, interpret=interpret_mode())
