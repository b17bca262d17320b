"""Gradient-sync collectives with wire-format compression + error feedback.

Two compression levels for the gradient all-reduce:

  * ``bf16_all_reduce`` — cast to bf16 on the wire, mean across replicas;
  * ``compressed_all_reduce`` — int8 quantization (per-tensor absmax scale)
    with an error-feedback residual: each step transmits ``quantize(g + err)``
    and carries ``err' = (g + err) - dequantize(...)`` into the next step, so
    quantization error is fed back instead of lost (1-bit-Adam/PowerSGD-style
    EF; here at int8, the paper-adjacent "communication compression" knob the
    autotuner trades against plan runtime via the calibrated wire factors in
    ``core/cost_model.py``; see docs/cost_model.md).

Two *sync paths* consume these numerics (``MemoryPlan.sync_mode``, dataflow
diagram in docs/architecture.md):

  * **xla** — under jit, GSPMD already inserts the reductions the shardings
    imply. Passing ``mesh=None`` (what train/step_builder.py does for this
    path) applies the pure wire-format numerics to the already-reduced
    gradients — exactly what a compressed collective would have produced with
    synchronized replicas, but the bytes XLA moves are the *uncompressed*
    gradients (calibration measures wire factor ~1.0: numerics only).
  * **manual** — the step builder runs loss/grad under ``shard_map`` and owns
    the reduction via the ``manual_*`` functions below. Two topologies:

    - *replicated leaves* (DDP-style): each device quantizes its local
      gradient (plus its error-feedback residual) to int8, the *compressed*
      payload is all-gathered over the sync axes (int8 on the wire — a
      gather-based all-reduce, the only all-reduce XLA lets us express with
      an integer wire dtype without overflow), and every device dequantizes
      and averages the shards locally.
    - *ZeRO-sharded leaves* (``manual_*_reduce_scatter``): each device chunks
      its local full gradient along the sharded dim, quantizes per chunk, and
      an ``all_to_all`` delivers chunk *j*'s int8 payload (+ fp32 scale) to
      shard-owner *j*, which dequantizes and averages — a compressed
      reduce-scatter moving ``(z-1)/z`` of the int8 bytes per device, so each
      device ends up owning its ZeRO shard's reduced gradient. The EF
      residual is *shard*-sized: it feeds back the error of the chunk the
      device contributes to its own shard (the 1/z of the quantization error
      that re-enters this device's state; errors on chunks shipped to other
      owners are plain round-to-nearest noise, bounded by half a
      quantization step — see ``manual_int8_ef_reduce_scatter``).

    Real wire bytes drop by the quantization ratio; each device carries its
    own residual.

    ``gather_param_lazy`` completes the ZeRO-3 picture: a custom-vjp bf16
    param all-gather whose transpose runs the compressed reduce-scatter, so
    the manual zero3 path gathers each chunk just-in-time inside the layer
    scan and receives shard-sized gradients (and fresh EF residuals)
    straight out of AD — no up-front gather, no full-grad workspace.

Everything outside a shard_map body is guarded on mesh size so 1-device
meshes (and the CPU test meshes) take the local math path; the manual
entry points are only ever called inside a shard_map body the step builder
guards the same way.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _mesh_size(mesh) -> int:
    return math.prod(mesh.devices.shape)


def _replica_mean(x: jax.Array, mesh, axis_names) -> jax.Array:
    """Mean across all replicas of a replicated array via an explicit psum."""
    axes = tuple(axis_names) if axis_names is not None else tuple(mesh.axis_names)
    n = math.prod(dict(zip(mesh.axis_names, mesh.devices.shape))[a] for a in axes)

    def mean(v):
        return (jax.lax.psum(v.astype(jnp.float32), axes) / n).astype(x.dtype)

    return jax.shard_map(mean, mesh=mesh, in_specs=P(), out_specs=P())(x)


# ---------------------------------------------------------------------------
# bf16 wire format
# ---------------------------------------------------------------------------
def bf16_all_reduce(x: jax.Array, mesh=None, axis_names=None) -> jax.Array:
    """Mean-all-reduce with bf16 on the wire; returns x's dtype."""
    xb = x.astype(jnp.bfloat16)
    if mesh is None or _mesh_size(mesh) == 1:
        return xb.astype(x.dtype)
    return _replica_mean(xb, mesh, axis_names).astype(x.dtype)


# ---------------------------------------------------------------------------
# int8 + error feedback
# ---------------------------------------------------------------------------
def _quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-tensor absmax int8: returns (q int8, scale fp32 scalar)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_all_reduce(
    x: jax.Array, err: jax.Array, mesh=None, axis_names=None
) -> tuple[jax.Array, jax.Array]:
    """Int8 error-feedback mean-all-reduce.

    Returns ``(avg, new_err)`` with the invariant ``avg + new_err == x + err``
    on one device (nothing is lost — the residual carries exactly what the
    wire dropped) and ``|new_err|`` bounded by half a quantization step.
    """
    c = x.astype(jnp.float32) + err.astype(jnp.float32)
    q, scale = _quantize_int8(c)
    local = _dequantize_int8(q, scale)
    new_err = c - local
    if mesh is not None and _mesh_size(mesh) > 1:
        avg = _replica_mean(local, mesh, axis_names)
    else:
        avg = local
    return avg.astype(x.dtype), new_err.astype(err.dtype)


# ---------------------------------------------------------------------------
# Manual sync primitives (called INSIDE a shard_map body; see step_builder)
# ---------------------------------------------------------------------------
def manual_mean(x: jax.Array, axis_names) -> jax.Array:
    """Uncompressed mean over the sync axes (fp32 accumulate on the wire)."""
    return jax.lax.pmean(x.astype(jnp.float32), axis_names).astype(x.dtype)


def manual_bf16_mean(x: jax.Array, axis_names) -> jax.Array:
    """Mean with bf16 on the wire: psum of the bf16-cast local value."""
    return jax.lax.pmean(x.astype(jnp.bfloat16), axis_names).astype(x.dtype)


def manual_int8_ef_sync(
    x: jax.Array, err: jax.Array, axis_names
) -> tuple[jax.Array, jax.Array]:
    """Int8+EF mean over the sync axes with the compressed payload on the wire.

    Gather-based all-reduce: quantize ``x + err`` locally, all-gather the int8
    payload and fp32 scales (int8 is what actually crosses the link — psum of
    int8 would overflow, so the sum happens after dequantization), then every
    device dequantizes and averages identically, keeping the result exactly
    replicated. ``err`` is per-device: each device feeds back what *its* wire
    transmission dropped.
    """
    c = x.astype(jnp.float32) + err.astype(jnp.float32)
    q, scale = _quantize_int8(c)
    new_err = c - _dequantize_int8(q, scale)
    qg = jax.lax.all_gather(q, axis_names)  # (n, *x.shape) int8 on the wire
    sg = jax.lax.all_gather(scale, axis_names)  # (n,) fp32 scales (negligible)
    deq = qg.astype(jnp.float32) * sg.reshape((-1,) + (1,) * x.ndim)
    return jnp.mean(deq, axis=0).astype(x.dtype), new_err.astype(err.dtype)


def _names(axis_names) -> tuple[str, ...]:
    return (axis_names,) if isinstance(axis_names, str) else tuple(axis_names)


def _sync_extent(axis_names) -> int:
    """Extent of the (possibly compound) sync axis, inside a shard_map body.

    ``psum`` of a Python constant folds to the static axis size."""
    return int(jax.lax.psum(1, _names(axis_names)))


def _flat_axis_index(axis_names) -> jax.Array:
    """Row-major flattened device index over the sync axes — the shard-owner
    coordinate, matching both PartitionSpec layout and the device order
    jax.lax.all_to_all uses for a sequence of axis names."""
    idx = jnp.zeros((), jnp.int32)
    for a in _names(axis_names):
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return idx


def _pad_dim(x: jax.Array, dim: int, z: int) -> jax.Array:
    """Zero-pad ``dim`` up to the next multiple of z (uneven-divisor leaves).

    The state layout only ZeRO-shards evenly-divisible dims (dist/sharding
    keeps the rest replicated), so in the train step this is a no-op; the
    primitives still handle uneven dims so they compose as standalone
    collectives — every owner then holds the *padded* shard and the caller
    strips the tail."""
    pad = (-x.shape[dim]) % z
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[dim] = (0, pad)
    return jnp.pad(x, widths)


def _chunk(x: jax.Array, dim: int, z: int) -> jax.Array:
    """(…, dim, …) -> (z, …, dim/z, …): shard chunks moved to a leading axis."""
    x = _pad_dim(x, dim, z)
    shard = x.shape[dim] // z
    parts = x.reshape(x.shape[:dim] + (z, shard) + x.shape[dim + 1 :])
    return jnp.moveaxis(parts, dim, 0)


# Fused quantize/pack dispatch for the reduce-scatter wire path. Tri-state:
# None (default) resolves to the Pallas kernel; True/False force the path
# (differential tests drive both sides, and the fidelity/bench harnesses pin
# it for labeled rows).
_FUSED_QUANT: bool | None = None


def set_fused_quant(enabled: bool | None) -> None:
    """Force (True/False) or restore auto-resolution (None) of the fused
    int8 quantize+pack kernel in ``manual_int8_ef_reduce_scatter``."""
    global _FUSED_QUANT
    _FUSED_QUANT = enabled


def fused_quant_enabled() -> bool:
    return True if _FUSED_QUANT is None else _FUSED_QUANT


def manual_reduce_scatter(x: jax.Array, axis_names, dim: int,
                          wire_dtype=None) -> jax.Array:
    """Mean-reduce-scatter over the sync axes: returns this device's shard of
    the mean gradient, shard dim ``dim`` (padded to a multiple of the sync
    extent when uneven). ``wire_dtype`` casts the payload (bf16 wire format);
    default keeps fp32 accumulation."""
    z = _sync_extent(axis_names)
    xw = _pad_dim(x.astype(wire_dtype or jnp.float32), dim, z)
    out = jax.lax.psum_scatter(xw, _names(axis_names), scatter_dimension=dim,
                               tiled=True)
    return (out.astype(jnp.float32) / z).astype(x.dtype)


def manual_bf16_reduce_scatter(x: jax.Array, axis_names, dim: int) -> jax.Array:
    """Mean-reduce-scatter with bf16 on the wire."""
    return manual_reduce_scatter(x, axis_names, dim, wire_dtype=jnp.bfloat16)


def manual_int8_ef_reduce_scatter(
    x: jax.Array, err: jax.Array, axis_names, dim: int
) -> tuple[jax.Array, jax.Array]:
    """Int8+EF mean-reduce-scatter with the compressed payload on the wire.

    Each device splits its local full gradient into z shard-chunks along
    ``dim``, adds its shard-sized error-feedback residual to the chunk headed
    for *its own* shard, and quantizes each chunk with a per-chunk absmax
    scale. An ``all_to_all`` then ships chunk j's int8 payload (+ fp32 scale)
    to shard-owner j — int8 is what crosses the link; summing int8 would
    overflow, so the sum happens owner-side after dequantization. The owner
    dequantizes the z received chunks and averages: it now owns its ZeRO
    shard's reduced gradient.

    Returns ``(shard_mean, new_err)`` where both are shard-sized (``dim``
    divided by the sync extent, zero-padded when uneven). The residual
    carries exactly the error of this device's own-chunk transmission — the
    component that feeds back into the shard this device owns and updates;
    errors on the z-1 chunks shipped to other owners are not recoverable at
    shard-sized state and stay plain rounding noise (bounded by half a
    quantization step, i.e. |err| <= absmax/254 per element).
    """
    z = _sync_extent(axis_names)
    me = _flat_axis_index(axis_names)
    ch = _chunk(x.astype(jnp.float32), dim, z)  # (z, *shard_shape)
    ch = ch.at[me].add(err.astype(jnp.float32))
    if fused_quant_enabled():
        # One fused pass: absmax + quantize + pack + own-chunk EF residual
        # (kernels/fused_quant.py). Bit-identical to the three-op sequence
        # below when each path is jit'd separately; the unfused sequence
        # stays as the differential-testing / pallas-less fallback.
        from repro.kernels import fused_quantize_ef

        q, scale, new_err = fused_quantize_ef(ch, me)
    else:
        scale = jnp.maximum(
            jnp.max(jnp.abs(ch), axis=tuple(range(1, ch.ndim))), 1e-30) / 127.0
        q = jnp.clip(
            jnp.round(ch / scale.reshape((z,) + (1,) * (ch.ndim - 1))), -127, 127
        ).astype(jnp.int8)
        own_c = ch[me]
        new_err = own_c - q[me].astype(jnp.float32) * scale[me]
    qr = jax.lax.all_to_all(q, _names(axis_names), 0, 0)  # int8 on the wire
    sr = jax.lax.all_to_all(scale, _names(axis_names), 0, 0)  # (z,) fp32 scales
    deq = qr.astype(jnp.float32) * sr.reshape((z,) + (1,) * (qr.ndim - 1))
    return jnp.mean(deq, axis=0).astype(x.dtype), new_err.astype(err.dtype)


# ---------------------------------------------------------------------------
# Lazy per-chunk param gather (manual ZeRO-3; called INSIDE a shard_map body)
# ---------------------------------------------------------------------------
def _tiled_all_gather(x: jax.Array, axis_names, dim: int) -> jax.Array:
    return jax.lax.all_gather(x, _names(axis_names), axis=dim, tiled=True)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _gather_param_lazy(axis_names, dim, compress, w, err):
    return _tiled_all_gather(w, axis_names, dim)


def _gather_param_lazy_fwd(axis_names, dim, compress, w, err):
    return _tiled_all_gather(w, axis_names, dim), err


def _gather_param_lazy_bwd(axis_names, dim, compress, err, ct):
    if compress == "int8_ef":
        g_shard, new_err = manual_int8_ef_reduce_scatter(ct, err, axis_names, dim)
        return g_shard, new_err
    rs = manual_bf16_reduce_scatter if compress == "bf16" else manual_reduce_scatter
    return rs(ct, axis_names, dim), err


_gather_param_lazy.defvjp(_gather_param_lazy_fwd, _gather_param_lazy_bwd)


def gather_param_lazy(w: jax.Array, err, axis_names, dim: int,
                      compress: str = "int8_ef", anchor=None) -> jax.Array:
    """Just-in-time bf16 param all-gather whose transpose is the compressed
    reduce-scatter (the manual ZeRO-3 dataflow; see train/sync.py).

    Forward: tiled all-gather of this device's param shard along ``dim`` over
    the sync axes — the full leaf exists only at its point of use (inside the
    layer scan, so chunks are gathered one at a time; whether the gathered
    value survives to BWD or is re-gathered is the caller's remat policy —
    the plan's ``n_buffer``).

    Backward: the incoming cotangent is this device's *local full* gradient
    for the leaf; instead of materializing it into a workspace and syncing
    later, the VJP rule runs ``manual_int8_ef_reduce_scatter`` directly —
    each device receives only its owned grad shard straight out of AD, with
    the int8 payload on the wire.

    Error feedback threads through the VJP: ``err`` (shard-sized fp32, or
    None for bf16/none wire formats) is unused in the forward, and its
    "cotangent" is defined to be the *new* residual the reduce-scatter
    produces — so ``jax.grad`` w.r.t. ``(w, err)`` yields
    ``(grad_shard, new_err)`` and the caller carries the residual as explicit
    state keyed by chunk.

    ``anchor`` double-buffers the gather (the training twin of
    serve/paging's prefetch ordering): when given, the gathered leaf is
    ``optimization_barrier``-paired with the anchor value, so XLA may issue
    this chunk's all-gather as soon as the anchor exists — during the
    previous chunk's matmuls — but never earlier (pipeline depth stays
    bounded). The barrier is differentiable (its transpose barriers the
    cotangents), so the reduce-scatter transpose above is untouched.
    """
    g = _gather_param_lazy(tuple(_names(axis_names)), int(dim), compress, w, err)
    if anchor is not None:
        g, _ = jax.lax.optimization_barrier((g, anchor))
    return g


# Tree-level dispatch (replicated vs ZeRO-sharded leaves) lives in
# train/sync.py (manual_tree_sync): the strategy layer owns which primitive
# syncs which leaf; this module owns only the wire formats and topologies.


# ---------------------------------------------------------------------------
# Pytree variants (what the step builder consumes)
# ---------------------------------------------------------------------------
def init_error_feedback(grads):
    """fp32 zero residuals matching a gradient pytree."""
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def bf16_tree_all_reduce(grads, mesh=None, axis_names=None):
    return jax.tree.map(lambda g: bf16_all_reduce(g, mesh, axis_names), grads)


def compressed_tree_all_reduce(grads, errs, mesh=None, axis_names=None):
    """Leaf-wise compressed_all_reduce; returns (avg_tree, new_err_tree)."""
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(errs)
    outs = [compressed_all_reduce(g, e, mesh, axis_names) for g, e in zip(flat_g, flat_e)]
    return (
        treedef.unflatten([o[0] for o in outs]),
        treedef.unflatten([o[1] for o in outs]),
    )
