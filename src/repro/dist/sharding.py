"""ParamDef axis tags -> mesh shardings, honoring MemoryPlan placement.

Every ``ParamDef`` names its dims with tags (``layer``/``zero``/``tp``/
``exp``/``none``, see models/layers.py). This module is the single place
those tags meet a concrete ``jax.sharding.Mesh``:

  tag       persist            hbm / host               dp_only
  ------    ----------------   ----------------------   -----------------
  zero      replicated         sharded over zero axes   sharded over zero axes
  tp/exp    "model" axis       "model" axis             replicated
  layer     never sharded (the scan axis)
  none      never sharded

The *zero axes* are every mesh axis except ``model`` (``("data",)`` on the
single-pod mesh, ``("pod", "data")`` multi-pod). ``placement="host"``
additionally pins the sharding to the platform's host memory kind
(``pinned_host`` on TPU/GPU, ``unpinned_host`` on the CPU backend used by
tests; see repro/compat.py). ``dp_only=True`` repurposes the model axis as an
extra data axis: weights replicate across it and the batch shards over it.

A dim only takes an axis assignment when its size is divisible by the axis
extent — otherwise it stays replicated (tiny test models on forced
multi-device CPU meshes must lower cleanly, same policy as the KV-cache
shardings in train/step_builder.py).
"""
from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import host_memory_kind
from repro.models.layers import EXP, LAYER, TP, ZERO, ParamDef

_is_def = lambda x: isinstance(x, ParamDef)  # noqa: E731


# ---------------------------------------------------------------------------
# Mesh geometry helpers
# ---------------------------------------------------------------------------
def mesh_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def zero_axes(mesh) -> tuple[str, ...]:
    """ZeRO (data-parallel) axes: everything except the model axis."""
    return tuple(a for a in mesh.axis_names if a != "model")


def batch_axes(mesh, dp_only: bool = False) -> tuple[str, ...]:
    """Axes the batch dim shards over; with dp_only the model axis joins in."""
    return tuple(mesh.axis_names) if dp_only else zero_axes(mesh)


def _extent(mesh, axes: tuple[str, ...]) -> int:
    sizes = mesh_sizes(mesh)
    return math.prod(sizes[a] for a in axes)


def _entry(axes: tuple[str, ...]):
    """PartitionSpec entry: bare string for one axis, tuple for several."""
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else axes


def _fits(mesh, dim: int, axes: tuple[str, ...]) -> bool:
    n = _extent(mesh, axes)
    return n == 1 or (dim % n == 0 and dim >= n)


# ---------------------------------------------------------------------------
# Single-def shardings
# ---------------------------------------------------------------------------
def _spec(d: ParamDef, mesh, placement: str, dp_only: bool) -> P:
    names = set(mesh.axis_names)
    used: set[str] = set()
    entries = []
    for dim, tag in zip(d.shape, d.axes):
        ax: tuple[str, ...] = ()
        if tag == ZERO and placement != "persist":
            ax = zero_axes(mesh)
        elif tag in (TP, EXP) and not dp_only and "model" in names:
            ax = ("model",)
        ax = tuple(a for a in ax if a not in used)
        if not ax or not _fits(mesh, dim, ax):
            entries.append(None)
            continue
        used.update(ax)
        entries.append(_entry(ax))
    return P(*entries)


def sharding_for(
    d: ParamDef, mesh, *, placement: str = "hbm", dp_only: bool = False
) -> NamedSharding:
    """Run-state sharding for one ParamDef under a chunk placement."""
    assert placement in ("persist", "hbm", "host"), placement
    spec = _spec(d, mesh, placement, dp_only)
    if placement == "host":
        return NamedSharding(mesh, spec, memory_kind=host_memory_kind(mesh))
    return NamedSharding(mesh, spec)


def host_layer_sliceable(d) -> bool:
    """Whether one layer of a stacked leaf (a ``ParamDef`` or an array, its
    layer axis leading) can move between host memory and the device on
    its own. The layer axis must lie outside the TPU's two minor, tiled
    dims, so the leaf needs rank 3 or more: one layer of a ``[L, d]`` leaf
    cuts across the (8, 128) tile's sublanes, a DMA the compiler refuses
    ("Sublane slicing size not multiple of chunk sublane size") or that
    halts the core ("The DMA stride is invalid"). Host runs keep such
    parameters in HBM (``train/step_builder.py``), and the streamed Adam
    update leaves their states whole (``optim/adam.streams``)."""
    return len(d.shape) >= 3


def gather_sharding(d: ParamDef, mesh, *, dp_only: bool = False) -> NamedSharding:
    """Point-of-use layout: ZeRO axes gathered (replicated), TP kept, in
    device memory — the target of the per-chunk all-gather."""
    return NamedSharding(mesh, _spec(d, mesh, "persist", dp_only))


# ---------------------------------------------------------------------------
# Pytree variants
# ---------------------------------------------------------------------------
def tree_shardings(defs, mesh, *, placement: str = "hbm", dp_only: bool = False):
    return jax.tree.map(
        lambda d: sharding_for(d, mesh, placement=placement, dp_only=dp_only),
        defs, is_leaf=_is_def,
    )


def tree_specs(defs, shardings):
    """ShapeDtypeStruct pytree carrying the shardings (jit input specs)."""
    return jax.tree.map(
        lambda d, s: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype), sharding=s),
        defs, shardings, is_leaf=_is_def,
    )


def tree_gather_shardings(
    stacked_defs, mesh, *, persistent: bool = False, dp_only: bool = False
):
    """Per-repeat gather targets for a stacked block-def tree.

    The defs carry a leading ``layer`` axis (stacked superblock repeats); the
    gather happens inside the layer scan on one repeat's slice, so the specs
    drop that axis. Persistent runs return None: weights are already
    replicated and ``gather_weights`` skips the device_put entirely.
    """
    if persistent:
        return None

    def one(d: ParamDef) -> NamedSharding:
        if d.axes and d.axes[0] == LAYER:
            d = ParamDef(d.shape[1:], d.axes[1:], init=d.init, scale=d.scale, dtype=d.dtype)
        return gather_sharding(d, mesh, dp_only=dp_only)

    return jax.tree.map(one, stacked_defs, is_leaf=_is_def)


# ---------------------------------------------------------------------------
# Manual-sync shard_map specs (sync_mode="manual"; see train/step_builder.py)
# ---------------------------------------------------------------------------
def manual_sync_axes(mesh, dp_only: bool = False) -> tuple[str, ...]:
    """Mesh axes the manual gradient sync reduces over: the batch axes
    (== ZeRO axes; with dp_only the model axis joins them). The manual path
    requires params replicated over exactly these axes (all-persist plans)."""
    return batch_axes(mesh, dp_only)


def manual_batch_pspec(rank: int, mesh, dp_only: bool = False) -> P:
    """shard_map in_spec for a rank-``rank`` batch input: leading dim split
    over the sync axes, the rest replicated — the PartitionSpec twin of
    ``batch_sharding`` (which produces the jit-side NamedSharding)."""
    return P(_entry(manual_sync_axes(mesh, dp_only)), *([None] * (rank - 1)))


def leaf_sync_dim(sharding: NamedSharding, sync_axes: tuple[str, ...]) -> int | None:
    """Dim index a leaf ZeRO-shards over *exactly* the manual sync axes.

    Returns None for leaves the manual sync must treat as replicated — truly
    replicated leaves (persistent chunks, norms/scalars) and leaves whose
    tagged dim did not divide the axis extent (``_spec`` kept them whole).
    The full-axes-match requirement is what makes the reduce-scatter's
    shard-owner coordinate identical to the storage layout's."""
    target = _entry(tuple(sync_axes))
    for i, e in enumerate(sharding.spec):
        if e == target or (isinstance(e, (tuple, list)) and tuple(e) == tuple(sync_axes)):
            return i
    return None


def manual_state_pspecs(tree):
    """shard_map in/out specs for the train state under manual sync: each
    leaf's spec is its actual sharding (``P()`` for replicated leaves and
    unsharded scalars). All-persistent (DDP-kind) plans yield replicated
    specs everywhere; ZeRO-kind plans yield the sharded specs, so the body
    sees true local shards. Host memory kinds never appear here — manual
    eligibility (``MemoryPlan.manual_sync_kind``) excludes host chunks."""

    def ps(leaf):
        sh = getattr(leaf, "sharding", None)
        return sh.spec if isinstance(sh, NamedSharding) else P()

    return jax.tree.map(
        ps, tree,
        is_leaf=lambda x: isinstance(x, (jax.Array, jax.ShapeDtypeStruct)),
    )


# ---------------------------------------------------------------------------
# Batch / activation shardings
# ---------------------------------------------------------------------------
def batch_sharding(mesh, rank: int, dp_only: bool = False) -> NamedSharding:
    """Leading-dim batch sharding for a rank-``rank`` input array."""
    ba = batch_axes(mesh, dp_only)
    return NamedSharding(mesh, P(_entry(ba), *([None] * (rank - 1))))


def make_activation_sharder(mesh, plan) -> Callable[[jax.Array, str], jax.Array]:
    """Activation sharding constraints for the model's ``shard_act`` hook.

    Kinds (see models/model.py): ``bsd`` pins block-boundary activations
    (batch over the batch axes; the seq dim additionally over TP when the plan
    enables sequence parallelism), ``enter`` gathers a seq-sharded boundary
    back to batch-only before layer compute, ``logits`` shards the vocab dim
    over TP. Constraints are skipped for dims the mesh does not divide.
    """
    dp = bool(getattr(plan, "dp_only", False))
    ba = batch_axes(mesh, dp)
    tp = ("model",) if (not dp and "model" in mesh.axis_names) else ()
    if math.prod(mesh.devices.shape) == 1:
        return lambda x, kind="bsd": x
    seq_shard = bool(getattr(plan, "seq_shard_acts", False))

    def sharder(x: jax.Array, kind: str = "bsd") -> jax.Array:
        if x.ndim < 2:
            return x
        b = _entry(ba) if _fits(mesh, x.shape[0], ba) else None
        rest: list[Any] = [None] * (x.ndim - 1)
        if kind == "logits" and tp and _fits(mesh, x.shape[-1], tp):
            rest[-1] = _entry(tp)
        elif kind == "bsd" and seq_shard and tp and _fits(mesh, x.shape[1], tp):
            rest[0] = _entry(tp)
        # kind == "enter" (and non-SP "bsd"): batch-only, seq/feature replicated
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(b, *rest)))

    return sharder
