"""Backend facts the rest of the package branches on, in one place.

The package targets the installed jax (0.9): ``jax.shard_map``,
``jax.sharding.AxisType`` and the differentiable
``jax.lax.optimization_barrier`` are used directly. What remains here are
the two backend decisions that must never silently hide the device:

  * ``pallas_interpret_required`` — Pallas kernels run compiled on TPU and
    in interpret mode on the CPU test backend; any other backend is an
    error, not a quiet fallback;
  * ``host_memory_kind`` — the host memory space host-placed state lives
    in; a platform without one raises instead of degrading host placement
    to device residence.
"""
from __future__ import annotations

import functools

import jax


@functools.lru_cache(maxsize=None)
def pallas_interpret_required() -> bool:
    """True on the CPU backend (Pallas interpret mode), False on TPU
    (compiled kernels). Raises on any other backend."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"no Pallas mode for backend {backend!r}: "
                       "kernels compile on tpu and interpret on cpu")


def host_memory_kind(mesh) -> str:
    """The host memory kind the mesh's devices address.

    TPU exposes ``pinned_host``; the CPU backend only ``unpinned_host``
    (which still exercises every placement/fetch code path in tests).
    """
    kinds = {m.kind for m in mesh.devices.flat[0].addressable_memories()}
    for kind in ("pinned_host", "unpinned_host"):
        if kind in kinds:
            return kind
    raise RuntimeError(
        f"{mesh.devices.flat[0]} has no host memory space (kinds: "
        f"{sorted(kinds)}); host-placed state cannot be realised")
