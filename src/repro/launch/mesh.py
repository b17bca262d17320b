"""Production mesh construction.

``make_production_mesh`` is a function (never a module-level constant) so that
importing this module does not touch jax device state — the dry-run must set
XLA_FLAGS before anything initializes the backend.
"""
from __future__ import annotations

import jax

from repro.core.hardware import MULTI_POD, SINGLE_POD, MeshSpec


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def mesh_spec(*, multi_pod: bool = False) -> MeshSpec:
    return MULTI_POD if multi_pod else SINGLE_POD


def make_local_mesh(devices=None):
    """Single-process mesh over whatever devices exist (tests/examples)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    model = 1
    for cand in (4, 2, 1):
        if n % cand == 0 and n >= cand:
            model = cand
            break
    return jax.make_mesh(
        (n // model, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
