"""Pallas kernels and their package-level entry points.

Consumers request fused ops from *this package*; ``ops.py`` runs each
kernel compiled on TPU and in interpret mode on the CPU test backend
(``compat.pallas_interpret_required``), and ``ref.py`` holds the pure-jnp
oracles the kernels are tested against.

``fused_adam_update``, ``decode_paged_attention``, and ``fused_quantize_ef``
are re-exported at package level: their names do not collide with a
submodule. ``flash_attention`` / ``rmsnorm`` keep their submodule import
paths (``repro.kernels.ops``) — binding same-named functions on the package
would shadow the ``repro.kernels.flash_attention`` /
``repro.kernels.rmsnorm`` modules for ``import … as`` style imports (which
is also why the decode kernel exports as ``decode_paged_attention``, not
``paged_attention``).
"""
from __future__ import annotations

from repro.kernels.ops import (  # noqa: F401
    decode_paged_attention,
    fused_adam_update,
    fused_quantize_ef,
)
