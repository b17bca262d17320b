"""Distributed substrate: ParamDef->mesh sharding and gradient collectives.

``repro.dist.sharding`` maps the axis tags declared on every ``ParamDef``
(``zero``/``tp``/``exp``/``layer``/``none``) onto the production
``("data", "model")`` / ``("pod", "data", "model")`` meshes, honoring a
MemoryPlan's placement (persist | hbm | host) via sharding memory kinds.

``repro.dist.collectives`` provides the wire-format-compressed gradient
synchronization primitives (bf16 cast, int8 + error feedback).
"""
from repro.dist import collectives, sharding  # noqa: F401
