"""Mixed-precision Adam with fp32 master weights (paper §2: fp16/bf16 compute,
fp32 updates). Pure-pytree implementation (no optax dependency) so optimizer
state sharding/placement stays fully under the planner's control.

The Pallas ``fused_adam`` kernel (kernels/fused_adam.py) provides the fused
single-pass update for TPU; the jnp path here is the portable reference and
what the CPU tests run.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.dist.sharding import host_layer_sliceable


@dataclasses.dataclass(frozen=True)
class AdamConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    use_fused_kernel: bool = False


def init_opt_state(params) -> dict:
    """master: fp32 copy; m, v: fp32 zeros. Same tree structure as params."""
    master = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "master": master,
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "count": jnp.zeros((), jnp.int32),
    }


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


def global_norm_clip(grads, max_norm: float, norm: jax.Array | None = None):
    """The clip to ``max_norm`` of the gradients' global norm, as a function
    of one gradient leaf (or slice of one), with that norm: the streamed
    update clips one layer at a time, so no clipped copy of a whole leaf is
    made. ``norm`` overrides the locally-computed global norm — the manual
    ZeRO sync path holds shard-sized gradient leaves, so the true global
    norm needs a cross-device reduction the caller owns (train/sync.py)."""
    norm = global_norm(grads) if norm is None else norm
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return (lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype)), norm


@dataclasses.dataclass(frozen=True)
class HostLeaf:
    """Placement of one host-offloaded leaf through its update: ``param`` is
    the new parameter's sharding, ``host`` the fp32 states' in host memory,
    ``device`` the states' while they are updated. ``stacked``: the leaf's
    leading axis is its layer axis (a run's stacked block leaf)."""

    param: Any
    host: Any
    device: Any
    stacked: bool = False


def _adam_math(g, master, m, v, dtype, *, cfg: AdamConfig, lr, bc1, bc2):
    """The elementwise Adam update of one leaf or one layer of it: the same
    expression per element either way, so both give the same bits."""
    gf = g.astype(jnp.float32)
    m_new = cfg.b1 * m + (1 - cfg.b1) * gf
    v_new = cfg.b2 * v + (1 - cfg.b2) * gf * gf
    m_hat = m_new / bc1
    v_hat = v_new / bc2
    upd = m_hat / (jnp.sqrt(v_hat) + cfg.eps)
    if cfg.weight_decay:
        upd = upd + cfg.weight_decay * master
    master_new = master - lr * upd
    return master_new.astype(dtype), master_new, m_new, v_new


def _update_leaf(p, g, master, m, v, *, cfg: AdamConfig, lr, bc1, bc2, fused: bool,
                 host: HostLeaf | None = None):
    """One Adam leaf update, whole. A host-offloaded leaf (``host``) brings
    its fp32 master/m/v to the device, updates them there and writes them
    back (the TPU adaptation of the paper's CPU Adam): the writeback can
    start only once the whole leaf is updated, and the update only once the
    whole leaf has arrived. Stacked leaves that qualify stream instead
    (``_stream_update``)."""
    if host is not None:
        master, m, v = (jax.device_put(x, host.device) for x in (master, m, v))
    elif fused:
        from repro.kernels import fused_adam_update

        return fused_adam_update(
            p, g, master, m, v, lr=lr, b1=cfg.b1, b2=cfg.b2, eps=cfg.eps,
            weight_decay=cfg.weight_decay, bc1=bc1, bc2=bc2,
        )
    out = _adam_math(g, master, m, v, p.dtype, cfg=cfg, lr=lr, bc1=bc1, bc2=bc2)
    if host is None:
        return out
    p_new, *states = out
    return (jax.device_put(p_new, host.param),
            *(jax.device_put(x, host.host) for x in states))


def _leading_unsharded(sharding) -> bool:
    spec = getattr(sharding, "spec", None)
    return spec is not None and (len(spec) == 0 or spec[0] is None)


def streams(p, host: HostLeaf | None) -> bool:
    """Whether a leaf's update streams layer by layer: a host-offloaded
    stacked leaf whose layer axis leads, is not sharded, and can be sliced
    one layer at a time over the host link (``dist.sharding.host_layer_sliceable``:
    not a 2-D ``[L, d]`` leaf)."""
    return (host is not None and host.stacked and host_layer_sliceable(p)
            and p.shape[0] >= 2
            and all(map(_leading_unsharded, (host.param, host.host, host.device))))


def _per_layer(sharding):
    """A stacked leaf's sharding for one layer of it (the layer axis dropped)."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(sharding.mesh, PartitionSpec(*sharding.spec[1:]),
                         memory_kind=sharding.memory_kind)


def _stream_update(ps, gs, masters, ms, vs, hosts, clip, math):
    """Adam over stacked host leaves of one layer count, one layer at a time.

    One loop over the layer axis updates layer i of every leaf while layer
    i+1's states are fetched and layer i-1's written back: the fetched
    states and the updated ones ride the carry (a double buffer), and each
    writeback lands in the carried host buffers (donated by the step, so
    in place). The device holds a few layers' states, not whole leaves,
    and both directions of the host link carry data at once.
    """
    n = ps[0].shape[0]
    dev = [_per_layer(h.device) for h in hosts]
    hst = [_per_layer(h.host) for h in hosts]
    par = [_per_layer(h.param) for h in hosts]
    row = lambda x, i: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False)  # noqa: E731

    def fetch(i, bufs):
        return [tuple(jax.device_put(row(x, i), d) for x in b[1:]) for b, d in zip(bufs, dev)]

    def update(i, fetched):
        return [math(clip(row(g, i)), *f, p.dtype) for g, f, p in zip(gs, fetched, ps)]

    def write(i, new, bufs):
        out = []
        for b, (p_new, *states), pd, hd in zip(bufs, new, par, hst):
            out.append((jax.lax.dynamic_update_index_in_dim(b[0], jax.device_put(p_new, pd), i, 0),
                        *(jax.lax.dynamic_update_index_in_dim(x, jax.device_put(y, hd), i, 0)
                          for x, y in zip(b[1:], states))))
        return out

    def body(i, carry):
        fetched, new, bufs = carry
        ahead = fetch(i + 1, bufs)  # reads layer i+1 before the writeback below
        return ahead, update(i, fetched), write(i - 1, new, bufs)

    # placed where the step keeps them (a no-op on TPU; the CPU backend
    # hands host-placed state to a step in device memory)
    bufs = [(jax.device_put(p, h.param), *(jax.device_put(x, h.host) for x in st))
            for p, h, *st in zip(ps, hosts, masters, ms, vs)]
    first, second = fetch(0, bufs), fetch(1, bufs)
    fetched, new, bufs = jax.lax.fori_loop(1, n - 1, body, (second, update(0, first), bufs))
    bufs = write(n - 2, new, bufs)
    return write(n - 1, update(n - 1, fetched), bufs)


def adam_update(params, grads, opt_state, cfg: AdamConfig, lr: float | jax.Array,
                host_plan: list | None = None, grad_norm: jax.Array | None = None):
    """Returns (new_params, new_opt_state, grad_norm).

    ``host_plan``: optional flat list aligned with the flattened params; each
    entry is None or the ``HostLeaf`` of a host-offloaded leaf. Leaves that
    ``streams`` admits are updated layer by layer, one loop per layer
    count; the other host leaves are updated whole, first, so that their
    copies overlap the loops. ``grad_norm``: externally-computed global
    norm for clipping (manual ZeRO sync: leaves are device-local shards)."""
    clip, gnorm = global_norm_clip(grads, cfg.grad_clip, grad_norm)
    count = opt_state["count"] + 1
    bc1 = 1 - cfg.b1 ** count.astype(jnp.float32)
    bc2 = 1 - cfg.b2 ** count.astype(jnp.float32)

    flat_p, treedef = jax.tree.flatten(params)
    flat = [flat_p] + [treedef.flatten_up_to(t) for t in
                       (grads, opt_state["master"], opt_state["m"], opt_state["v"])]
    if host_plan is None:
        host_plan = [None] * len(flat_p)

    outs: list = [None] * len(flat_p)
    groups: dict[int, list[int]] = {}
    for k, (p, h) in enumerate(zip(flat_p, host_plan)):
        if streams(p, h):
            groups.setdefault(p.shape[0], []).append(k)
            continue
        p, g, *states = (x[k] for x in flat)
        outs[k] = _update_leaf(p, clip(g), *states, cfg=cfg, lr=lr, bc1=bc1, bc2=bc2,
                               fused=cfg.use_fused_kernel, host=h)
    math = functools.partial(_adam_math, cfg=cfg, lr=lr, bc1=bc1, bc2=bc2)
    for ks in groups.values():
        new = _stream_update(*([x[k] for k in ks] for x in flat),
                             [host_plan[k] for k in ks], clip, math)
        for k, o in zip(ks, new):
            outs[k] = o
    new_p = treedef.unflatten([o[0] for o in outs])
    new_state = {
        "master": treedef.unflatten([o[1] for o in outs]),
        "m": treedef.unflatten([o[2] for o in outs]),
        "v": treedef.unflatten([o[3] for o in outs]),
        "count": count,
    }
    return new_p, new_state, gnorm


# ---------------------------------------------------------------------------
# LR schedules
# ---------------------------------------------------------------------------
def cosine_schedule(base_lr: float, warmup: int, total: int):
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = base_lr * step / max(warmup, 1)
        prog = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = 0.5 * base_lr * (1 + jnp.cos(jnp.pi * prog))
        return jnp.where(step < warmup, warm, cos)

    return lr
