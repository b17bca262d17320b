"""Mixed-precision Adam with fp32 master weights (paper §2: fp16/bf16 compute,
fp32 updates). Pure-pytree implementation (no optax dependency) so optimizer
state sharding/placement stays fully under the planner's control.

The Pallas ``fused_adam`` kernel (kernels/fused_adam.py) provides the fused
single-pass update for TPU; the jnp path here is the portable reference and
what the CPU tests run.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


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


def clip_by_global_norm(grads, max_norm: float, norm: jax.Array | None = None):
    """``norm`` overrides the locally-computed global norm — the manual ZeRO
    sync path holds shard-sized gradient leaves, so the true global norm
    needs a cross-device reduction the caller owns (train/sync.py)."""
    norm = global_norm(grads) if norm is None else norm
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads), norm


def _update_leaf(p, g, master, m, v, *, cfg: AdamConfig, lr, bc1, bc2, fused: bool,
                 host: tuple | None = None):
    """One Adam leaf update. ``host`` = (param_shard, opt_host_shard,
    opt_dev_shard) for host-offloaded chunks: optimizer states round-trip
    device<->host (the TPU adaptation of the paper's CPU Adam — XLA schedules
    the DMA off the critical path; see DESIGN.md)."""
    if host is not None:
        p_shard, h_shard, d_shard = host
        master = jax.device_put(master, d_shard)
        m = jax.device_put(m, d_shard)
        v = jax.device_put(v, d_shard)
    if fused and host is None:
        from repro.kernels import fused_adam_update

        return fused_adam_update(
            p, g, master, m, v, lr=lr, b1=cfg.b1, b2=cfg.b2, eps=cfg.eps,
            weight_decay=cfg.weight_decay, bc1=bc1, bc2=bc2,
        )
    gf = g.astype(jnp.float32)
    m_new = cfg.b1 * m + (1 - cfg.b1) * gf
    v_new = cfg.b2 * v + (1 - cfg.b2) * gf * gf
    m_hat = m_new / bc1
    v_hat = v_new / bc2
    upd = m_hat / (jnp.sqrt(v_hat) + cfg.eps)
    if cfg.weight_decay:
        upd = upd + cfg.weight_decay * master
    master_new = master - lr * upd
    p_new = master_new.astype(p.dtype)
    if host is not None:
        p_new = jax.device_put(p_new, p_shard)
        master_new = jax.device_put(master_new, h_shard)
        m_new = jax.device_put(m_new, h_shard)
        v_new = jax.device_put(v_new, h_shard)
    return p_new, master_new, m_new, v_new


def adam_update(params, grads, opt_state, cfg: AdamConfig, lr: float | jax.Array,
                host_plan: list | None = None, grad_norm: jax.Array | None = None):
    """Returns (new_params, new_opt_state, grad_norm).

    ``host_plan``: optional flat list aligned with the flattened params; each
    entry is None or (param_sharding, opt_host_sharding, opt_device_sharding)
    marking a host-offloaded leaf. ``grad_norm``: externally-computed global
    norm for clipping (manual ZeRO sync: leaves are device-local shards)."""
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip, norm=grad_norm)
    count = opt_state["count"] + 1
    bc1 = 1 - cfg.b1 ** count.astype(jnp.float32)
    bc2 = 1 - cfg.b2 ** count.astype(jnp.float32)

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_master = treedef.flatten_up_to(opt_state["master"])
    flat_m = treedef.flatten_up_to(opt_state["m"])
    flat_v = treedef.flatten_up_to(opt_state["v"])
    if host_plan is None:
        host_plan = [None] * len(flat_p)

    outs = [
        _update_leaf(p, g, ma, m, v, cfg=cfg, lr=lr, bc1=bc1, bc2=bc2,
                     fused=cfg.use_fused_kernel, host=h)
        for p, g, ma, m, v, h in zip(flat_p, flat_g, flat_master, flat_m, flat_v, host_plan)
    ]
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
