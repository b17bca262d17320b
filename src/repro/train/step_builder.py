"""Build jit-able train/serve steps that realize a MemoryPlan.

This is where ProTrain's plan becomes an XLA program:

  * chunk placement  -> per-run parameter NamedShardings (persist = replicated
    over ZeRO axes; hbm = sharded; host = sharded + pinned_host memory kind)
  * n_buffer         -> gathered-weight save policy (re-gather in BWD or not)
  * block policies   -> per-position jax.checkpoint policies (keep / remat /
    host-offload / quantize-on-save): plan.block_policy(b) — the scalar
    n_swap/n_ckpt prefixes or the explicit act_policies vector — splits the
    layer stack into runs, one policy per run
  * microbatch       -> gradient-accumulation scan
  * host_optimizer   -> optimizer states of host chunks live in pinned_host
  * sync_mode        -> who owns the gradient reduction; lowered through the
    strategy objects in train/sync.py: "xla" (GSPMD inserts it; grad_compress
    applies wire numerics to the reduced grads) or "manual" (the whole step
    body runs under shard_map with in/out specs from dist/sharding.py and the
    compressed payload crosses the wire — DDP-style gather sync for
    replicated layouts, compressed reduce-scatter for ZeRO-sharded ones; see
    docs/architecture.md for the dataflows and eligibility rules)

The returned artifacts carry ShapeDtypeStruct specs for every input so the
multi-pod dry-run can ``.lower().compile()`` without allocating anything.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.plan import MemoryPlan
from repro.dist import collectives as COLL
from repro.dist import sharding as SH
from repro.models import kvcache as KV
from repro.models import model as M
from repro.models.layers import LAYER, ParamDef
from repro.optim import adam as OPT
from repro.train import sync as SYNC
from repro.train.losses import chunked_cross_entropy


# ---------------------------------------------------------------------------
# Plan -> run layout
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RunLayout:
    start: int  # first superblock repeat (== chunk index - 1)
    length: int
    placement: str  # persist | hbm | host
    buffered: bool
    act_policy: str  # none | checkpoint | swap | compress8 | compress16


def plan_runs(plan: MemoryPlan, n_repeats: int) -> list[RunLayout]:
    runs: list[RunLayout] = []
    for r in range(n_repeats):
        chunk = r + 1  # chunk 0 is the embedding
        key = (
            plan.chunk_placement(chunk),
            plan.chunk_buffered(chunk),
            plan.block_policy(min(r, plan.n_blocks - 1)),
        )
        if runs and (runs[-1].placement, runs[-1].buffered, runs[-1].act_policy) == key:
            runs[-1].length += 1
        else:
            runs.append(RunLayout(r, 1, *key))
    return runs


def _slice_run_defs(block_defs, length: int):
    """Stacked (R, ...) ParamDefs -> (length, ...) defs for one run."""
    return jax.tree.map(
        lambda d: dataclasses.replace(d, shape=(length,) + d.shape[1:]),
        block_defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def _per_repeat_defs(block_defs):
    return jax.tree.map(
        lambda d: dataclasses.replace(d, shape=d.shape[1:], axes=d.axes[1:]),
        block_defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


# ---------------------------------------------------------------------------
# Step artifacts
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class StepArtifacts:
    fn: Callable  # (state, batch) -> (state, metrics)   [or serve variants]
    state_specs: Any  # ShapeDtypeStruct pytree (with shardings)
    batch_specs: Any
    state_shardings: Any
    batch_shardings: Any
    plan: MemoryPlan
    runs: list[RunLayout]
    init: Callable | None = None  # (key) -> state, concrete (small models)

    def jit(self, donate: bool = True):
        """The step under ``jax.jit`` with the state's shardings pinned on
        both sides: the state a step returns then hits the program compiled
        for the state ``init`` made, and one compile serves the whole run."""
        if jax.default_backend() == "cpu" and any(
                s.memory_kind != "device" for s in jax.tree.leaves(self.state_shardings)):
            # The CPU backend returns host-placed outputs in device memory
            # and aborts when a donated host buffer is aliased into an
            # output, so host-placed state steps there unpinned and
            # undonated. On TPU the state stays in pinned_host, donated.
            return jax.jit(self.fn)
        return jax.jit(self.fn, in_shardings=(self.state_shardings, self.batch_shardings),
                       out_shardings=(self.state_shardings, None),
                       donate_argnums=(0,) if donate else ())

    def lower(self, donate: bool = True):
        return self.jit(donate).lower(self.state_specs, self.batch_specs)


def record_offload_inventory(state_specs, microbatch: int, host_plan: list) -> None:
    """Record the bytes one step moves over each device's host link, per
    direction, as the gauge ``offload.bytes_per_step{dir=fetch|writeback}``,
    and the part of them the layer-streamed update moves as
    ``offload.streamed_bytes_per_step{dir=...}``.

    The host-offloaded Adam update brings every host-placed fp32 master/m/v
    leaf to the device and sends it back once a step; host-placed bf16
    parameters are fetched by every microbatch's forward and written back
    once by the update. Counted from the state specs' memory kinds and
    per-device shard shapes; a parameter fetched again for recomputation
    is not counted. ``host_plan`` (the update's ``HostLeaf`` per parameter
    leaf) names the leaves that stream (``optim.adam.streams``): their
    states both ways, and their new parameters when those live on the
    host. Like ``sync.record_sync_inventory``, a no-op without an
    installed telemetry handle.
    """
    from repro import obs

    reg = obs.current_telemetry().registry

    def nbytes(s) -> int:
        return math.prod(s.sharding.shard_shape(s.shape)) * s.dtype.itemsize

    def on_host(s) -> bool:
        return s.sharding.memory_kind not in (None, "device")

    def host_bytes(tree) -> int:
        return sum(nbytes(s) for s in jax.tree.leaves(tree) if on_host(s))

    opt, params = host_bytes(state_specs["opt"]), host_bytes(state_specs["params"])
    reg.gauge("offload.bytes_per_step", dir="fetch").set(opt + params * microbatch)
    reg.gauge("offload.bytes_per_step", dir="writeback").set(opt + params)

    p_flat = jax.tree.leaves(state_specs["params"])
    states = zip(*(jax.tree.leaves(state_specs["opt"][k]) for k in ("master", "m", "v")))
    fetch = back = 0
    for p, h, st in zip(p_flat, host_plan, states):
        if OPT.streams(p, h):
            fetch += sum(map(nbytes, st))
            back += sum(map(nbytes, st)) + (nbytes(p) if on_host(p) else 0)
    reg.gauge("offload.streamed_bytes_per_step", dir="fetch").set(fetch)
    reg.gauge("offload.streamed_bytes_per_step", dir="writeback").set(back)


def record_param_fetch(param_specs, microbatch: int, runs: list[RunLayout]) -> None:
    """Record the bf16 parameter bytes one step fetches from host memory,
    per device, as the gauge ``offload.param_fetch_bytes_per_step``. Every
    microbatch's forward fetches each host-placed parameter: a run's one
    layer at a time (``models.model.gather_weights``, scope
    ``param_fetch``), the loss head's whole. The backward of a run that
    does not keep its fetched weights (``RunLayout.buffered`` False, the
    plan's ``n_buffer``) fetches its layers again. Counted from the specs'
    memory kinds and per-device shard shapes; a no-op without an installed
    telemetry handle."""
    from repro import obs

    total = 0
    for path, s in jax.tree_util.tree_flatten_with_path(param_specs)[0]:
        if s.sharding.memory_kind in (None, "device"):
            continue
        again = path[0].key == "runs" and not runs[path[1].idx].buffered
        total += (math.prod(s.sharding.shard_shape(s.shape)) * s.dtype.itemsize
                  * microbatch * (2 if again else 1))
    obs.current_telemetry().registry.gauge("offload.param_fetch_bytes_per_step").set(total)


def _stacked(d: ParamDef) -> bool:
    """A run's stacked block leaf: its leading axis is the layer axis."""
    return bool(d.axes) and d.axes[0] == LAYER


def _opt_placement(placement: str, plan: MemoryPlan) -> str:
    """Optimizer-state placement for a chunk placement."""
    if placement == "persist":
        return "zero1" if plan.zero1_persistent else "persist"
    return placement


def _opt_sharding(d: ParamDef, mesh, placement: str, plan: MemoryPlan) -> NamedSharding:
    op = _opt_placement(placement, plan)
    if op == "zero1":
        return SH.sharding_for(d, mesh, placement="hbm", dp_only=plan.dp_only)
    return SH.sharding_for(d, mesh, placement=op, dp_only=plan.dp_only)


def build_train_step(
    cfg: ModelConfig,
    plan: MemoryPlan,
    mesh,
    shape: ShapeConfig,
    *,
    adam: OPT.AdamConfig | None = None,
    attn_impl: str = "blockwise",
    ce_chunk: int = 2048,
    lr_schedule: Callable | None = None,
) -> StepArtifacts:
    adam = adam or OPT.AdamConfig()
    period = M.superblock_period(cfg)
    n_rep = M.num_repeats(cfg)
    runs_layout = plan_runs(plan, n_rep)
    defs = M.param_defs(cfg)
    head_chunk = plan.chunk_placement(plan.n_chunks - 1)
    embed_chunk = plan.chunk_placement(0)
    dp = plan.dp_only

    def param_place(pl: str, d: ParamDef | None = None) -> str:
        """A parameter's placement in a chunk placed ``pl``. ZeRO-Offload
        split: bf16 params stay in HBM; only opt states go host. A stacked
        leaf the host link cannot fetch one layer at a time (``[L, d]``
        norms) stays in HBM too."""
        if pl != "host":
            return pl
        if not plan.host_params or (d is not None and _stacked(d)
                                    and not SH.host_layer_sliceable(d)):
            return "hbm"
        return pl

    def place_tree(subtree_defs, pl: str):
        return jax.tree.map(
            lambda d: SH.sharding_for(d, mesh, placement=param_place(pl, d), dp_only=dp),
            subtree_defs, is_leaf=lambda x: isinstance(x, ParamDef))

    head_pchunk = param_place(head_chunk)
    embed_pchunk = param_place(embed_chunk)

    # --- parameter defs & shardings, organized by run ----------------------
    p_defs: dict[str, Any] = {
        "embed": defs["embed"],
        "final_norm": defs["final_norm"],
        "runs": [_slice_run_defs(defs["blocks"], r.length) for r in runs_layout],
    }
    if "head" in defs:
        p_defs["head"] = defs["head"]
    if "encoder" in defs:
        p_defs["encoder"] = defs["encoder"]

    p_shard: dict[str, Any] = {
        "embed": place_tree(defs["embed"], embed_chunk),
        "final_norm": place_tree(defs["final_norm"], head_chunk),
        "runs": [place_tree(p_defs["runs"][i], r.placement)
                 for i, r in enumerate(runs_layout)],
    }
    if "head" in defs:
        p_shard["head"] = place_tree(defs["head"], head_chunk)
    if "encoder" in defs:
        p_shard["encoder"] = place_tree(defs["encoder"], embed_chunk)

    # --- optimizer state shardings (fp32 master/m/v) ------------------------
    def opt_tree(fn_placement):
        out = {
            "embed": jax.tree.map(
                lambda d: fn_placement(d, embed_chunk), defs["embed"],
                is_leaf=lambda x: isinstance(x, ParamDef)),
            "final_norm": jax.tree.map(
                lambda d: fn_placement(d, head_chunk), defs["final_norm"],
                is_leaf=lambda x: isinstance(x, ParamDef)),
            "runs": [
                jax.tree.map(lambda d, _r=r: fn_placement(d, _r.placement), p_defs["runs"][i],
                             is_leaf=lambda x: isinstance(x, ParamDef))
                for i, r in enumerate(runs_layout)
            ],
        }
        if "head" in defs:
            out["head"] = jax.tree.map(lambda d: fn_placement(d, head_chunk), defs["head"],
                                       is_leaf=lambda x: isinstance(x, ParamDef))
        if "encoder" in defs:
            out["encoder"] = jax.tree.map(lambda d: fn_placement(d, embed_chunk), defs["encoder"],
                                          is_leaf=lambda x: isinstance(x, ParamDef))
        return out

    def fp32_def(d: ParamDef) -> ParamDef:
        return dataclasses.replace(d, dtype="float32")

    o_shard_one = opt_tree(lambda d, pl: _opt_sharding(d, mesh, pl, plan))
    o_defs_one = jax.tree.map(fp32_def, p_defs, is_leaf=lambda x: isinstance(x, ParamDef))
    opt_defs = {"master": o_defs_one, "m": o_defs_one, "v": o_defs_one}
    opt_shard = {"master": o_shard_one, "m": o_shard_one, "v": o_shard_one}

    # host-offloaded leaves: where each is updated (optim/adam.HostLeaf)
    def host_entry(d: ParamDef, pl: str):
        if pl != "host" or not plan.host_optimizer:
            return None
        df = fp32_def(d)
        return OPT.HostLeaf(
            SH.sharding_for(d, mesh, placement=param_place("host", d), dp_only=dp),
            SH.sharding_for(df, mesh, placement="host", dp_only=dp),
            SH.sharding_for(df, mesh, placement="hbm", dp_only=dp),
            stacked=_stacked(d),
        )

    host_plan_flat = [
        host_entry(d, pl)
        for d, pl in zip(
            jax.tree.leaves(p_defs, is_leaf=lambda x: isinstance(x, ParamDef)),
            jax.tree.leaves(
                opt_tree(lambda d, pl: pl), is_leaf=lambda x: isinstance(x, str)
            ),
        )
    ]

    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    state_specs = {
        "params": SH.tree_specs(p_defs, p_shard),
        "opt": {
            **{k: SH.tree_specs(opt_defs[k], opt_shard[k]) for k in ("master", "m", "v")},
            "count": scalar,
        },
        "step": scalar,
    }
    state_shardings = {
        "params": p_shard,
        "opt": {**opt_shard, "count": NamedSharding(mesh, P())},
        "step": NamedSharding(mesh, P()),
    }

    # --- batch specs ---------------------------------------------------------
    bsh = SH.batch_sharding(mesh, 2, dp_only=dp)
    gb, sl = shape.global_batch, shape.seq_len
    batch_specs: dict[str, Any] = {
        "tokens": jax.ShapeDtypeStruct((gb, sl), jnp.int32, sharding=bsh),
        "labels": jax.ShapeDtypeStruct((gb, sl), jnp.int32, sharding=bsh),
    }
    bsh3 = SH.batch_sharding(mesh, 3, dp_only=dp)
    if cfg.kind == "encdec":
        batch_specs["frames"] = jax.ShapeDtypeStruct(
            (gb, sl, cfg.d_model), jnp.dtype(cfg.dtype), sharding=bsh3
        )
    if cfg.frontend == "vision_patches":
        n_patch = min(1024, sl)
        batch_specs["patches"] = jax.ShapeDtypeStruct(
            (gb, n_patch, cfg.d_model), jnp.dtype(cfg.dtype), sharding=bsh3
        )
    batch_shardings = jax.tree.map(lambda s: s.sharding, batch_specs,
                                   is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    # --- gather specs per run (point-of-use all-gather) ---------------------
    per_rep = _per_repeat_defs(defs["blocks"])
    gather_specs = [
        SH.tree_gather_shardings(defs["blocks"], mesh,
                                 persistent=r.placement == "persist", dp_only=dp)
        for r in runs_layout
    ]
    enc_gather = None
    if "encoder" in defs:
        enc_gather = SH.tree_gather_shardings(
            defs["encoder"]["blocks"], mesh, persistent=embed_chunk == "persist",
            dp_only=dp,
        )

    # Non-run parameter groups (embed / final_norm / head / encoder norm) need
    # an explicit device fetch when host-placed (and an explicit gather point
    # for the sharded head); runs handle this inside gather_weights.
    def _fetch_specs(subtree_defs, placement, force=False):
        if placement != "host" and not force:
            return None
        return jax.tree.map(lambda d: SH.gather_sharding(d, mesh, dp_only=dp), subtree_defs,
                            is_leaf=lambda x: isinstance(x, ParamDef))

    fetch_specs = {
        "embed": _fetch_specs(defs["embed"], embed_pchunk),
        "final_norm": _fetch_specs(defs["final_norm"], head_pchunk),
    }
    if "head" in defs:
        fetch_specs["head"] = _fetch_specs(defs["head"], head_pchunk,
                                           force=head_pchunk != "persist")
    if "encoder" in defs:
        fetch_specs["encoder_final_norm"] = _fetch_specs(
            defs["encoder"]["final_norm"], embed_pchunk)

    def fetch(params):
        out = dict(params)
        for key in ("embed", "final_norm", "head"):
            spec = fetch_specs.get(key)
            if spec is not None and key in out:
                out[key] = jax.tree.map(jax.device_put, out[key], spec)
        if fetch_specs.get("encoder_final_norm") is not None:
            enc = dict(out["encoder"])
            enc["final_norm"] = jax.tree.map(
                jax.device_put, enc["final_norm"], fetch_specs["encoder_final_norm"]
            )
            out["encoder"] = enc
        return out

    sharder = SH.make_activation_sharder(mesh, plan)

    def make_runs(params, full: bool = False) -> list[M.Run]:
        """``full=True`` (manual sync): params were gathered to full leaves
        before the loss, so every run behaves persistent — no point-of-use
        device_put gathers (they cannot appear inside a shard_map body)."""
        return [
            M.Run(
                params=params["runs"][i],
                n_repeats=r.length,
                act_policy=r.act_policy,
                buffered=True if full else r.buffered,
                persistent=True if full else r.placement == "persist",
                host=not full and param_place(r.placement) == "host",
                gather_specs=None if full else gather_specs[i],
                ckpt_group=plan.ckpt_group,
            )
            for i, r in enumerate(runs_layout)
        ]

    # sharding for the CE head-grad accumulator (see losses.py): matches the
    # head weight as it enters the loss (gathered over ZeRO, sharded over TP)
    zero_axes = SH.batch_axes(mesh, dp)
    tp_axis = None if dp else ("model" if "model" in mesh.axis_names else None)
    if cfg.tie_embeddings:
        w_acc_sharding = NamedSharding(mesh, P(zero_axes or None, tp_axis))
    else:
        w_acc_sharding = NamedSharding(mesh, P(None, tp_axis))

    def make_loss_fn(act_sharder, w_acc, full: bool = False):
        """Loss closure; the manual path re-instantiates it with an identity
        activation sharder, no CE-accumulator constraint (NamedShardings
        cannot name axes that are Manual inside a shard_map body), and
        ``full=True``: params arrive pre-gathered to full leaves, so the
        device_put-based fetch/gather machinery is bypassed entirely."""

        @jax.named_scope("model")  # forward: jvp(model); backward: transpose(jvp(model))
        def loss_fn(params, batch):
            M.set_activation_sharder(act_sharder)
            fparams = params if full else fetch(params)
            if not full and plan.overlap:
                # overlap the loss-head fetches with the layer scan: the
                # final_norm/head device_puts (host upload and/or ZeRO
                # gather) are consumed only after the scan, so left alone
                # XLA may sink them to the loss head and pay their latency
                # serially. Bundling them with the embed subtree orders the
                # fetches at program start — in flight during the whole
                # forward — without delaying the scan (which reads only the
                # un-barriered run params).
                keys = [k for k in ("final_norm", "head")
                        if fetch_specs.get(k) is not None and k in fparams]
                if keys:
                    bundled, _ = jax.lax.optimization_barrier(
                        ({k: fparams[k] for k in keys}, fparams["embed"]))
                    fparams = {**fparams, **bundled}
            h, aux = M.forward(
                fparams, batch, cfg, runs=make_runs(params, full=full),
                attn_impl=attn_impl,
                encoder_gather_specs=None if full else enc_gather,
            )
            from repro.models.layers import apply_norm

            h = M.shard_act(h, "enter")  # SP: back to batch-only for the CE scan
            h = apply_norm(fparams["final_norm"], h, cfg.norm, cfg.norm_eps)
            w = fparams["embed"]["tok"].T if cfg.tie_embeddings else fparams["head"]["w"]
            loss = chunked_cross_entropy(
                h, w, batch["labels"], ce_chunk=ce_chunk, w_acc_sharding=w_acc
            )
            return loss + aux.astype(jnp.float32), loss

        return loss_fn

    loss_fn = make_loss_fn(sharder, w_acc_sharding)

    def make_lazy_loss_fn(strategy):
        """Manual "zero3" loss closure: per-chunk lazy gather hooks instead
        of a pre-gathered param tree. Sharded leaves route through
        ``dist.collectives.gather_param_lazy`` — run (block) leaves inside
        the layer scan (one chunk's full weights at a time, remat policy
        deciding FWD->BWD buffering per the plan's ``n_buffer``), non-run
        groups (embed / head / encoder — each its own chunk) at their point
        of use. The EF residual tree rides along as a loss *input* whose
        "gradient" is the new residual (see gather_param_lazy)."""
        axes, compress = strategy.axes, plan.grad_compress
        leafs_tree = SYNC.leaf_sync_tree(state_specs["params"], axes)
        _is_ls = lambda x: isinstance(x, SYNC.LeafSync)  # noqa: E731

        def per_repeat_ls(ls_tree):
            # stacked run leaves carry the LAYER axis first; the scan slices
            # it off, so the per-repeat shard dim is the stacked dim - 1
            return jax.tree.map(
                lambda ls: SYNC.LeafSync(None if ls.dim is None else ls.dim - 1),
                ls_tree, is_leaf=_is_ls)

        def subtree_gather(pp, epp, ls_sub, name=False, anchor=None):
            flat_w, td = jax.tree.flatten(pp)
            flat_ls = td.flatten_up_to(ls_sub)
            flat_e = (td.flatten_up_to(epp) if epp is not None
                      else [None] * len(flat_w))
            out = []
            for w, ls, e in zip(flat_w, flat_ls, flat_e):
                if ls.dim is None:
                    out.append(w)
                    continue
                g = COLL.gather_param_lazy(w, e, axes, ls.dim, compress,
                                           anchor=anchor)
                out.append(checkpoint_name(g, M.GATHERED_W) if name else g)
            return td.unflatten(out)

        def make_zero3_runs(params, ef):
            out = []
            for i, r in enumerate(runs_layout):
                if r.placement == "persist":
                    out.append(M.Run(
                        params=params["runs"][i], n_repeats=r.length,
                        act_policy=r.act_policy, buffered=True,
                        persistent=True, gather_specs=None,
                        ckpt_group=plan.ckpt_group))
                    continue
                ls_rep = per_repeat_ls(leafs_tree["runs"][i])
                out.append(M.Run(
                    params=params["runs"][i], n_repeats=r.length,
                    act_policy=r.act_policy, buffered=r.buffered,
                    persistent=False, gather_specs=None,
                    ckpt_group=plan.ckpt_group,
                    lazy_gather=lambda pp, epp, j, anchor=None,
                    _ls=ls_rep: subtree_gather(
                        pp, epp, _ls[f"pos{j}"], name=True, anchor=anchor),
                    ef=None if ef is None else ef["runs"][i],
                    # double-buffered gather prefetch (model.apply_runs):
                    # active only for buffered runs under an overlap plan
                    # with n_buffer >= 2 — everything else keeps the serial
                    # inline gather
                    prefetch=plan.gather_prefetch_depth >= 2,
                ))
            return out

        @jax.named_scope("model")
        def lazy_loss(params, ef, batch):
            M.set_activation_sharder(lambda x, kind="bsd": x)
            fparams = dict(params)
            for key in ("embed", "final_norm", "head", "encoder"):
                if key in fparams:
                    fparams[key] = subtree_gather(
                        fparams[key], None if ef is None else ef[key],
                        leafs_tree[key])
            h, aux = M.forward(
                fparams, batch, cfg, runs=make_zero3_runs(params, ef),
                attn_impl=attn_impl, encoder_gather_specs=None,
            )
            from repro.models.layers import apply_norm

            h = M.shard_act(h, "enter")
            h = apply_norm(fparams["final_norm"], h, cfg.norm, cfg.norm_eps)
            w = fparams["embed"]["tok"].T if cfg.tie_embeddings else fparams["head"]["w"]
            loss = chunked_cross_entropy(
                h, w, batch["labels"], ce_chunk=ce_chunk, w_acc_sharding=None
            )
            return loss + aux.astype(jnp.float32), loss

        return lazy_loss

    # gradient shardings: same partitioning as params, but always in device
    # memory (host-chunk grads are reduce-scattered on device, then the
    # optimizer round-trips the states). Without this constraint the transpose
    # of the point-of-use gather leaves cotangents unsharded and XLA happily
    # materializes replicated full-model gradients.
    g_shard = jax.tree.map(
        lambda s: NamedSharding(s.mesh, s.spec), p_shard,
        is_leaf=lambda x: isinstance(x, NamedSharding),
    )

    def pin_grads(grads):
        return jax.tree.map(jax.lax.with_sharding_constraint, grads, g_shard)

    # --- gradient sync: strategy object owns the control flow ---------------
    # train/sync.py picks the pipeline for (sync_mode, layout kind) — raising
    # for structurally-ineligible manual plans even on 1-device meshes (so
    # code first exercised locally fails the same way it would deployed) and
    # falling back to the local-math xla strategy on one device. The EF
    # residual layout is the strategy's to define: replicated-grad residuals
    # are stacked per-device, ZeRO-shard residuals live in the gradient's own
    # sharded layout.
    tp_degree = SH.mesh_sizes(mesh).get("model", 1)
    strategy = SYNC.make_strategy(plan, mesh, tp_degree)
    # telemetry (host-side, no-op without an installed handle): the step's
    # static collective wire-byte and host-link byte inventories — both move
    # inside jit, so they are recorded from the leaf specs, not counted at
    # runtime
    SYNC.record_sync_inventory(strategy, state_specs["params"], plan.microbatch)
    record_offload_inventory(state_specs, plan.microbatch, host_plan_flat)
    record_param_fetch(state_specs["params"], plan.microbatch, runs_layout)
    compress = plan.grad_compress
    ef_layout = strategy.ef_state(o_defs_one, g_shard)
    if ef_layout is not None:
        state_specs["ef"], state_shardings["ef"] = ef_layout

    @jax.named_scope("optimizer")
    def apply_update(state, grads, total, ce, new_ef, metrics, *,
                     host_plan, repin, grad_norm=None):
        """Optimizer update + new-state/metrics assembly, shared tail of both
        step bodies (manual passes host_plan=None, repin=False: no host
        chunks exist under manual eligibility, and device_put cannot appear
        inside a shard_map body; it supplies grad_norm because its shard-
        local gradient leaves need a cross-device norm for clipping)."""
        lr = lr_schedule(state["step"]) if lr_schedule else adam.lr
        new_params, new_opt, gnorm = OPT.adam_update(
            state["params"], grads, state["opt"], adam, lr,
            host_plan=host_plan, grad_norm=grad_norm,
        )
        if repin:  # keep shardings/memory kinds pinned through the update
            new_params = jax.tree.map(jax.device_put, new_params, p_shard)
        new_state = {"params": new_params, "opt": new_opt, "step": state["step"] + 1}
        if compress == "int8_ef":
            new_state["ef"] = new_ef
        metrics.update({"loss": total, "ce": ce, "grad_norm": gnorm, "lr": jnp.asarray(lr)})
        return new_state, metrics

    if strategy.manual_active:
        step_fn = strategy.build_step_fn(
            loss=make_loss_fn(lambda x, kind="bsd": x, None, full=True),
            lazy_loss=(make_lazy_loss_fn(strategy)
                       if strategy.kind == "zero3" else None),
            apply_update=apply_update,
            state_specs=state_specs,
            batch_specs=batch_specs,
            global_batch=shape.global_batch,
            microbatch=plan.microbatch,
        )
    else:
        def step_fn(state, batch):
            def micro_grad(mb_batch, ef_c):
                (total, ce), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(state["params"], mb_batch)
                return g, total, ce, ef_c

            grads, total, ce, _ = SYNC.accumulate_grads(
                micro_grad, batch, plan.microbatch, None, state["params"],
                pin=pin_grads)
            grads, new_ef, metrics = strategy.finalize_grads(
                grads, state.get("ef"), pin_grads, g_shard)
            return apply_update(state, grads, total, ce, new_ef, metrics,
                                host_plan=host_plan_flat, repin=True)

    def init(key):
        from repro.models.layers import init_tree

        params = jax.tree.map(jax.device_put, init_tree(p_defs, key), p_shard)

        # Each optimizer leaf is made and placed on its own, so device
        # memory never holds the whole fp32 state at once (host-placed
        # states exceed HBM), and as a fresh buffer: an fp32 param's
        # ``astype`` would alias its master copy and break donation.
        def master(p, s):
            return jax.device_put(jnp.array(p, jnp.float32, copy=True), s)

        def zeros(p, s):
            return jax.device_put(jnp.zeros(p.shape, jnp.float32), s)

        opt = {
            "master": jax.tree.map(master, params, opt_shard["master"]),
            "m": jax.tree.map(zeros, params, opt_shard["m"]),
            "v": jax.tree.map(zeros, params, opt_shard["v"]),
            "count": jax.device_put(jnp.zeros((), jnp.int32), state_shardings["opt"]["count"]),
        }
        state = {"params": params, "opt": opt,
                 "step": jax.device_put(jnp.zeros((), jnp.int32), state_shardings["step"])}
        if compress == "int8_ef":
            # zeros matching state_specs["ef"] — param-shaped replicated for
            # the xla path, stacked per-device for manual (see above)
            state["ef"] = jax.tree.map(
                lambda s: jax.device_put(jnp.zeros(s.shape, s.dtype), s.sharding),
                state_specs["ef"],
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
            )
        return state

    return StepArtifacts(
        fn=step_fn,
        state_specs=state_specs,
        batch_specs=batch_specs,
        state_shardings=state_shardings,
        batch_shardings=batch_shardings,
        plan=plan,
        runs=runs_layout,
        init=init,
    )


# ---------------------------------------------------------------------------
# Serving steps (prefill / decode)
# ---------------------------------------------------------------------------
def build_serve_params(cfg: ModelConfig, plan: MemoryPlan, mesh):
    """Serving keeps weights only; plan decides persist vs gathered chunks."""
    defs = M.param_defs(cfg)
    n_rep = M.num_repeats(cfg)
    runs_layout = plan_runs(plan, n_rep)
    head_chunk = plan.chunk_placement(plan.n_chunks - 1)
    embed_chunk = plan.chunk_placement(0)
    dp = plan.dp_only
    # serving has no optimizer states: host placement == weights on host
    head_pchunk, embed_pchunk = head_chunk, embed_chunk
    p_defs = {
        "embed": defs["embed"],
        "final_norm": defs["final_norm"],
        # serving keeps the canonical stacked layout (single run per placement
        # is meaningless without buffering semantics) but honors placement
        "blocks": defs["blocks"],
    }
    blocks_placement = plan.chunk_placement(1)
    p_shard = {
        "embed": SH.tree_shardings(defs["embed"], mesh, placement=embed_pchunk, dp_only=dp),
        "final_norm": SH.tree_shardings(defs["final_norm"], mesh, placement=head_pchunk, dp_only=dp),
        "blocks": SH.tree_shardings(defs["blocks"], mesh, placement=blocks_placement),
    }
    if "head" in defs:
        p_defs["head"] = defs["head"]
        p_shard["head"] = SH.tree_shardings(defs["head"], mesh, placement=head_pchunk, dp_only=dp)
    if "encoder" in defs:
        p_defs["encoder"] = defs["encoder"]
        p_shard["encoder"] = SH.tree_shardings(defs["encoder"], mesh, placement=embed_pchunk, dp_only=dp)
    gather = SH.tree_gather_shardings(defs["blocks"], mesh,
                                      persistent=blocks_placement == "persist")

    def _fs(subtree_defs, placement, force=False):
        if placement != "host" and not force:
            return None
        return jax.tree.map(lambda d: SH.gather_sharding(d, mesh), subtree_defs,
                            is_leaf=lambda x: isinstance(x, ParamDef))

    fetch_specs = {
        "embed": _fs(defs["embed"], embed_chunk),
        "final_norm": _fs(defs["final_norm"], head_chunk),
    }
    if "head" in defs:
        fetch_specs["head"] = _fs(defs["head"], head_chunk, force=head_chunk != "persist")

    def fetch(params):
        out = dict(params)
        for key in ("embed", "final_norm", "head"):
            spec = fetch_specs.get(key)
            if spec is not None and key in out:
                out[key] = jax.tree.map(jax.device_put, out[key], spec)
        return out

    return p_defs, p_shard, gather, fetch


def _serve_cache_layout(cfg: ModelConfig, plan: MemoryPlan, mesh,
                        shape: ShapeConfig, paging):
    """Shared decode/prefill cache layout for a serve plan.

    Returns ``(cache_sds, cache_shard, kv_io, host_pin, tok_batch_ax)``:
    the sharded cache ShapeDtypeStructs, their sharding tree, the PagedKV
    hook (None for resident layouts), the cold-leaf re-pin tree (paged
    layouts re-emit cold leaves in device memory out of the repeat scan),
    and the batch axis tokens shard over."""
    from repro.compat import host_memory_kind

    bsz = shape.global_batch
    if paging is None:
        cache_spec_tree = KV.cache_specs(cfg, bsz, shape.seq_len)
    else:
        from repro.serve.paging import paged_cache_specs

        cache_spec_tree = paged_cache_specs(cfg, bsz, shape.seq_len, paging)
    ba = SH.batch_axes(mesh)
    tp = "model" if "model" in mesh.axis_names else None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def fits(dim: int, axes) -> bool:
        if axes is None:
            return False
        names = (axes,) if isinstance(axes, str) else axes
        n = 1
        for a in names:
            n *= sizes[a]
        return dim % n == 0 and dim >= n

    def cache_sharding(name: str, s: jax.ShapeDtypeStruct) -> NamedSharding:
        """Attention caches (R,B,S,kv,hd): batch over ZeRO axes when divisible;
        the sequence dim takes TP (and absorbs the ZeRO axes too for
        single-sequence long-context decode, where batch cannot shard).
        Paged leaves reuse the same geometry — hot rings and cold pages are
        slot-axis slices of the resident layout — with cold pinned to the
        platform's host memory kind."""
        shp = s.shape
        batch_ax = ba if fits(shp[1], ba) else None
        if name in ("k", "v", "xk", "xv", "k_hot", "v_hot", "k_cold", "v_cold"):
            seq_ax = tp if batch_ax is not None else tuple(
                a for a in ((ba or ()) + ((tp,) if tp else ())) if a
            ) or None
            if not fits(shp[2], seq_ax):
                seq_ax = tp if fits(shp[2], tp) else None
            spec = P(None, batch_ax, seq_ax, None, None)
            if name in ("k_cold", "v_cold"):
                return NamedSharding(mesh, spec, memory_kind=host_memory_kind(mesh))
            return NamedSharding(mesh, spec)
        if name == "conv":  # (R, B, K, conv_dim)
            ch = tp if fits(shp[3], tp) else None
            return NamedSharding(mesh, P(None, batch_ax, None, ch))
        if name == "ssm":  # (R, B, H, P, N)
            h = tp if fits(shp[2], tp) else None
            return NamedSharding(mesh, P(None, batch_ax, h, None, None))
        raise KeyError(name)

    cache_shard = {
        pos: {name: cache_sharding(name, s) for name, s in entry.items()}
        for pos, entry in cache_spec_tree.items()
    }
    tok_batch_ax = ba if fits(bsz, ba) else None
    cache_sds = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        cache_spec_tree, cache_shard,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )

    kv_io = None
    host_pin = None
    if paging is not None:
        from repro.serve.paging import PagedKV

        # one fetched page, per repeat: (B, P, n_kv, hd), batch-sharded,
        # device memory — the h2d target of the cold-page device_put
        page_batch_ax = ba if fits(bsz, ba) else None
        fetch_sharding = NamedSharding(mesh, P(page_batch_ax, None, None, None))
        kv_io = PagedKV(paging, fetch_sharding=fetch_sharding)
        # the repeat scan re-emits cold leaves in device memory; pin them back
        host_pin = {
            pos: {name: sh for name, sh in entry.items()
                  if name in ("k_cold", "v_cold")}
            for pos, entry in cache_shard.items()
        }
    return cache_sds, cache_shard, kv_io, host_pin, tok_batch_ax


def _repin_cold(new_cache: dict, host_pin) -> dict:
    if host_pin is None:
        return new_cache
    return {
        pos: {
            name: (jax.device_put(leaf, host_pin[pos][name])
                   if name in host_pin[pos] else leaf)
            for name, leaf in entry.items()
        }
        for pos, entry in new_cache.items()
    }


def _resolve_paging(cfg: ModelConfig, plan: MemoryPlan, shape: ShapeConfig, paging):
    """Derive the PagingSpec a serve plan encodes when none is passed."""
    if paging is None and plan.cold_kv_pages > 0:
        from repro.core.serve_plan import paging_from_plan

        paging = paging_from_plan(cfg, shape, plan)
    return paging


def build_decode_step(cfg: ModelConfig, plan: MemoryPlan, mesh, shape: ShapeConfig,
                      *, paging=None, per_slot_pos: bool = False) -> StepArtifacts:
    """Decode step for a serve plan.

    ``paging`` (a ``serve.paging.PagingSpec``) switches the attention caches
    to the paged layout: hot rings stay in HBM, the canonical cold pages live
    in host memory (``compat.host_memory_kind``), and the step reconstructs
    each layer's cache page-wise inside the repeat scan through the
    ``PagedKV`` kv_io hook — the serving twin of ``Run.lazy_gather``. When
    ``plan.cold_kv_pages > 0`` and no spec is passed, one is derived via
    ``serve_plan.paging_from_plan``. ``per_slot_pos`` widens the ``pos``
    input to (B,) so every batch slot decodes at its own position
    (continuous batching), and adds an optional ``active`` (B,) bool batch
    input masking cache writes of non-participating slots (the engine passes
    it when some slots are mid-chunked-prefill)."""
    paging = _resolve_paging(cfg, plan, shape, paging)
    p_defs, p_shard, gather, fetch = build_serve_params(cfg, plan, mesh)
    sharder = SH.make_activation_sharder(mesh, plan)
    bsz = shape.global_batch

    cache_sds, cache_shard, kv_io, host_pin, tok_batch_ax = _serve_cache_layout(
        cfg, plan, mesh, shape, paging)

    state_specs = {
        "params": SH.tree_specs(p_defs, p_shard),
        "cache": cache_sds,
    }
    pos_spec = (jax.ShapeDtypeStruct((bsz,), jnp.int32) if per_slot_pos
                else jax.ShapeDtypeStruct((), jnp.int32))
    batch_specs = {
        "tokens": jax.ShapeDtypeStruct(
            (bsz, 1), jnp.int32, sharding=NamedSharding(mesh, P(tok_batch_ax, None))
        ),
        "pos": pos_spec,
    }
    if per_slot_pos:
        batch_specs["active"] = jax.ShapeDtypeStruct((bsz,), jnp.bool_)

    def step_fn(state, batch):
        M.set_activation_sharder(sharder)
        fparams = fetch(state["params"])
        logits, new_cache = KV.decode_step(
            fparams, state["cache"], batch["tokens"], batch["pos"], cfg,
            gather_specs=gather, kv_io=kv_io, active=batch.get("active"),
        )
        new_cache = _repin_cold(new_cache, host_pin)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return {"params": state["params"], "cache": new_cache}, next_tok

    return StepArtifacts(
        fn=step_fn,
        state_specs=state_specs,
        batch_specs=batch_specs,
        state_shardings={"params": p_shard, "cache": cache_shard},
        batch_shardings=None,
        plan=plan,
        runs=plan_runs(plan, M.num_repeats(cfg)),
    )


def build_prefill_step(cfg: ModelConfig, plan: MemoryPlan, mesh, shape: ShapeConfig,
                       *, chunk: int | None = None, paging=None) -> StepArtifacts:
    """Prefill for a serve plan, in one of two forms.

    ``chunk=None`` (legacy): a stateless full-sequence parallel forward
    returning last-position logits — the shape/fidelity dryrun path, which
    never touches a decode cache.

    ``chunk=C``: the cache-ingesting chunked prefill the serving engine
    admits requests through (serve/prefill.py). State and shardings match
    ``build_decode_step`` exactly (params + decode cache, paged or resident),
    so one state dict threads through both programs; the batch is a (B, C)
    token block with per-slot start positions and per-slot token counts.
    Feeding the same tokens through this step and through token-by-token
    decode replay produces bitwise-identical caches and logits (the per-token
    ops are the same; tests/test_serve_prefill.py asserts diff == 0.0).
    """
    if chunk is not None:
        return _build_chunked_prefill_step(cfg, plan, mesh, shape,
                                           chunk=chunk, paging=paging)
    p_defs, p_shard, gather, fetch = build_serve_params(cfg, plan, mesh)
    sharder = SH.make_activation_sharder(mesh, plan)
    gb, sl = shape.global_batch, shape.seq_len
    bsh = SH.batch_sharding(mesh, 2)
    batch_specs: dict[str, Any] = {
        "tokens": jax.ShapeDtypeStruct((gb, sl), jnp.int32, sharding=bsh),
    }
    if cfg.kind == "encdec":
        batch_specs["frames"] = jax.ShapeDtypeStruct(
            (gb, sl, cfg.d_model), jnp.dtype(cfg.dtype), sharding=SH.batch_sharding(mesh, 3)
        )
    if cfg.frontend == "vision_patches":
        batch_specs["patches"] = jax.ShapeDtypeStruct(
            (gb, min(1024, sl), cfg.d_model), jnp.dtype(cfg.dtype),
            sharding=SH.batch_sharding(mesh, 3),
        )

    def step_fn(params, batch):
        M.set_activation_sharder(sharder)
        params = fetch(params)
        runs = [
            M.Run(params=params["blocks"], n_repeats=M.num_repeats(cfg),
                  act_policy="none", buffered=True,
                  persistent=plan.chunk_placement(1) == "persist", gather_specs=gather)
        ]
        h, _ = M.forward(params, batch, cfg, runs=runs)
        from repro.models.layers import apply_norm

        h = apply_norm(params["final_norm"], h[:, -1:], cfg.norm, cfg.norm_eps)
        w = params["embed"]["tok"].T if cfg.tie_embeddings else params["head"]["w"]
        return (h @ w)[:, 0]  # (B, V) next-token logits

    return StepArtifacts(
        fn=step_fn,
        state_specs=SH.tree_specs(p_defs, p_shard),
        batch_specs=batch_specs,
        state_shardings=p_shard,
        batch_shardings=None,
        plan=plan,
        runs=plan_runs(plan, M.num_repeats(cfg)),
    )


def _build_chunked_prefill_step(cfg: ModelConfig, plan: MemoryPlan, mesh,
                                shape: ShapeConfig, *, chunk: int,
                                paging=None) -> StepArtifacts:
    from repro.serve.prefill import prefill_chunk

    paging = _resolve_paging(cfg, plan, shape, paging)
    p_defs, p_shard, gather, fetch = build_serve_params(cfg, plan, mesh)
    sharder = SH.make_activation_sharder(mesh, plan)
    bsz = shape.global_batch

    cache_sds, cache_shard, kv_io, host_pin, tok_batch_ax = _serve_cache_layout(
        cfg, plan, mesh, shape, paging)

    state_specs = {
        "params": SH.tree_specs(p_defs, p_shard),
        "cache": cache_sds,
    }
    batch_specs = {
        "tokens": jax.ShapeDtypeStruct(
            (bsz, chunk), jnp.int32,
            sharding=NamedSharding(mesh, P(tok_batch_ax, None))),
        "pos": jax.ShapeDtypeStruct((bsz,), jnp.int32),
        "n_tok": jax.ShapeDtypeStruct((bsz,), jnp.int32),
    }

    def step_fn(state, batch):
        M.set_activation_sharder(sharder)
        fparams = fetch(state["params"])
        last, new_cache = prefill_chunk(
            fparams, state["cache"], batch["tokens"], batch["pos"],
            batch["n_tok"], cfg, gather_specs=gather, kv_io=kv_io,
        )
        new_cache = _repin_cold(new_cache, host_pin)
        next_tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        return {"params": state["params"], "cache": new_cache}, next_tok

    return StepArtifacts(
        fn=step_fn,
        state_specs=state_specs,
        batch_specs=batch_specs,
        state_shardings={"params": p_shard, "cache": cache_shard},
        batch_shardings=None,
        plan=plan,
        runs=plan_runs(plan, M.num_repeats(cfg)),
    )
