"""Gradient-sync strategy layer: who owns the reduce, and how it lowers.

``train/step_builder.py`` used to inline all sync control flow in its two
step bodies; this module owns it instead. A strategy object encapsulates one
``(sync_mode, layout-kind)`` pipeline:

  * ``XlaSync`` — ``sync_mode="xla"`` (and the 1-device manual fallback):
    GSPMD inserts the reduce implied by the shardings; ``finalize_grads``
    applies the compressed collective's wire *numerics* (int8+EF / bf16) to
    the already-reduced gradients. Wire bytes unchanged (calibrated factor
    ~1.0).
  * ``ManualSync`` — ``sync_mode="manual"`` on a multi-device mesh: the whole
    step body runs under ``shard_map`` and the only collectives in the
    program are the ones ``dist/collectives.py`` emits, so compressed
    payloads really cross the wire. One strategy covers both eligibility
    kinds (``MemoryPlan.manual_sync_kind``) through per-leaf descriptors:

      - a *replicated* leaf (all leaves of "ddp" plans; persistent chunks,
        norms, and non-divisible dims of "zero" plans) syncs DDP-style —
        quantize the full local grad, all-gather the int8 payload, dequantize
        and average identically everywhere; EF is per-device and stored
        stacked ``(n_sync, *shape)``, sharded over the sync axes;
      - a *ZeRO-sharded* leaf (``dist/sharding.leaf_sync_dim`` finds the dim
        carrying exactly the sync axes) reduce-scatters: chunk the local full
        grad along that dim, quantize per chunk, ``all_to_all`` the int8
        payload to shard owners, who dequantize and average — each device
        ends up owning its shard's reduced gradient and updates shard-local
        fp32 optimizer state in place. EF is *shard*-sized, laid out exactly
        like the gradient shard it corrects.

    ZeRO-sharded plans come in two dataflows (``MemoryPlan.zero_stage``):
    "zero2" gathers the bf16 param shards up front (full bf16 params live
    for the step; fp32 master/m/v and the synced grad stay shard-resident)
    and reduce-scatters gradients post-AD; "zero3" (default) gathers each
    chunk just-in-time inside the layer scan through
    ``dist.collectives.gather_param_lazy`` — a custom-vjp all-gather whose
    transpose *is* the compressed reduce-scatter, so sharded leaves' grads
    (and their new EF residuals) arrive shard-sized straight out of AD, full
    params never coexist, and ``n_buffer`` regains its xla-path meaning
    (buffered chunks keep gathered weights FWD->BWD, unbuffered ones
    re-gather in BWD). In every kind the per-microbatch sync collapses
    gradients to shard size before accumulation — the carry is shard-sized.

Dataflow diagrams and eligibility rules: docs/architecture.md §2.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import collectives as COLL
from repro.dist import sharding as SH
from repro.models.layers import ParamDef

_is_def = lambda x: isinstance(x, ParamDef)  # noqa: E731
_is_sds = lambda x: isinstance(x, jax.ShapeDtypeStruct)  # noqa: E731


# ---------------------------------------------------------------------------
# Shared accumulate skeleton (both sync paths, all manual kinds)
# ---------------------------------------------------------------------------
@jax.named_scope("accumulate")
def accumulate_grads(micro_grad, batch, microbatch, ef, acc_like, pin=None,
                     overlap=False):
    """Microbatch gradient accumulation, shared by every sync strategy.
    Runs under ``jax.named_scope("accumulate")``: the microbatch loop's own
    work (the gradient buffers, each microbatch's fold into them, the final
    mean) carries that scope, around the loss's ``model`` scope.

    ``micro_grad(mb_batch, ef) -> (grads, total, ce, ef)`` computes one
    microbatch's gradients — already synced for the manual strategies (the
    "zero3" kind reduce-scatters them *inside* AD via the lazy-gather VJP) —
    threading the EF residual so each wire transmission feeds its
    quantization error back into the next. ``acc_like`` shapes the
    accumulation carry: the manual ZeRO kinds pass the *local* state params
    (shard-sized leaves), because each microbatch's grads collapse to shard
    size before they are accumulated. ``pin`` re-asserts gradient shardings
    on the carry (omitted inside shard_map).

    ``overlap`` defers each microbatch's accumulate by one iteration:
    iteration m folds microbatch m-1's *already-synced* grads into the
    accumulator while microbatch m's reduce-scatter is still draining, so
    the sync's only consumer is the loop carry and the collective can hide
    under the next microbatch's backward (docs/cost_model.md §2). The adds
    are the serial path's exact fp32 adds, shifted one iteration — numerics
    are bit-identical. Returns ``(grads, total, ce, ef)``."""
    pin = pin if pin is not None else (lambda g: g)
    if microbatch == 1:
        grads, total, ce, ef = micro_grad(batch, ef)
        return pin(grads), total, ce, ef

    def split(x):
        return x.reshape(microbatch, x.shape[0] // microbatch, *x.shape[1:])

    micro = jax.tree.map(split, batch)
    zeros = pin(jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), acc_like))

    if overlap:

        def acc_body(carry, mb_batch):
            g_acc, g_pend, l_acc, ef_c = carry
            g, tot, _ce, ef_c = micro_grad(mb_batch, ef_c)
            g = pin(g)
            # Fold the *previous* microbatch's synced grads; this
            # microbatch's tree only flows into the carry, off the critical
            # path. The barrier pairs the fresh tree with the fold so at
            # most one synced tree is ever pending (the double-buffer idiom
            # from serve/paging).
            g_acc = jax.tree.map(lambda a, b: a + b, g_acc, g_pend)
            g_pend = jax.tree.map(lambda a, b: b.astype(a.dtype), g_acc, g)
            g_pend, _ = jax.lax.optimization_barrier((g_pend, g_acc))
            return (g_acc, g_pend, l_acc + tot, ef_c), None

        (g_acc, g_pend, total, ef), _ = jax.lax.scan(
            acc_body, (zeros, zeros, jnp.zeros((), jnp.float32), ef), micro)
        grads = jax.tree.map(lambda a, b: a + b, g_acc, g_pend)
    else:

        def acc_body(carry, mb_batch):
            g_acc, l_acc, ef_c = carry
            g, tot, _ce, ef_c = micro_grad(mb_batch, ef_c)
            g = pin(g)
            g_acc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), g_acc, g)
            return (g_acc, l_acc + tot, ef_c), None

        (grads, total, ef), _ = jax.lax.scan(
            acc_body, (zeros, jnp.zeros((), jnp.float32), ef), micro)
    grads = pin(jax.tree.map(lambda g: g / microbatch, grads))
    return grads, total / microbatch, total / microbatch, ef


# ---------------------------------------------------------------------------
# Per-leaf sync descriptors
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LeafSync:
    """How the manual path syncs one gradient leaf: ``dim`` is the
    ZeRO-sharded dim (reduce-scatter to shard owners) or None (replicated —
    DDP-style gather sync)."""
    dim: int | None


def leaf_sync_tree(spec_tree, sync_axes: tuple[str, ...]):
    """LeafSync descriptors for a ShapeDtypeStruct (or sharding) pytree."""

    def one(leaf) -> LeafSync:
        sh = getattr(leaf, "sharding", leaf)
        if not isinstance(sh, NamedSharding):
            return LeafSync(None)
        return LeafSync(SH.leaf_sync_dim(sh, sync_axes))

    return jax.tree.map(
        one, spec_tree,
        is_leaf=lambda x: isinstance(x, (NamedSharding, jax.ShapeDtypeStruct)),
    )


def manual_tree_sync(grads, errs, axis_names, compress: str, leaf_syncs):
    """Leaf-wise manual sync of one microbatch's local grad tree, dispatching
    per leaf between the gather-based all-reduce (replicated leaves) and the
    reduce-scatter (ZeRO-sharded leaves). Returns ``(synced, new_errs)``;
    uncompressed modes pass the error tree through unchanged."""
    flat_g, treedef = jax.tree.flatten(grads)
    flat_ls = treedef.flatten_up_to(leaf_syncs)
    if compress == "int8_ef":
        flat_e = treedef.flatten_up_to(errs)
        outs = []
        for g, e, ls in zip(flat_g, flat_e, flat_ls):
            if ls.dim is None:
                outs.append(COLL.manual_int8_ef_sync(g, e, axis_names))
            else:
                outs.append(
                    COLL.manual_int8_ef_reduce_scatter(g, e, axis_names, ls.dim))
        return (
            treedef.unflatten([o[0] for o in outs]),
            treedef.unflatten([o[1] for o in outs]),
        )

    def one(g, ls):
        if ls.dim is None:
            sync = (COLL.manual_bf16_mean if compress == "bf16"
                    else COLL.manual_mean)
            return sync(g, axis_names)
        rs = (COLL.manual_bf16_reduce_scatter if compress == "bf16"
              else COLL.manual_reduce_scatter)
        return rs(g, axis_names, ls.dim)

    return (
        treedef.unflatten([one(g, ls) for g, ls in zip(flat_g, flat_ls)]),
        errs,
    )


def _local_sq(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)


# ---------------------------------------------------------------------------
# Strategies
# ---------------------------------------------------------------------------
class XlaSync:
    """GSPMD owns the reduce; compression is wire numerics on reduced grads.

    Also serves as the 1-device fallback for manually-eligible plans: on one
    device the collective *is* the local math, so the xla body with the same
    numerics is bit-identical (same guard policy as the mesh-size checks in
    dist/collectives.py)."""

    manual_active = False

    def __init__(self, plan, mesh):
        self.plan = plan
        self.mesh = mesh
        self.compress = plan.grad_compress

    def ef_state(self, o_defs_one, g_shard):
        """(specs, shardings) of the EF residual state, or None. The xla
        residual is param-shaped fp32, sharded exactly like the grads."""
        if self.compress != "int8_ef":
            return None
        return SH.tree_specs(o_defs_one, g_shard), g_shard

    def finalize_grads(self, grads, ef, pin, ef_shard):
        """Post-accumulation wire numerics. Returns (grads, new_ef, metrics)."""
        from repro.optim.adam import global_norm

        metrics: dict[str, Any] = {}
        new_ef = None
        if self.compress == "int8_ef":
            grads, new_ef = COLL.compressed_tree_all_reduce(grads, ef)
            grads = pin(grads)
            new_ef = jax.tree.map(
                jax.lax.with_sharding_constraint, new_ef, ef_shard)
            metrics["ef_norm"] = global_norm(new_ef)
        elif self.compress == "bf16":
            grads = pin(COLL.bf16_tree_all_reduce(grads))
        return grads, new_ef, metrics


class ManualSync:
    """The whole step body under shard_map; dist/collectives own the wire.

    ``kind`` is ``MemoryPlan.manual_sync_kind``'s verdict ("ddp" | "zero2" |
    "zero3"); the per-leaf descriptors make the kinds one code path — a "ddp"
    plan simply has no sharded leaves, so its gather is the identity and
    every leaf takes the all-gather sync. The two ZeRO kinds differ only in
    *when* params are gathered:

      * "zero2" all-gathers every sharded bf16 leaf up front and keeps the
        full tree live for the step; gradients reduce-scatter post-AD
        (``manual_tree_sync``).
      * "zero3" never materializes the full tree: the loss closure (built by
        step_builder.make_lazy_loss_fn) gathers each chunk just-in-time
        inside the layer scan via ``dist.collectives.gather_param_lazy``,
        whose VJP *is* the compressed reduce-scatter — sharded leaves' grads
        arrive shard-sized straight out of AD, and the new EF residuals come
        out as the "gradient" w.r.t. the residual inputs. Only replicated
        leaves still sync post-AD (DDP-style). ``n_buffer`` keeps its
        xla-path meaning: buffered chunks save gathered weights FWD->BWD,
        unbuffered ones re-gather in BWD through the remat policy.
    """

    manual_active = True

    def __init__(self, plan, mesh, kind: str):
        self.plan = plan
        self.mesh = mesh
        self.kind = kind
        self.compress = plan.grad_compress
        # ZeRO kinds sync over the ZeRO (param-shard) axes so the
        # reduce-scatter owner coordinate matches the storage layout;
        # eligibility pins tp_degree == 1, making them the full batch extent
        # either way.
        self.axes = (SH.zero_axes(mesh) if kind in ("zero2", "zero3")
                     else SH.manual_sync_axes(mesh, plan.dp_only))
        sizes = SH.mesh_sizes(mesh)
        self.n_sync = math.prod(sizes[a] for a in self.axes)

    # -- EF residual state layout -------------------------------------------
    def ef_state(self, o_defs_one, g_shard):
        """Manual EF is device-varying state. Replicated leaves store it
        stacked — leading axis ``n_sync``, sharded over the sync axes — so
        checkpoints see the true per-device residuals. ZeRO-sharded leaves
        store one fp32 array in the *gradient's own sharded layout*: each
        device's residual is the shard it owns, so per-device bytes are
        shard-sized and the global view is directly checkpointable."""
        if self.compress != "int8_ef":
            return None
        stacked_ps = SH.manual_batch_pspec(1, self.mesh, self.plan.dp_only)

        def spec(d: ParamDef, s: NamedSharding):
            if SH.leaf_sync_dim(s, self.axes) is not None:
                return jax.ShapeDtypeStruct(d.shape, jnp.float32, sharding=s)
            return jax.ShapeDtypeStruct(
                (self.n_sync,) + d.shape, jnp.float32,
                sharding=NamedSharding(self.mesh, stacked_ps))

        specs = jax.tree.map(spec, o_defs_one, g_shard, is_leaf=_is_def)
        shardings = jax.tree.map(lambda s: s.sharding, specs, is_leaf=_is_sds)
        return specs, shardings

    # -- step construction ---------------------------------------------------
    def build_step_fn(self, *, loss, apply_update, state_specs, batch_specs,
                      global_batch: int, microbatch: int, lazy_loss=None):
        """Assemble the shard_map'd step. ``loss`` must be the manual-mode
        loss closure (identity activation sharder, fully-gathered params —
        see step_builder.make_loss_fn); for the "zero3" kind ``lazy_loss`` is
        the per-chunk-gather closure ``(params, ef, batch) -> (total, ce)``
        (step_builder.make_lazy_loss_fn) and ``loss`` is unused.
        ``apply_update`` is the shared optimizer/assembly tail."""
        axes, n_sync, compress, kind = self.axes, self.n_sync, self.compress, self.kind
        local_b = global_batch // max(n_sync, 1)
        if global_batch % n_sync or (microbatch > 1 and local_b % microbatch):
            raise ValueError(
                "manual sync splits the per-device batch shard into "
                f"microbatches: global_batch={global_batch} must divide "
                f"by sync extent {n_sync} (and the local batch {local_b} by "
                f"microbatch={microbatch})"
            )
        if kind == "zero3" and lazy_loss is None:
            raise ValueError("manual 'zero3' sync needs the lazy-gather loss "
                             "closure (step_builder.make_lazy_loss_fn)")
        leafs = leaf_sync_tree(state_specs["params"], axes)
        has_sharded = any(ls.dim is not None for ls in jax.tree.leaves(
            leafs, is_leaf=lambda x: isinstance(x, LeafSync)))

        def gather_full(params):
            """Up-front all-gather of ZeRO-sharded bf16 param shards to full
            leaves ("zero2"; identity for "ddp" plans: no sharded leaves)."""

            def one(w, ls: LeafSync):
                if ls.dim is None:
                    return w
                return jax.lax.all_gather(w, axes, axis=ls.dim, tiled=True)

            return jax.tree.map(one, params, leafs)

        def replicated_sync(g, ee, eg, ls):
            """Post-AD sync of one replicated leaf; sharded leaves were
            already reduce-scattered inside AD (zero3), whose new residual is
            ``eg`` — the loss's "gradient" w.r.t. the residual input."""
            if ls.dim is not None:
                return g, eg
            if compress == "int8_ef":
                return COLL.manual_int8_ef_sync(g, ee, axes)
            sync = COLL.manual_bf16_mean if compress == "bf16" else COLL.manual_mean
            return sync(g, axes), ee

        def split_ef(ef):
            """Global EF view -> this device's local residuals (stacked
            leaves carry a size-1 leading slice; sharded leaves arrive as
            the owned shard already)."""
            return jax.tree.map(
                lambda e, ls: e if ls.dim is not None else e[0], ef, leafs)

        def stack_ef(ef):
            return jax.tree.map(
                lambda e, ls: e if ls.dim is not None else e[None], ef, leafs)

        def grad_norm(grads):
            """Global gradient norm: sharded leaves hold disjoint shards
            (their squared sums add across devices); replicated leaves are
            identical everywhere (count once)."""
            flat_g, treedef = jax.tree.flatten(grads)
            flat_ls = treedef.flatten_up_to(leafs)
            sq_shard = sum(
                (jnp.sum(jnp.square(g.astype(jnp.float32)))
                 for g, ls in zip(flat_g, flat_ls) if ls.dim is not None),
                start=jnp.zeros((), jnp.float32))
            sq_rep = sum(
                (jnp.sum(jnp.square(g.astype(jnp.float32)))
                 for g, ls in zip(flat_g, flat_ls) if ls.dim is None),
                start=jnp.zeros((), jnp.float32))
            if has_sharded:
                sq_shard = jax.lax.psum(sq_shard, axes)
            return jnp.sqrt(sq_shard + sq_rep)

        def body(state, batch):
            ef = split_ef(state["ef"]) if compress == "int8_ef" else None
            if kind == "zero3":
                # per-chunk lazy gather: sharded leaves' grads (and new EF
                # residuals) come out of AD already reduce-scattered; only
                # replicated leaves need the post-AD DDP-style sync
                def micro_grad(mb_batch, ef_c):
                    if compress == "int8_ef":
                        (tot, ce), (g, ef_g) = jax.value_and_grad(
                            lazy_loss, argnums=(0, 1), has_aux=True)(
                                state["params"], ef_c, mb_batch)
                        flat_g, td = jax.tree.flatten(g)
                        outs = [replicated_sync(gg, ee, eg, ls)
                                for gg, ee, eg, ls in zip(
                                    flat_g, td.flatten_up_to(ef_c),
                                    td.flatten_up_to(ef_g),
                                    td.flatten_up_to(leafs))]
                        return (td.unflatten([o[0] for o in outs]), tot, ce,
                                td.unflatten([o[1] for o in outs]))
                    (tot, ce), g = jax.value_and_grad(
                        lazy_loss, has_aux=True)(state["params"], None, mb_batch)
                    flat_g, td = jax.tree.flatten(g)
                    synced = [replicated_sync(gg, None, None, ls)[0]
                              for gg, ls in zip(flat_g, td.flatten_up_to(leafs))]
                    return td.unflatten(synced), tot, ce, ef_c
            else:
                full_params = gather_full(state["params"])

                def micro_grad(mb_batch, ef_c):
                    (tot, ce), g = jax.value_and_grad(
                        loss, has_aux=True)(full_params, mb_batch)
                    g, ef_c = manual_tree_sync(g, ef_c, axes, compress, leafs)
                    return g, tot, ce, ef_c

            grads, total, ce, ef = accumulate_grads(
                micro_grad, batch, microbatch, ef, acc_like=state["params"],
                overlap=self.plan.overlap)

            # losses were computed on the local batch shard; average them
            total = jax.lax.pmean(total, axes)
            ce = jax.lax.pmean(ce, axes)

            metrics: dict[str, Any] = {}
            new_ef = None
            if compress == "int8_ef":
                # global residual norm: per-device values differ, so reduce
                # the squared sums for a replicated metric
                metrics["ef_norm"] = jnp.sqrt(jax.lax.psum(_local_sq(ef), axes))
                new_ef = stack_ef(ef)

            return apply_update(state, grads, total, ce, new_ef, metrics,
                                host_plan=None, repin=False,
                                grad_norm=grad_norm(grads))

        state_ps = SH.manual_state_pspecs(state_specs)
        batch_ps = jax.tree.map(
            lambda s: SH.manual_batch_pspec(
                len(s.shape), self.mesh, self.plan.dp_only),
            batch_specs, is_leaf=_is_sds,
        )
        metric_names = ["loss", "ce", "grad_norm", "lr"] + (
            ["ef_norm"] if compress == "int8_ef" else [])
        metrics_ps = {k: P() for k in metric_names}
        # replication check off: the checker cannot see that a gather-based
        # all-reduce (all_gather + identical local mean) yields replicated
        # outputs; replication holds by construction (dist/collectives.py)
        return jax.shard_map(body, mesh=self.mesh, in_specs=(state_ps, batch_ps),
                             out_specs=(state_ps, metrics_ps), check_vma=False)


def make_strategy(plan, mesh, tp_degree: int) -> XlaSync | ManualSync:
    """Sync strategy for a plan on a mesh; raises for ineligible manual plans.

    Structural eligibility is validated even on 1-device meshes (code first
    exercised locally fails the same way it would deployed); the 1-device
    *fallback* to the local-math xla strategy only applies to plans that
    could lower manually in the first place."""
    if plan.sync_mode != "manual":
        return XlaSync(plan, mesh)
    kind = plan.manual_sync_kind(tp_degree)
    if kind is None:
        raise ValueError(
            "sync_mode='manual' requires a layout the shard_map body can "
            "lower: no swap blocks, no host-resident chunks, no "
            "zero1_persistent, and tp_degree == 1 (all-persist 'ddp' plans "
            "may instead set dp_only to absorb the model axis). Got "
            f"{plan.describe()} on tp_degree={tp_degree}. "
            "See MemoryPlan.manual_sync_kind / docs/architecture.md."
        )
    if math.prod(mesh.devices.shape) == 1:
        return XlaSync(plan, mesh)
    return ManualSync(plan, mesh, kind)


# ---------------------------------------------------------------------------
# Telemetry: static per-step wire-byte inventory
# ---------------------------------------------------------------------------
def record_sync_inventory(strategy, params_specs, microbatch: int,
                          registry=None) -> dict[str, int]:
    """Record the step's collective wire-byte inventory as gauges.

    Collectives execute inside jit, so runtime counters cannot observe them
    — the traced program runs the Python body exactly once. What *is* known
    statically is the payload each strategy puts on the wire per step, and
    that is what this records, from the parameter leaf specs:

      * ``sync.wire_bytes_per_step{strategy=..., op=grad_sync}`` — the
        gradient sync payload: every param leaf at the compression payload
        width (1 B int8_ef / 2 B bf16 / 4 B fp32), once per step (sync
        happens after microbatch accumulation).
      * ``sync.wire_bytes_per_step{strategy=..., op=param_gather}`` — bf16
        param-gather traffic of ZeRO kinds: zero2 gathers sharded leaves
        once up front; zero3 re-gathers inside the scan every microbatch.
      * ``sync.wire_payload{strategy=...}`` — the payload element width.

    Logical payload bytes, not per-link ring traffic (multiply by
    (n-1)/n per hop for that). Resolves the registry through
    ``obs.current_telemetry()`` when not given; with none installed this
    only builds the (small) returned dict.
    """
    from repro import obs

    reg = registry if registry is not None else obs.current_telemetry().registry
    kind = getattr(strategy, "kind", "xla")
    compress = strategy.compress
    itemsize = {"int8_ef": 1, "bf16": 2}.get(compress, 4)
    axes = getattr(strategy, "axes", ())

    grad_bytes = 0
    gather_bytes = 0
    for leaf in jax.tree.leaves(params_specs):
        n = math.prod(leaf.shape)
        grad_bytes += n * itemsize
        if kind in ("zero2", "zero3"):
            sh = getattr(leaf, "sharding", None)
            if isinstance(sh, NamedSharding) and \
                    SH.leaf_sync_dim(sh, axes) is not None:
                gather_bytes += n * 2  # bf16 gather payload
    if kind == "zero3":
        gather_bytes *= microbatch
    inv = {"grad_sync": grad_bytes, "param_gather": gather_bytes,
           "payload_itemsize": itemsize}
    reg.gauge("sync.wire_bytes_per_step", strategy=kind,
              op="grad_sync").set(grad_bytes)
    reg.gauge("sync.wire_bytes_per_step", strategy=kind,
              op="param_gather").set(gather_bytes)
    reg.gauge("sync.wire_payload", strategy=kind).set(itemsize)
    return inv
