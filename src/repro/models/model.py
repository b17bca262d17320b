"""Model assembly: superblocks, decoder / encoder-decoder forward, decode.

A *superblock* is the smallest repeating unit of layers — ``lcm(len(mixer
pattern), moe.every)`` layers (1 for uniform archs, 8 for Jamba). Parameters
are stacked over superblock repeats so the layer stack lowers to a single
``lax.scan`` regardless of depth; this is also the chunk granularity used by
ProTrain's planner (paper §B.1 groups one transformer block per chunk).

The layer stack is executed as a list of *runs* — contiguous repeat ranges
sharing one (weights-buffered?, activation-policy) pair — which is how the
planner's {n_persist, n_buffer, n_swap, n_checkpoint} choice is realized (see
train/step_builder.py).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models import moe as MOE
from repro.models.layers import NONE, TP, ZERO, LAYER, ParamDef

ACT = "act"  # checkpoint_name for offloadable activations
ACT_CMP = "act_cmp"  # checkpoint_name for compressed (quantized) activations
GATHERED_W = "gathered_w"  # checkpoint_name for gathered (unsharded) weights


def superblock_period(cfg: ModelConfig) -> int:
    p = len(cfg.mixer_pattern)
    if cfg.moe is not None:
        p = math.lcm(p, cfg.moe.every)
    return p


def num_repeats(cfg: ModelConfig) -> int:
    p = superblock_period(cfg)
    assert cfg.num_layers % p == 0, (cfg.name, cfg.num_layers, p)
    return cfg.num_layers // p


# ---------------------------------------------------------------------------
# Param definitions
# ---------------------------------------------------------------------------
def _position_defs(cfg: ModelConfig, pos: int, cross_attention: bool = False) -> dict:
    """ParamDefs for one layer position within the superblock."""
    defs: dict[str, Any] = {"norm1": L.norm_defs(cfg.d_model, cfg.norm)}
    if cfg.mixer_at(pos) == "attention":
        defs["attn"] = L.attention_defs(cfg)
    else:
        defs["mamba"] = M2.mamba2_defs(cfg)
    if cross_attention:
        defs["norm_x"] = L.norm_defs(cfg.d_model, cfg.norm)
        defs["xattn"] = L.cross_attention_defs(cfg)
    if cfg.moe_at(pos):
        defs["norm2"] = L.norm_defs(cfg.d_model, cfg.norm)
        defs["moe"] = MOE.moe_defs(cfg)
    elif cfg.d_ff:
        defs["norm2"] = L.norm_defs(cfg.d_model, cfg.norm)
        defs["mlp"] = L.mlp_defs(cfg)
    return defs


def _stack_defs(defs, n: int):
    """Prepend a stacked LAYER axis of size n to every ParamDef."""
    return jax.tree.map(
        lambda d: ParamDef((n,) + d.shape, (LAYER,) + d.axes, init=d.init, scale=d.scale, dtype=d.dtype),
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def param_defs(cfg: ModelConfig) -> dict:
    """Full parameter ParamDef pytree for the model."""
    p = superblock_period(cfg)
    r = num_repeats(cfg)
    defs: dict[str, Any] = {
        "embed": {"tok": ParamDef((cfg.vocab_size, cfg.d_model), (TP, ZERO), scale=0.02)},
        "blocks": {
            f"pos{j}": _stack_defs(_position_defs(cfg, j, cross_attention=cfg.kind == "encdec"), r)
            for j in range(p)
        },
        "final_norm": L.norm_defs(cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        defs["head"] = {"w": ParamDef((cfg.d_model, cfg.vocab_size), (ZERO, TP), scale=0.02)}
    if cfg.kind == "encdec":
        defs["encoder"] = {
            "blocks": _stack_defs(_position_defs(cfg, 0), cfg.encoder_layers),
            "final_norm": L.norm_defs(cfg.d_model, cfg.norm),
        }
    if cfg.dtype != "bfloat16":
        # ParamDefs default to bf16 compute dtype; explicit fp32 defs
        # (A_log, router, ...) keep theirs.
        defs = jax.tree.map(
            lambda d: dataclasses.replace(d, dtype=cfg.dtype) if d.dtype == "bfloat16" else d,
            defs,
            is_leaf=lambda x: isinstance(x, ParamDef),
        )
    return defs


def init_params(cfg: ModelConfig, key: jax.Array):
    return L.init_tree(param_defs(cfg), key)


# ---------------------------------------------------------------------------
# Activation sharding hook (set by the step builder; no-op by default)
# ---------------------------------------------------------------------------
_ACT_SHARDER: Callable[[jax.Array, str], jax.Array] = lambda x, kind: x


def set_activation_sharder(fn) -> None:
    global _ACT_SHARDER
    _ACT_SHARDER = fn


def _pin_cotangent_dtype(x: jax.Array) -> jax.Array:
    """Identity whose VJP casts the incoming cotangent back to x.dtype.

    Mixed-precision transposes (fp32-accumulating einsums, fp32 loss heads)
    otherwise promote dL/dx to fp32 at every block boundary — doubling the
    backward activation traffic and the saved-residual stacks.
    """

    @jax.custom_vjp
    def pin(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, ct):
        return (ct.astype(x.dtype),)

    pin.defvjp(fwd, bwd)
    return pin(x)


def shard_act(x: jax.Array, kind: str = "bsd") -> jax.Array:
    if kind == "bsd":
        x = _pin_cotangent_dtype(x)
    return _ACT_SHARDER(x, kind)


# ---------------------------------------------------------------------------
# Compressed activation saves (quantize-on-save / dequantize-on-use)
# ---------------------------------------------------------------------------
# Tri-state dispatch for the int8 activation quantizer, mirroring
# dist.collectives.set_fused_quant: None = auto (the PR-8 fused Pallas
# quantize kernel when it can run *compiled* — interpret mode unrolls the
# (rows,) grid and is unusable at activation sizes), True/False = forced.
_ACT_QUANT_KERNEL: bool | None = None


def set_act_quant_kernel(enabled: bool | None) -> None:
    global _ACT_QUANT_KERNEL
    _ACT_QUANT_KERNEL = enabled


def act_quant_kernel_active() -> bool:
    if _ACT_QUANT_KERNEL is not None:
        return _ACT_QUANT_KERNEL
    from repro.compat import pallas_interpret_required

    return not pallas_interpret_required()


def _quantize_rows(x2d: jax.Array):
    """Per-row absmax int8 quantize of a (rows, d) fp32 array -> (q, scale).

    Dispatches to the fused Pallas quantize/pack kernel (kernels package)
    when it runs compiled, else the vectorized ref oracle — the two are
    bitwise-identical (tests/test_paged_attention_kernel.py), so the seam
    never changes numerics, only where the bytes are produced."""
    me = jnp.int32(0)  # EF slot unused for activations: the error is discarded
    if act_quant_kernel_active():
        from repro.kernels import fused_quantize_ef

        q, s, _ = fused_quantize_ef(x2d, me)
    else:
        from repro.kernels.ref import fused_quantize_ef_ref

        q, s, _ = fused_quantize_ef_ref(x2d, me)
    return q, s


def compress_act(x: jax.Array, mode: str = "compress8") -> jax.Array:
    """Save-compressed seam: the activation twin of ``Run.lazy_gather``.

    Under a ``save_only_these_names(ACT_CMP, ...)`` remat policy the block
    holds only the quantized payload FWD->BWD and dequantizes at point of
    use in the backward replay; everything between compressed sites is
    rematerialized. Two parts make that true:

      * the quantized payload (q, scale) is produced by *named plain eqn
        outputs* (``checkpoint_name(·, ACT_CMP)``) — custom_vjp residuals do
        not persist under jax.checkpoint, named saveables do. The quantizer
        itself is wrapped in a custom_vjp so AD never traces the Pallas call
        (its cotangent to x is zero — the gradient does not flow through the
        rounding);
      * a dequantize-on-use custom_vjp ``use(q, s, x)`` whose primal reads
        ONLY (q, s) — so the replay reconstructs the activation from the
        saved payload, not from x — and whose VJP routes the cotangent
        straight through to x (the straight-through estimator; absmax
        clipping makes the identity exact up to rounding).

    ``compress16`` is the degenerate lattice point: a named bf16 downcast
    (linear, differentiable — no custom_vjp needed).
    """
    if mode == "compress16":
        return checkpoint_name(x.astype(jnp.bfloat16), ACT_CMP).astype(x.dtype)
    assert mode == "compress8", mode
    dtype = x.dtype
    shape = x.shape
    rows = math.prod(shape[:-1])
    x2d = x.astype(jnp.float32).reshape(rows, shape[-1])

    @jax.custom_vjp
    def quantize(x2d):
        return _quantize_rows(x2d)

    def q_fwd(x2d):
        return quantize(x2d), None

    def q_bwd(_, ct):
        return (jnp.zeros((rows, shape[-1]), jnp.float32),)

    quantize.defvjp(q_fwd, q_bwd)
    q, s = quantize(x2d)
    q = checkpoint_name(q, ACT_CMP)
    s = checkpoint_name(s, ACT_CMP)

    def _deq(q, s):
        return (q.astype(jnp.float32) * s[:, None]).reshape(shape).astype(dtype)

    @jax.custom_vjp
    def use(q, s, x):
        return _deq(q, s)

    def u_fwd(q, s, x):
        return _deq(q, s), None

    def u_bwd(_, ct):
        return (np.zeros((rows, shape[-1]), jax.dtypes.float0),
                jnp.zeros((rows,), jnp.float32), ct.astype(dtype))

    use.defvjp(u_fwd, u_bwd)
    return use(q, s, x)


def save_act(x: jax.Array, mode: str = "none") -> jax.Array:
    """Tag an activation save site: compressed for the compress policies,
    the plain offloadable ACT name otherwise."""
    if mode in ("compress8", "compress16"):
        return compress_act(x, mode)
    return checkpoint_name(x, ACT)


def gather_weights(params, specs=None):
    """Mark weights as gathered at point-of-use (named for remat policies).

    ``specs`` is an optional matching pytree of ``NamedSharding`` whose ZeRO
    axes have been dropped (replicated): the ``with_sharding_constraint``
    forces the all-gather here — per scanned superblock, i.e. chunk-wise, the
    paper's gather granularity. For persistent runs specs is None (weights are
    already replicated; the name alone is harmless).
    """
    if specs is None:
        return jax.tree.map(lambda w: checkpoint_name(w, GATHERED_W), params)
    # device_put (not with_sharding_constraint): it both forces the all-gather
    # over the dropped ZeRO axes *and* moves host-resident chunks into HBM.
    # The optimization barrier pins the gather *inside* the layer scan: without
    # it XLA commutes slice-of-stack with all-gather and hoists the gather of
    # the whole stacked run out of the loop — materializing every layer's
    # weights at once (the exact pattern chunk-wise gathering must avoid).
    # The scope names the fetch in forward and in a backward re-fetch.
    with jax.named_scope("param_fetch"):
        params = jax.lax.optimization_barrier(params)
        return jax.tree.map(
            lambda w, s: checkpoint_name(w if s is None else jax.device_put(w, s), GATHERED_W),
            params,
            specs,
        )


# ---------------------------------------------------------------------------
# Superblock forward
# ---------------------------------------------------------------------------
def apply_position(
    pparams: dict,
    x: jax.Array,
    cfg: ModelConfig,
    pos_j: int,
    *,
    positions: jax.Array | None = None,
    memory: jax.Array | None = None,
    attn_impl: str = "blockwise",
    act_mode: str = "none",
) -> tuple[jax.Array, jax.Array]:
    """One layer (superblock position). Returns (x, aux_loss).

    ``act_mode``: how this layer's save sites are tagged — "none" names them
    ACT (keep/offload/remat decided by the surrounding policy), the compress
    modes route them through the quantize-on-save seam (``save_act``)."""
    aux = jnp.zeros((), jnp.float32)
    x = shard_act(x, "enter")  # SP: gather seq-sharded boundary for compute
    h = L.apply_norm(pparams["norm1"], x, cfg.norm, cfg.norm_eps)
    h = save_act(h, act_mode)
    if "attn" in pparams:
        mix = L.attention_block(pparams["attn"], h, cfg, positions=positions, impl=attn_impl)
    else:
        mix = M2.apply_mamba2(pparams["mamba"], h, cfg)
    x = x + save_act(mix, act_mode)
    if memory is not None and "xattn" in pparams:
        hx = L.apply_norm(pparams["norm_x"], x, cfg.norm, cfg.norm_eps)
        x = x + save_act(L.cross_attention_block(pparams["xattn"], hx, memory, cfg), act_mode)
    if "moe" in pparams:
        h2 = L.apply_norm(pparams["norm2"], x, cfg.norm, cfg.norm_eps)
        out, moe_aux = MOE.apply_moe(pparams["moe"], h2, cfg)
        x = x + save_act(out, act_mode)
        aux = aux + moe_aux
    elif "mlp" in pparams:
        h2 = L.apply_norm(pparams["norm2"], x, cfg.norm, cfg.norm_eps)
        x = x + save_act(L.apply_mlp(pparams["mlp"], h2, cfg.mlp), act_mode)
    return shard_act(x), aux


def apply_superblock(
    block_params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    gather_specs=None,
    remat_policy=None,
    lazy_gather=None,
    ef=None,
    **kw,
):
    """block_params: {posJ: params-for-one-repeat}. Returns (x, aux).

    ``remat_policy``: optional jax.checkpoint policy applied *per position*
    (per transformer layer) — the paper's per-block activation management
    granularity. The gather is inside the rematted region, so gathered-weight
    save/offload follows the same policy (n_buffer semantics).

    ``lazy_gather``: manual-sync (shard_map) replacement for the
    device_put-based ``gather_weights``: a hook ``(per-position params,
    per-position EF subtree, position j) -> gathered params`` built on
    ``dist.collectives.gather_param_lazy``, whose VJP reduce-scatters the
    gradient to shard owners. ``ef`` is the error-feedback residual subtree
    threaded to the hook (sliced alongside the params by the run scan).
    """
    aux = jnp.zeros((), jnp.float32)

    def one(j, x):
        if lazy_gather is not None:
            pp = lazy_gather(block_params[f"pos{j}"],
                             None if ef is None else ef[f"pos{j}"], j)
        else:
            specs = None if gather_specs is None else gather_specs[f"pos{j}"]
            pp = gather_weights(block_params[f"pos{j}"], specs)
        return apply_position(pp, x, cfg, j, **kw)

    for j in range(superblock_period(cfg)):
        fn = one if remat_policy is None else jax.checkpoint(one, policy=remat_policy, static_argnums=(0,))
        x, a = fn(j, x)
        aux = aux + a
    return x, aux


REMAT_POLICIES: dict[tuple[str, bool, bool, bool], Any] = {}


def _is_lazy_gather_eqn(prim, params) -> bool:
    """Recognize the ``dist.collectives.gather_param_lazy`` custom_vjp call:
    a custom-vjp whose forward jaxpr is (only) a tiled all-gather."""
    if prim.name not in ("custom_vjp_call_jaxpr", "custom_vjp_call"):
        return False
    fj = params.get("fun_jaxpr") or params.get("call_jaxpr")
    eqns = getattr(getattr(fj, "jaxpr", fj), "eqns", [])
    return 0 < len(eqns) <= 2 and any(
        e.primitive.name == "all_gather" for e in eqns)


def _save_acts_not_fetches(host: bool = False):
    """save_anything_except_these_names(GATHERED_W), plus: never save the
    *raw* fetch output feeding the name. Without the second clause the
    name exclusion is defeated — the named value is an identity of the
    unnamed fetch output, so partial-eval happily saves the unnamed ancestor
    and the "re-gather in BWD" semantics silently becomes "buffered".

    A lazy run's fetch is its all-gather: by the time the policy runs the
    gather custom_vjp has been inlined, so the exclusion matches the
    ``all_gather`` primitive itself (the only all-gathers inside a lazy
    run's remat region are the lazy weight gathers; activation sharding is
    identity under manual sync) — with the custom_vjp-eqn matcher kept for
    jax versions that keep the call un-inlined. A host-resident run's
    (``host``) is ``gather_weights``' ``optimization_barrier`` and
    ``device_put`` into HBM: saved raw, they would keep every layer's
    weights in device memory from forward to backward."""
    base = jax.checkpoint_policies.save_anything_except_these_names(GATHERED_W)
    fetches = ("all_gather", "optimization_barrier", "device_put") if host else ("all_gather",)

    def policy(prim, *avals, **params):
        if prim.name in fetches or _is_lazy_gather_eqn(prim, params):
            return False
        return base(prim, *avals, **params)

    return policy


def _remat_policy(act_policy: str, buffered: bool, lazy: bool = False, host: bool = False):
    """Map (activation policy, weights-buffered?) to a jax.checkpoint policy.

    ``lazy``: the run gathers weights through ``gather_param_lazy`` (manual
    ZeRO-3); ``host``: its weights live in host memory. The unbuffered
    keep-activations policy must then also exclude the fetch's raw output
    from saving (see ``_save_acts_not_fetches``)."""
    key = (act_policy, buffered, lazy, host)
    if key in REMAT_POLICIES:
        return REMAT_POLICIES[key]
    cp = jax.checkpoint_policies
    if act_policy == "none":
        if buffered:
            pol = cp.everything_saveable
        elif lazy or host:
            pol = _save_acts_not_fetches(host)
        else:
            pol = cp.save_anything_except_these_names(GATHERED_W)
    elif act_policy == "checkpoint":
        pol = cp.save_only_these_names(GATHERED_W) if buffered else cp.nothing_saveable
    elif act_policy in ("compress8", "compress16"):
        # save the quantized payload (and the gathered weights when the run
        # buffers them); save_only_* default-excludes everything else, so the
        # ZeRO-3 lazy gathers are never saved — let alone quantized — and the
        # interiors between compressed sites rematerialize from the payload
        pol = (cp.save_only_these_names(ACT_CMP, GATHERED_W) if buffered
               else cp.save_only_these_names(ACT_CMP))
    elif act_policy == "swap":
        pol = cp.save_and_offload_only_these_names(
            names_which_can_be_saved=[GATHERED_W] if buffered else [],
            names_which_can_be_offloaded=[ACT],
            offload_src="device",
            offload_dst="pinned_host",
        )
    else:
        raise ValueError(act_policy)
    REMAT_POLICIES[key] = pol
    return pol


@dataclasses.dataclass
class Run:
    """A contiguous range of superblock repeats sharing one policy."""

    params: dict  # stacked over this run's repeats
    n_repeats: int
    act_policy: str = "none"  # none | checkpoint | swap | compress8 | compress16
    buffered: bool = True  # gathered weights saved fwd->bwd?
    persistent: bool = False  # params replicated over zero axes (no gather)
    host: bool = False  # params in host memory: the gather fetches over the host link
    gather_specs: Any = None  # per-repeat pytree of NamedSharding (ZeRO dropped)
    ckpt_group: int = 1  # remat region size in superblock repeats (sqrt(n) trade)
    # manual ZeRO-3 lazy gather: hook (per-repeat params, per-repeat ef) ->
    # gathered params, plus the stacked EF residual tree scanned alongside the
    # params so the gather VJP's new residuals come out stacked per repeat
    lazy_gather: Any = None
    ef: Any = None
    # double-buffered gather prefetch (plan.gather_prefetch_depth >= 2):
    # inside the run scan, repeat k+1's all-gathers are issued during repeat
    # k's matmuls, barrier-ordered after repeat k-1's output — the training
    # twin of serve/paging's cold-page prefetch. Only meaningful for
    # buffered lazy-gather runs (the carried gathered weights are saved
    # FWD->BWD anyway); everything else falls back to the serial inline
    # gather automatically.
    prefetch: bool = False


def apply_runs(
    runs: list[Run],
    x: jax.Array,
    cfg: ModelConfig,
    *,
    memory: jax.Array | None = None,
    attn_impl: str = "blockwise",
) -> tuple[jax.Array, jax.Array]:
    """Execute the layer stack as policy runs of scanned superblocks."""
    aux_total = jnp.zeros((), jnp.float32)

    for run in runs:
        # per-position (per-layer) remat policy; None = save everything
        lazy = run.lazy_gather is not None
        pol = (
            None
            if run.act_policy == "none" and run.buffered
            else _remat_policy(run.act_policy, run.buffered, lazy, run.host)
        )
        g = run.ckpt_group if run.act_policy == "checkpoint" else 1
        g = max(1, min(g, run.n_repeats))
        while run.n_repeats % g:
            g -= 1  # group must tile the run
        act_mode = (run.act_policy
                    if run.act_policy in ("compress8", "compress16") else "none")

        if (run.prefetch and lazy and run.buffered and run.act_policy == "none"
                and g == 1 and run.n_repeats >= 2):
            x, aux_total = _apply_run_prefetched(
                run, x, aux_total, cfg, memory=memory, attn_impl=attn_impl)
            continue

        if g == 1:
            def body(carry, sl, _run=run, _pol=pol, _mode=act_mode):
                x, aux = carry
                bp, ef = sl
                x, a = apply_superblock(
                    bp, x, cfg, gather_specs=_run.gather_specs, remat_policy=_pol,
                    lazy_gather=_run.lazy_gather, ef=ef,
                    memory=memory, attn_impl=attn_impl, act_mode=_mode,
                )
                return (x, aux + a), None

            scan_xs = (run.params, run.ef)
        else:
            # grouped remat: one checkpoint region spans g superblocks, so the
            # scan saves one boundary per g layers (recompute working set: g)
            def region(carry, gsl, _run=run, _g=g):
                x, aux = carry
                gp, gef = gsl
                for i in range(_g):
                    bp = jax.tree.map(lambda a, _i=i: a[_i], gp)
                    ef_i = (None if gef is None
                            else jax.tree.map(lambda a, _i=i: a[_i], gef))
                    x, a = apply_superblock(
                        bp, x, cfg, gather_specs=_run.gather_specs,
                        remat_policy=None, lazy_gather=_run.lazy_gather,
                        ef=ef_i, memory=memory, attn_impl=attn_impl,
                    )
                    aux = aux + a
                return (x, aux)

            region_ck = jax.checkpoint(
                region, policy=_remat_policy(run.act_policy, run.buffered, lazy, run.host))

            def body(carry, gsl, _f=region_ck):
                return _f(carry, gsl), None

            scan_xs = jax.tree.map(
                lambda a, _g=g: a.reshape(a.shape[0] // _g, _g, *a.shape[1:]),
                (run.params, run.ef),
            )

        n_iters = run.n_repeats // g
        if n_iters == 1:
            (x, aux_total), _ = body(
                (x, aux_total), jax.tree.map(lambda a: a[0], scan_xs)
            )
        else:
            (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), scan_xs)
    return x, aux_total


def _apply_run_prefetched(run: Run, x, aux_total, cfg, *, memory, attn_impl):
    """Double-buffered lazy-gather pipeline over one buffered run.

    Serial inline gathering (the non-prefetch path) only lets repeat k's
    all-gather start once repeat k-1's output exists — gather and matmuls
    alternate. Here repeat 0's weights are gathered before the scan and the
    scan body, at repeat k, (a) issues repeat k+1's gathers *anchored on the
    incoming activation* (repeat k-1's output — the earliest point the
    pipeline may start them, and nothing orders them after repeat k's
    compute) and (b) applies repeat k with the weights carried from the
    previous iteration. Exactly two repeats' gathered weights are ever in
    flight (``plan.gather_prefetch_depth == 2``), mirroring serve/paging's
    ``optimization_barrier`` cold-page double buffer.

    The scan runs ``n_repeats - 1`` iterations over the ``[1:]`` param/EF
    slices, with a trailing un-scanned apply for the last repeat — NOT a
    wrap-around gather of repeat 0, which would consume repeat 0's EF
    residual twice and corrupt the error-feedback semantics (the residual's
    cotangents from two gathers would add).

    Restricted to buffered ``act_policy="none"`` runs: the carried gathered
    weights become per-iteration scan AD residuals, which is free exactly
    when the run saves them FWD->BWD anyway. Unbuffered/checkpointed runs
    keep the serial inline gather (the documented fallback).
    """

    def gather_repeat(bp, efr, anchor=None, _run=run):
        return {
            k: _run.lazy_gather(bp[k], None if efr is None else efr[k],
                                int(k[3:]), anchor=anchor)
            for k in bp
        }

    first = jax.tree.map(lambda a: a[0], (run.params, run.ef))
    w0 = gather_repeat(*first)
    rest_xs = jax.tree.map(lambda a: a[1:], (run.params, run.ef))

    def body(carry, sl):
        x, aux, w_cur = carry
        bp, ef = sl
        w_next = gather_repeat(bp, ef, anchor=x)
        x, a = apply_superblock(
            w_cur, x, cfg, gather_specs=None, remat_policy=None,
            lazy_gather=None, ef=None, memory=memory, attn_impl=attn_impl,
        )
        return (x, aux + a, w_next), None

    (x, aux_total, w_last), _ = jax.lax.scan(body, (x, aux_total, w0), rest_xs)
    x, a = apply_superblock(
        w_last, x, cfg, gather_specs=None, remat_policy=None,
        lazy_gather=None, ef=None, memory=memory, attn_impl=attn_impl,
    )
    return x, aux_total + a


def default_runs(cfg: ModelConfig, params: dict) -> list[Run]:
    """Single fully-resident run (no ZeRO, no remat) — small-model default."""
    return [Run(params=params["blocks"], n_repeats=num_repeats(cfg), persistent=True)]


# ---------------------------------------------------------------------------
# Full-model forward
# ---------------------------------------------------------------------------
def embed_tokens(params: dict, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    emb = params["embed"]["tok"]
    return shard_act(jnp.take(emb, tokens, axis=0), "bsd")


def lm_head(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = L.apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    w = params["embed"]["tok"].T if cfg.tie_embeddings else params["head"]["w"]
    return shard_act(x @ w, "logits")


def encode(params: dict, frames: jax.Array, cfg: ModelConfig, gather_specs=None) -> jax.Array:
    """Encoder stack over precomputed frontend embeddings (B, S_src, D)."""
    enc = params["encoder"]
    x = shard_act(frames, "bsd")

    def body(carry, bp):
        x = carry
        pp = gather_weights(bp, gather_specs)
        h = L.apply_norm(pp["norm1"], x, cfg.norm, cfg.norm_eps)
        b, s, d = h.shape
        hd = cfg.resolved_head_dim
        q = (h @ pp["attn"]["wq"]).reshape(b, s, cfg.num_heads, hd)
        k = (h @ pp["attn"]["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
        v = (h @ pp["attn"]["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
        pos = jnp.arange(s)
        q = L.apply_rope(q, pos, cfg.rope_theta, cfg.rotary_fraction)
        k = L.apply_rope(k, pos, cfg.rope_theta, cfg.rotary_fraction)
        o = L.blockwise_attention(q, k, v, causal=False, block_kv=min(1024, s))
        x = x + checkpoint_name(o.reshape(b, s, -1) @ pp["attn"]["wo"], ACT)
        h2 = L.apply_norm(pp["norm2"], x, cfg.norm, cfg.norm_eps)
        x = x + checkpoint_name(L.apply_mlp(pp["mlp"], h2, cfg.mlp), ACT)
        return shard_act(x), None

    body_ck = jax.checkpoint(body, policy=_remat_policy("checkpoint", True))
    x, _ = jax.lax.scan(body_ck, x, enc["blocks"])
    return L.apply_norm(enc["final_norm"], x, cfg.norm, cfg.norm_eps)


def forward(
    params: dict,
    batch: dict,
    cfg: ModelConfig,
    *,
    runs: list[Run] | None = None,
    attn_impl: str = "blockwise",
    encoder_gather_specs=None,
) -> tuple[jax.Array, jax.Array]:
    """Training/prefill forward. ``batch`` keys: tokens (B,S) and optionally
    frames (B,S_src,D) [encdec] or patches (B,S_img,D) [vlm].
    Returns (hidden (B,S,D), aux_loss)."""
    x = embed_tokens(params, batch["tokens"], cfg)
    if cfg.frontend == "vision_patches" and "patches" in batch:
        x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
    memory = None
    if cfg.kind == "encdec":
        memory = encode(params, batch["frames"], cfg, gather_specs=encoder_gather_specs)
    if runs is None:
        runs = default_runs(cfg, params)
    x, aux = apply_runs(runs, x, cfg, memory=memory, attn_impl=attn_impl)
    if cfg.frontend == "vision_patches" and "patches" in batch:
        x = x[:, batch["patches"].shape[1] :]
    return x, aux
