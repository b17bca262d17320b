"""ISSUE-8: fused Pallas paged-attention decode kernel + fused int8 quantize.

Covers the acceptance criteria:
  * the decode-attention kernel (kernels/paged_attention.py) matches the
    ref.py oracle and the lax page-rebuild path *bitwise* — full-attention,
    SWA ring-wrap, hybrid Jamba, per-slot positions, and a 90-token
    engine-level decode;
  * the fused int8 quantize+pack kernel (kernels/fused_quant.py) matches
    the three-op absmax/round/residual sequence bitwise, including the EF
    residual round-trip, under hypothesis.

Exactness contract: each comparison jits the oracle as one program so both
sides see identical XLA fusion (the kernel body is always one traced
computation; an op-by-op eager oracle drifts by ~1 ulp from fused
multiply-adds — that drift belongs to the *oracle's* execution mode, not
the kernel). Under that discipline every assertion here is ``diff == 0.0``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config, reduced
from repro.configs.base import ShapeConfig
from repro.core.plan import MemoryPlan
from repro.kernels import ref as R
from repro.kernels.fused_quant import fused_quantize_ef
from repro.kernels.paged_attention import paged_attention
from repro.launch.mesh import make_local_mesh
from repro.models import kvcache as KV
from repro.models import model as M
from repro.serve import DecodeEngine, PagedKV, Request, choose_paging, init_paged_cache

KEY = jax.random.PRNGKey(0)

_pa_ref = jax.jit(R.paged_attention_ref)
_fq_ref = jax.jit(R.fused_quantize_ef_ref)


def _paged_inputs(key, b, hq, hkv, s, w, hd, masked_frac=0.2):
    ks = jax.random.split(key, 7)
    q = jax.random.normal(ks[0], (b, 1, hq, hd), jnp.float32)
    kh = jax.random.normal(ks[1], (b, w, hkv, hd), jnp.float32)
    vh = jax.random.normal(ks[2], (b, w, hkv, hd), jnp.float32)
    kc = jax.random.normal(ks[3], (b, s, hkv, hd), jnp.float32)
    vc = jax.random.normal(ks[4], (b, s, hkv, hd), jnp.float32)
    sel = jax.random.bernoulli(ks[5], 0.5, (b, s))
    mask = jnp.where(jax.random.bernoulli(ks[6], 1.0 - masked_frac, (b, s)),
                     0.0, -1e30).astype(jnp.float32)
    return q, kh, vh, kc, vc, sel, mask


# ---------------------------------------------------------------------------
# kernel vs ref.py oracle: synthetic sweeps, bitwise
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,hq,hkv,s,w,psz,hd", [
    (2, 8, 2, 64, 16, 8, 32),    # GQA 4:1, two hot pages
    (1, 4, 4, 32, 8, 8, 16),     # MHA, single hot page
    (3, 6, 3, 48, 24, 8, 64),    # GQA 2:1, three hot pages
    (2, 16, 1, 40, 8, 4, 8),     # MQA, small pages
])
def test_kernel_matches_oracle_bitwise(b, hq, hkv, s, w, psz, hd):
    args = _paged_inputs(jax.random.fold_in(KEY, s + w), b, hq, hkv, s, w, hd)
    out = paged_attention(*args, n_hot=w // psz, interpret=True)
    ref = _pa_ref(*args)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    assert float(jnp.abs(out - ref).max()) == 0.0


def test_kernel_fully_masked_rows_are_neutral():
    """A slot whose every non-causal position is masked must still produce
    finite output (the -1e30 additive mask keeps softmax well-defined) and
    agree with the oracle bitwise."""
    q, kh, vh, kc, vc, sel, _ = _paged_inputs(KEY, 2, 4, 2, 32, 8, 16)
    mask = jnp.where(jnp.arange(32)[None, :] < 1, 0.0, -1e30)
    mask = jnp.broadcast_to(mask, (2, 32)).astype(jnp.float32)
    out = paged_attention(q, kh, vh, kc, vc, sel, mask, n_hot=4, interpret=True)
    ref = _pa_ref(q, kh, vh, kc, vc, sel, mask)
    assert bool(jnp.isfinite(out).all())
    assert float(jnp.abs(out - ref).max()) == 0.0


# ---------------------------------------------------------------------------
# kernel vs the lax page-rebuild: decode drives through PagedKV.attend
# ---------------------------------------------------------------------------
def _drive_kernel_vs_lax(cfg, B, S, steps, page, hot, per_slot=False):
    """Decode ``steps`` tokens through two PagedKV hooks — the fused kernel
    vs the gather-then-attend lax rebuild — and return the worst logits
    divergence (must be 0.0: both reduce to _masked_decode_attn's op
    sequence)."""
    spec = choose_paging(KV.cache_len(cfg, S), page, hot)
    assert spec.n_cold > 0, "parity must exercise cold pages"
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    io_k = PagedKV(spec, use_kernel=True)
    io_l = PagedKV(spec, use_kernel=False)
    assert io_k.use_kernel and not io_l.use_kernel
    cache_k = init_paged_cache(cfg, B, S, spec)
    cache_l = init_paged_cache(cfg, B, S, spec)
    step_k = jax.jit(lambda c, t, p: KV.decode_step(params, c, t, p, cfg, kv_io=io_k))
    step_l = jax.jit(lambda c, t, p: KV.decode_step(params, c, t, p, cfg, kv_io=io_l))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, steps), 0, cfg.vocab_size)
    worst = 0.0
    for t in range(steps):
        pos = jnp.full((B,), t, jnp.int32) if per_slot else jnp.int32(t)
        lk, cache_k = step_k(cache_k, toks[:, t:t + 1], pos)
        ll, cache_l = step_l(cache_l, toks[:, t:t + 1], pos)
        worst = max(worst, float(jnp.abs(lk - ll).max()))
    return worst


@pytest.mark.parametrize("per_slot", [False, True])
def test_kernel_decode_parity_full_attention(per_slot):
    cfg = reduced(get_config("llama3-405b"))
    diff = _drive_kernel_vs_lax(cfg, B=4, S=64, steps=40, page=8, hot=2,
                                per_slot=per_slot)
    assert diff == 0.0, f"kernel decode diverged from lax rebuild: {diff}"


@pytest.mark.parametrize("hot", [1, 4])
def test_kernel_decode_parity_sliding_window_ring(hot):
    """Mixtral's ring cache, decoded far past the window: the ring wraps and
    the steady-state every-slot-valid mask exercises the stale-row rules the
    kernel's residency select must reproduce."""
    cfg = reduced(get_config("mixtral-8x22b"))
    assert cfg.sliding_window, "config must ring-buffer"
    diff = _drive_kernel_vs_lax(cfg, B=4, S=96, steps=90, page=8, hot=hot)
    assert diff == 0.0, f"SWA kernel decode diverged: {diff}"


def test_kernel_decode_parity_hybrid_mamba_resident():
    """Jamba: only the attention positions route through the kernel; mamba
    state stays O(1)-resident and must be untouched by the kv_io swap."""
    cfg = reduced(get_config("jamba-1.5-large-398b"))
    diff = _drive_kernel_vs_lax(cfg, B=4, S=64, steps=40, page=8, hot=2)
    assert diff == 0.0, f"hybrid kernel decode diverged: {diff}"


def test_engine_level_90_token_decode_resident_matches_paged():
    """90 generated tokens through the DecodeEngine stack (continuous
    batching, ring wrap) under resident and paged plans: identical streams.
    The engine's step-builder path host-shards the cold fetch (lax pipeline,
    see docs/kernels.md) — this guards the full stack around the kernel's
    dispatch seam, kernel-aware prefill-chunk pricing included."""
    cfg = reduced(get_config("mixtral-8x22b"))
    B, S = 2, 96
    mesh = make_local_mesh()
    shape = ShapeConfig("serve", S, B, "decode")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    spec = choose_paging(KV.cache_len(cfg, S), 8, 2)
    mk = lambda: [Request(0, [5, 9], 90)]  # noqa: E731
    rep_r = DecodeEngine(cfg, MemoryPlan(3, 2, n_persist=3), mesh, shape,
                         params).run(mk())
    rep_p = DecodeEngine(cfg, MemoryPlan(3, 2, n_persist=3, n_host=spec.n_cold),
                         mesh, shape, params, paging=spec).run(mk())
    assert rep_r.truncated == () and rep_p.truncated == ()
    assert len(rep_r.finished[0]) == 90
    assert rep_r.finished == rep_p.finished


# ---------------------------------------------------------------------------
# fused int8 quantize+pack vs the three-op sequence (hypothesis)
# ---------------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(
    z=st.integers(min_value=1, max_value=4),
    n=st.integers(min_value=1, max_value=257),
    me=st.integers(min_value=0, max_value=3),
    seed=st.integers(min_value=0, max_value=2**16),
    log_spread=st.integers(min_value=-3, max_value=4),
)
def test_fused_quantize_matches_three_op_bitwise(z, n, me, seed, log_spread):
    me = me % z
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    ch = (jax.random.normal(ks[0], (z, n), jnp.float32)
          * jnp.exp(jax.random.normal(ks[1], (z, 1)) * log_spread))
    qk, sk, ek = fused_quantize_ef(ch, me, interpret=True)
    qr, sr, er = _fq_ref(ch, me)
    assert qk.dtype == jnp.int8 and sk.dtype == jnp.float32
    assert int(jnp.abs(qk.astype(jnp.int32) - qr.astype(jnp.int32)).max()) == 0
    assert float(jnp.abs(sk - sr).max()) == 0.0
    assert float(jnp.abs(ek - er).max()) == 0.0
    # residual bound: reconstruction error of the owned chunk stays within
    # half a quantization step (scale = absmax/127, no clipping beyond it);
    # slack covers fp32 round-off in ch/scale and ch - q*scale near
    # half-integer quotients (~|q|*eps relative to the step, |q| <= 127)
    bound = float(sk[me]) * 0.5 * (1 + 1e-4) + 1e-30
    assert float(jnp.abs(ek).max()) <= bound


@settings(max_examples=10, deadline=None)
@given(
    me=st.integers(min_value=0, max_value=3),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_fused_quantize_ef_round_trip_matches_three_op(me, seed):
    """Iterated error feedback: feed each iteration's residual back into the
    next chunk (the wire loop of manual_int8_ef_reduce_scatter) on both
    paths; the full (q, scale, err) trajectory must stay bitwise equal."""
    z, n = 4, 64
    err_k = jnp.zeros((n,), jnp.float32)
    err_r = jnp.zeros((n,), jnp.float32)
    for it in range(5):
        ch = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed), it),
                               (z, n), jnp.float32) * 3.0
        qk, sk, err_k = fused_quantize_ef(ch.at[me].add(err_k), me, interpret=True)
        qr, sr, err_r = _fq_ref(ch.at[me].add(err_r), me)
        assert int(jnp.abs(qk.astype(jnp.int32) - qr.astype(jnp.int32)).max()) == 0
        assert float(jnp.abs(sk - sr).max()) == 0.0
        assert float(jnp.abs(err_k - err_r).max()) == 0.0
    assert float(jnp.abs(err_k).max()) > 0.0, "EF must accumulate something"


@pytest.mark.skipif(len(jax.devices()) < 2, reason="reduce-scatter needs >1 device")
def test_reduce_scatter_fused_vs_unfused_paths_agree():
    """manual_int8_ef_reduce_scatter under shard_map: the fused-kernel and
    three-op dispatches agree to fp32 fusion noise (inside one jit XLA may
    FMA-fuse the unfused residual subtract — ~1 ulp of the chunk scale; the
    bitwise contract is covered above where both paths jit alone)."""
    from jax.sharding import PartitionSpec as P

    from repro.dist.collectives import (
        manual_int8_ef_reduce_scatter,
        set_fused_quant,
    )

    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rows = 4 * n_dev
    g = jax.random.normal(jax.random.PRNGKey(0), (n_dev, rows, 6), jnp.float32)
    err0 = jnp.zeros((n_dev, rows // n_dev, 6), jnp.float32)

    def body(gl, el):
        s, ne = manual_int8_ef_reduce_scatter(gl[0], el[0], ("data",), 0)
        return s[None], ne[None]

    def run():
        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("data", None, None), P("data", None, None)),
            out_specs=(P("data", None, None), P("data", None, None)),
            check_vma=False))(g, err0)

    try:
        set_fused_quant(True)
        s_f, e_f = run()
        set_fused_quant(False)
        s_u, e_u = run()
    finally:
        set_fused_quant(None)
    scale_step = float(jnp.abs(g).max()) / 127.0
    np.testing.assert_allclose(np.asarray(s_f), np.asarray(s_u),
                               atol=scale_step * 1e-5)
    np.testing.assert_allclose(np.asarray(e_f), np.asarray(e_u),
                               atol=scale_step * 1e-5)


# ---------------------------------------------------------------------------
# dispatch plumbing
# ---------------------------------------------------------------------------
def test_package_dispatch_and_gating():
    """The package-level entry point runs the kernel; PagedKV uses it by
    default and *always* drops to lax under a host-sharded fetch plan
    (pallas_call is unpartitionable and cannot read host memory spaces)."""
    from repro import kernels as K

    args = _paged_inputs(KEY, 1, 4, 2, 16, 8, 8)
    out = K.decode_paged_attention(*args, n_hot=2)
    ref = _pa_ref(*args)
    assert out.shape == ref.shape
    assert float(jnp.abs(out - ref).max()) == 0.0

    spec = choose_paging(16, 4, 2)
    assert PagedKV(spec).use_kernel is True
    assert PagedKV(spec, fetch_sharding=object()).use_kernel is False
    assert PagedKV(spec, use_kernel=False).use_kernel is False
