"""ISSUE-5: paged KV-cache serving subsystem.

Covers the acceptance criteria:
  * host-paged decode produces logits *identical* (bitwise) to resident
    decode over >= 32 generated tokens, full-attention and sliding-window
    (ring wrap) cases, on the 4-device CI mesh — scalar and per-slot
    positions;
  * the continuous-batching scheduler leaks no slots or pages across
    admit/evict/finish cycles (hypothesis property tests);
  * serve_plan emits a paged candidate (n_host > 0) whenever the resident
    cache exceeds the HBM budget while the weights still fit;
  * the decode engine serves a request stream with identical results under
    resident and paged plans, reporting a real HBM cache reduction.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config, reduced
from repro.configs.base import ShapeConfig
from repro.core.hardware import HardwareSpec, LOCAL_CPU_HW, MeshSpec
from repro.core.plan import MemoryPlan
from repro.core.serve_plan import paging_from_plan, serve_memory_estimate, serve_plan
from repro.launch.mesh import make_local_mesh
from repro.models import kvcache as KV
from repro.models import model as M
from repro.serve import (
    ContinuousScheduler,
    DecodeEngine,
    PagePool,
    PagedKV,
    Request,
    choose_paging,
    init_paged_cache,
)

MESH1 = MeshSpec((1, 1), ("data", "model"))


def _drive_parity(cfg, B, S, steps, page, hot, per_slot=False):
    spec = choose_paging(KV.cache_len(cfg, S), page, hot)
    assert spec.n_cold > 0, "parity must exercise cold fetches"
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    cache_r = KV.init_cache(cfg, B, S)
    cache_p = init_paged_cache(cfg, B, S, spec)
    io = PagedKV(spec)
    step_r = jax.jit(lambda c, t, p: KV.decode_step(params, c, t, p, cfg))
    step_p = jax.jit(lambda c, t, p: KV.decode_step(params, c, t, p, cfg, kv_io=io))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, steps), 0, cfg.vocab_size)
    worst = 0.0
    for t in range(steps):
        pos = jnp.full((B,), t, jnp.int32) if per_slot else jnp.int32(t)
        lr, cache_r = step_r(cache_r, toks[:, t:t + 1], pos)
        lp, cache_p = step_p(cache_p, toks[:, t:t + 1], pos)
        worst = max(worst, float(jnp.abs(lr - lp).max()))
    return worst


@pytest.mark.parametrize("per_slot", [False, True])
def test_paged_decode_parity_full_attention(per_slot):
    cfg = reduced(get_config("llama3-405b"))
    diff = _drive_parity(cfg, B=4, S=64, steps=40, page=8, hot=2, per_slot=per_slot)
    assert diff == 0.0, f"paged decode diverged from resident: {diff}"


@pytest.mark.parametrize("hot", [1, 2, 4])
def test_paged_decode_parity_sliding_window_ring(hot):
    """Mixtral's ring cache: decode far past the window so the ring wraps
    and the steady-state every-slot-valid mask exercises stale-row rules."""
    cfg = reduced(get_config("mixtral-8x22b"))
    assert cfg.sliding_window, "config must ring-buffer"
    diff = _drive_parity(cfg, B=4, S=96, steps=90, page=8, hot=hot)
    assert diff == 0.0, f"SWA paged decode diverged: {diff}"


def test_paged_decode_parity_hybrid_mamba_resident():
    """Jamba: attention positions page, mamba state stays O(1)-resident."""
    cfg = reduced(get_config("jamba-1.5-large-398b"))
    diff = _drive_parity(cfg, B=4, S=64, steps=40, page=8, hot=2)
    assert diff == 0.0, f"hybrid paged decode diverged: {diff}"


def test_paged_step_builder_parity_on_ci_mesh():
    """build_decode_step(paging=...) on the forced 4-device mesh: the full
    jit path with host memory kinds, >= 32 tokens, identical samples."""
    cfg = reduced(get_config("llama3-405b"))
    B, S = 4, 64
    mesh = make_local_mesh()
    shape = ShapeConfig("serve", S, B, "decode")
    spec = choose_paging(KV.cache_len(cfg, S), 8, 2)
    plan_r = MemoryPlan(n_chunks=3, n_blocks=2, n_persist=3)
    plan_p = MemoryPlan(n_chunks=3, n_blocks=2, n_persist=3, n_host=spec.n_cold)
    from repro.train.step_builder import build_decode_step

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    art_r = build_decode_step(cfg, plan_r, mesh, shape)
    art_p = build_decode_step(cfg, plan_p, mesh, shape, paging=spec)
    # cold leaves really live in the platform's host memory space
    from repro.compat import host_memory_kind

    kind = host_memory_kind(mesh)
    for entry in art_p.state_shardings["cache"].values():
        assert entry["k_cold"].memory_kind == kind
        assert entry["v_cold"].memory_kind == kind
    step_r = jax.jit(art_r.fn)
    step_p = jax.jit(art_p.fn)
    cache_r = jax.tree.map(jax.device_put, KV.init_cache(cfg, B, S),
                           art_r.state_shardings["cache"])
    cache_p = init_paged_cache(cfg, B, S, spec,
                               shardings=art_p.state_shardings["cache"])
    st_r = {"params": params, "cache": cache_r}
    st_p = {"params": params, "cache": cache_p}
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 33), 0, cfg.vocab_size)
    for t in range(33):
        batch = {"tokens": toks[:, t:t + 1], "pos": jnp.int32(t)}
        st_r, nr = step_r(st_r, batch)
        st_p, np_ = step_p(st_p, batch)
        assert bool((nr == np_).all()), f"sampled tokens diverged at step {t}"


# ---------------------------------------------------------------------------
# Scheduler properties: no slot/page leaks across admit/evict/finish cycles
# ---------------------------------------------------------------------------
def _check_invariants(sched: ContinuousScheduler, submitted: set[int]):
    pool = sched.pool
    held = sum(pool.held_by(b) for b in range(sched.n_slots))
    assert pool.n_free + held == pool.n_pages, "page leak"
    assert len(pool._owner) == held, "orphaned page ownership"
    for b, s in enumerate(sched.slots):
        if s is None:
            assert pool.held_by(b) == 0, f"freed slot {b} still owns pages"
        else:
            assert pool.held_by(b) >= 1, f"live slot {b} owns no pages"
    live = {s.rid for s in sched.slots if s is not None}
    queued = {r.rid for r in sched.queue}
    done = set(sched.finished) | set(sched.rejected)
    assert live | queued | done == submitted, "request leaked or invented"
    assert not (live & done) and not (queued & done), "request in two states"


@settings(max_examples=30, deadline=None)
@given(
    n_slots=st.integers(min_value=1, max_value=4),
    pool_pages=st.integers(min_value=1, max_value=12),
    page_size=st.integers(min_value=1, max_value=4),
    reqs=st.lists(
        st.tuples(st.integers(min_value=1, max_value=5),   # prompt len
                  st.integers(min_value=1, max_value=6)),  # max_new
        min_size=1, max_size=8),
    evict_every=st.integers(min_value=0, max_value=5),
)
def test_scheduler_no_slot_or_page_leaks(n_slots, pool_pages, page_size,
                                         reqs, evict_every):
    cache_len = 16
    sched = ContinuousScheduler(n_slots, PagePool(pool_pages), page_size, cache_len)
    submitted = set()
    for i, (pl, mn) in enumerate(reqs):
        sched.submit([Request(i, list(range(1, pl + 1)), mn)])
        submitted.add(i)
    for step in range(200):
        if sched.idle:
            break
        sched.admit()
        _check_invariants(sched, submitted)
        toks, _, _ = sched.step_inputs()
        sched.advance([t + 1 for t in toks])
        if evict_every and step % evict_every == evict_every - 1:
            sched._evict_youngest()
        _check_invariants(sched, submitted)
    # every request reached a terminal state (finished or rejected)
    assert sched.idle, "scheduler failed to drain"
    assert set(sched.finished) | set(sched.rejected) == submitted


def test_scheduler_finishes_exact_token_counts():
    sched = ContinuousScheduler(2, PagePool(8), 4, 16)
    sched.submit([Request(0, [1, 2, 3], 4), Request(1, [5], 2), Request(2, [9, 9], 3)])
    for _ in range(100):
        if sched.idle:
            break
        sched.admit()
        toks, _, _ = sched.step_inputs()
        sched.advance([t + 1 for t in toks])
    assert {k: len(v) for k, v in sched.finished.items()} == {0: 4, 1: 2, 2: 3}


# ---------------------------------------------------------------------------
# Planner: paged candidates + memory estimate
# ---------------------------------------------------------------------------
def _tight_hw(hbm_gb: float) -> HardwareSpec:
    return dataclasses.replace(LOCAL_CPU_HW, hbm_bytes=hbm_gb * 1e9,
                               host_bw=1e12)  # fast link: fetch feasible


def test_serve_plan_emits_paged_candidate_when_cache_overflows():
    cfg = reduced(get_config("llama3-405b"), num_layers=4)
    shape = ShapeConfig("serve", 32_768, 64, "decode")
    # generous HBM: resident; tight HBM (cache >> weights): paged
    roomy = serve_plan(cfg, shape, MESH1, _tight_hw(1000.0))
    assert roomy.n_persist == roomy.n_chunks and roomy.n_host == 0
    tight = serve_plan(cfg, shape, MESH1, _tight_hw(3.0))
    assert tight.n_host > 0, "resident cache exceeds budget: must page"
    assert tight.n_persist == tight.n_chunks, "weights fit: stay persistent"
    spec = paging_from_plan(cfg, shape, tight)
    assert spec is not None and spec.n_cold == tight.n_host
    est = serve_memory_estimate(cfg, shape, MESH1, tight)
    resident_est = serve_memory_estimate(
        cfg, shape, MESH1, MemoryPlan(tight.n_chunks, tight.n_blocks,
                                      n_persist=tight.n_chunks))
    assert est["peak_gb"] < resident_est["peak_gb"], "paging must shrink HBM"
    assert est["host_cache_gb"] > 0
    assert est["peak_gb"] < _tight_hw(3.0).capacity_bytes() / 1e9


def test_serve_plan_prefers_larger_hot_windows_on_faster_links():
    cfg = reduced(get_config("llama3-405b"), num_layers=4)
    shape = ShapeConfig("serve", 32_768, 64, "decode")
    slow = dataclasses.replace(_tight_hw(3.0), host_bw=1e6)
    fast = _tight_hw(3.0)
    p_slow, p_fast = (serve_plan(cfg, shape, MESH1, h) for h in (slow, fast))
    # both page; the slow link cannot make any window feasible, so it falls
    # back to the largest *fitting* window — never more cold pages than fast
    assert p_slow.n_host > 0 and p_fast.n_host > 0
    assert p_slow.n_host <= p_fast.n_host or p_slow.n_host == p_fast.n_host


def test_serve_plan_shards_weights_when_weights_overflow():
    cfg = reduced(get_config("llama3-405b"), num_layers=4)
    shape = ShapeConfig("serve", 1024, 8, "decode")  # tiny cache
    hw = dataclasses.replace(LOCAL_CPU_HW, hbm_bytes=2e6)  # weights >> hbm
    plan = serve_plan(cfg, shape, MESH1, hw)
    assert plan.n_persist == 0 and plan.n_host == 0


def test_page_fetch_feasibility_mirrors_drain_check():
    from repro.core.cost_model import page_fetch_feasible, t_page_fetch

    cfg = reduced(get_config("llama3-405b"), num_layers=4)
    shape = ShapeConfig("serve", 32_768, 64, "decode")
    spec = choose_paging(KV.cache_len(cfg, shape.seq_len), 256, 4)
    fast = dataclasses.replace(LOCAL_CPU_HW, host_bw=1e13)
    slow = dataclasses.replace(LOCAL_CPU_HW, host_bw=1e3)
    assert page_fetch_feasible(cfg, shape, MESH1, fast, spec)
    assert not page_fetch_feasible(cfg, shape, MESH1, slow, spec)
    assert t_page_fetch(cfg, shape, MESH1, slow, spec) > t_page_fetch(
        cfg, shape, MESH1, fast, spec)


# ---------------------------------------------------------------------------
# Engine: continuous batching end-to-end, resident == paged
# ---------------------------------------------------------------------------
def test_engine_continuous_batching_resident_matches_paged():
    cfg = reduced(get_config("llama3-405b"))
    B, S = 4, 64
    mesh = make_local_mesh()
    shape = ShapeConfig("serve", S, B, "decode")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    spec = choose_paging(KV.cache_len(cfg, S), 8, 2)
    plan_r = MemoryPlan(n_chunks=3, n_blocks=2, n_persist=3)
    plan_p = MemoryPlan(n_chunks=3, n_blocks=2, n_persist=3, n_host=spec.n_cold)
    mk = lambda: [Request(i, [7 + i, 11, 13 + i], 5 + i) for i in range(6)]  # noqa: E731
    rep_r = DecodeEngine(cfg, plan_r, mesh, shape, params).run(mk())
    rep_p = DecodeEngine(cfg, plan_p, mesh, shape, params, paging=spec).run(mk())
    assert rep_r.finished == rep_p.finished, "paged engine diverged"
    assert set(rep_r.finished) == set(range(6))
    assert all(len(v) == 5 + i for i, v in sorted(rep_r.finished.items()))
    assert rep_p.hbm_cache_bytes < rep_p.resident_cache_bytes
    assert rep_p.host_cache_bytes > 0


def test_engine_sliding_window_wraps_past_cache_length():
    """Ring caches keep generating past the window (slot reuse); paged and
    resident engines agree through the wrap and nothing is truncated."""
    cfg = reduced(get_config("mixtral-8x22b"))
    B, S = 2, 48  # cache_len = min(sliding_window=64, 48) = 48
    mesh = make_local_mesh()
    shape = ShapeConfig("serve", S, B, "decode")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    spec = choose_paging(KV.cache_len(cfg, S), 8, 2)
    mk = lambda: [Request(0, [5, 9], 60)]  # 62 tokens total > 48 slots  # noqa: E731
    rep_r = DecodeEngine(cfg, MemoryPlan(3, 2, n_persist=3), mesh, shape,
                         params).run(mk())
    rep_p = DecodeEngine(cfg, MemoryPlan(3, 2, n_persist=3, n_host=spec.n_cold),
                         mesh, shape, params, paging=spec).run(mk())
    assert rep_r.truncated == () and rep_p.truncated == ()
    assert len(rep_r.finished[0]) == 60
    assert rep_r.finished == rep_p.finished


def test_engine_full_attention_truncates_at_cache_exhaustion():
    cfg = reduced(get_config("llama3-405b"))
    B, S = 2, 16
    mesh = make_local_mesh()
    shape = ShapeConfig("serve", S, B, "decode")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rep = DecodeEngine(cfg, MemoryPlan(3, 2, n_persist=3), mesh, shape,
                       params).run([Request(0, [5, 9], 30)])
    assert rep.truncated == (0,), "cache exhaustion must be reported"
    assert len(rep.finished[0]) < 30
    assert rep.drained


def test_engine_staggered_admission_matches_dedicated_runs():
    """Requests admitted mid-stream (continuous batching) must decode the
    same tokens as a dedicated single-request engine run."""
    cfg = reduced(get_config("llama3-405b"))
    B, S = 2, 64
    mesh = make_local_mesh()
    shape = ShapeConfig("serve", S, B, "decode")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    reqs = [Request(i, [3 + 2 * i, 17 + i], 6) for i in range(4)]
    batched = DecodeEngine(cfg, MemoryPlan(3, 2, n_persist=3), mesh, shape,
                           params).run([Request(r.rid, list(r.prompt), 6)
                                        for r in reqs])
    for r in reqs:
        solo = DecodeEngine(cfg, MemoryPlan(3, 2, n_persist=3), mesh, shape,
                            params).run([Request(r.rid, list(r.prompt), 6)])
        assert solo.finished[r.rid] == batched.finished[r.rid], (
            f"request {r.rid}: continuous batching changed its tokens")
