"""Manual shard_map gradient sync (MemoryPlan.sync_mode="manual").

Covers the ISSUE-2 and ISSUE-3 acceptance criteria: numerics parity with the
xla path on a multi-device mesh (CI forces 4 CPU devices) for both manual
kinds — DDP-style replicated layouts and ZeRO-sharded layouts synced by the
compressed reduce-scatter — error-feedback residuals that carry across steps
(stacked per-device for replicated leaves, shard-sized for ZeRO leaves), s8
payloads visible in the compiled HLO (all-gathers for DDP, all-to-alls for
ZeRO), the 1-device fallback guard, the manual_sync_kind eligibility
lattice, the wire-cost calibration round trip, and the autotuner searching
sync_mode with calibrated factors."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.configs.base import ShapeConfig
from repro.core import cost_model as CM
from repro.core.plan import MemoryPlan
from repro.data.pipeline import SyntheticTokenPipeline
from repro.optim.adam import AdamConfig
from repro.train.step_builder import build_train_step

N_DEV = len(jax.devices())
TINY = reduced(ARCHS["llama3-405b"])
SHAPE = ShapeConfig("tiny", 32, 16, "train")  # local batch 16/N_DEV per device

needs_multi_device = pytest.mark.skipif(
    N_DEV < 2 or 16 % N_DEV != 0,
    reason="manual-vs-xla parity needs a multi-device mesh (CI forces 4)",
)


def dp_mesh(n=None):
    n = n if n is not None else N_DEV
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def run_steps(plan, mesh, steps=10, lr=3e-3, seed=0):
    art = build_train_step(TINY, plan, mesh, SHAPE, adam=AdamConfig(lr=lr))
    state = art.init(jax.random.PRNGKey(seed))
    jfn = jax.jit(art.fn, donate_argnums=(0,))
    pipe = SyntheticTokenPipeline(TINY, SHAPE, seed=0)
    losses, metrics = [], None
    for _ in range(steps):
        state, metrics = jfn(state, pipe.next_sync())
        losses.append(float(metrics["loss"]))
    return art, state, losses, metrics


def persist_plan(**kw):
    return MemoryPlan(n_chunks=4, n_blocks=2, n_persist=4, **kw)


def zero_plan(n_persist=0, **kw):
    return MemoryPlan(n_chunks=4, n_blocks=2, n_persist=n_persist, **kw)


# ---------------------------------------------------------------------------
# numerics parity + EF carry-over
# ---------------------------------------------------------------------------
@needs_multi_device
@pytest.mark.parametrize("n_persist,zero_stage",
                         [(4, 3), (0, 2), (0, 3)],
                         ids=["ddp", "zero2", "zero3"])
def test_manual_matches_xla_losses_over_ten_steps(n_persist, zero_stage):
    """Acceptance (ISSUE-2 ddp, ISSUE-3 zero2, ISSUE-4 zero3): int8+EF manual
    sync tracks the xla path within bf16 tolerance over >= 10 steps for the
    replicated (gather-synced) layout and both ZeRO-sharded dataflows —
    up-front gather ("zero2") and lazy per-chunk gather with the
    reduce-scatter transpose ("zero3"). The paths quantize before vs after
    the reduce, so they are not bitwise equal — EF keeps them together."""
    mesh = dp_mesh()
    _, _, l_xla, _ = run_steps(
        zero_plan(n_persist, grad_compress="int8_ef", sync_mode="xla"), mesh)
    _, _, l_man, m_man = run_steps(
        zero_plan(n_persist, grad_compress="int8_ef", sync_mode="manual",
                  zero_stage=zero_stage), mesh)
    assert all(np.isfinite(l_man))
    # bf16 has ~8 mantissa bits: tolerate ~2 ulp of relative drift
    np.testing.assert_allclose(l_man, l_xla, rtol=2e-2)
    assert float(m_man["ef_norm"]) > 0


@needs_multi_device
def test_manual_int8_payload_is_on_the_wire():
    """The compiled manual program must move s8 payloads (real compression),
    and must contain no fp32 gradient all-reduce."""
    mesh = dp_mesh()
    art = build_train_step(
        TINY, persist_plan(grad_compress="int8_ef", sync_mode="manual"), mesh, SHAPE)
    hlo = art.lower(donate=False).compile().as_text()
    s8_gathers = [ln for ln in hlo.splitlines() if "all-gather(" in ln and "s8[" in ln]
    assert s8_gathers, "expected int8 all-gathers in the manual-sync HLO"


@needs_multi_device
@pytest.mark.parametrize("zero_stage", [2, 3], ids=["zero2", "zero3"])
def test_manual_zero_int8_reduce_scatter_on_the_wire_and_shard_ef(zero_stage):
    """Acceptance (ISSUE-3/4): a ZeRO-sharded manual plan compiles to s8
    scatter-equivalent collectives (all_to_all of the quantized chunks) in
    both dataflows, and its EF residuals are shard-sized on each device yet
    globally checkpointable (full logical shape, sharded layout)."""
    mesh = dp_mesh()
    plan = zero_plan(grad_compress="int8_ef", sync_mode="manual",
                     zero_stage=zero_stage)
    art = build_train_step(TINY, plan, mesh, SHAPE)
    hlo = art.lower(donate=False).compile().as_text()
    s8_a2a = [ln for ln in hlo.splitlines() if "all-to-all" in ln and "s8[" in ln]
    assert s8_a2a, "expected s8 all-to-alls (compressed reduce-scatter) in HLO"

    state = art.init(jax.random.PRNGKey(0))
    jfn = jax.jit(art.fn, donate_argnums=(0,))
    pipe = SyntheticTokenPipeline(TINY, SHAPE, seed=0)
    state, _ = jfn(state, pipe.next_sync())

    from repro.dist.sharding import leaf_sync_dim, zero_axes

    axes = zero_axes(mesh)
    ef_leaves = jax.tree.leaves(state["ef"])
    param_leaves = jax.tree.leaves(state["params"])
    sharded = 0
    for e, p in zip(ef_leaves, param_leaves):
        if e.shape == p.shape:
            # ZeRO-sharded residual: full logical (= param) shape, laid out
            # in the gradient's own sharded spec — checkpointable, and each
            # device holds only its 1/N_DEV shard
            d = leaf_sync_dim(e.sharding, axes)
            assert d is not None
            sharded += 1
            local = e.addressable_shards[0].data.shape
            assert local[d] == e.shape[d] // N_DEV
        else:
            # replicated leaf: stacked per-device residual, as in DDP
            assert e.shape == (N_DEV,) + p.shape
    assert sharded > 0, "zero plan should have ZeRO-sharded EF leaves"
    # the residuals are checkpoint round-trippable as plain arrays
    as_np = [np.asarray(e) for e in ef_leaves]
    assert any(np.abs(a).max() > 0 for a in as_np)


@needs_multi_device
def test_manual_ef_residual_carries_across_steps():
    mesh = dp_mesh()
    plan = persist_plan(grad_compress="int8_ef", sync_mode="manual")
    art, state, _, _ = run_steps(plan, mesh, steps=1)
    # manual EF is device-varying state, stored stacked (n_sync leading axis,
    # sharded over the sync axes) so checkpoints see every device's residual
    for leaf in jax.tree.leaves(state["ef"]):
        assert leaf.shape[0] == N_DEV
    ef1 = [np.asarray(x) for x in jax.tree.leaves(state["ef"])]
    assert any(np.abs(e).max() > 0 for e in ef1)  # quantization dropped something
    # the per-device slices genuinely differ (each fed back its own error)
    assert any(
        np.abs(e[0] - e[1]).max() > 0 for e in ef1 if e.shape[0] > 1
    )

    jfn = jax.jit(art.fn, donate_argnums=(0,))
    pipe = SyntheticTokenPipeline(TINY, SHAPE, seed=1)
    state2, _ = jfn(state, pipe.next_sync())
    ef2 = [np.asarray(x) for x in jax.tree.leaves(state2["ef"])]
    # the residual is live state: it keeps changing as new error feeds back
    assert any(np.abs(a - b).max() > 0 for a, b in zip(ef1, ef2))


@needs_multi_device
def test_manual_microbatch_sync_per_microbatch():
    mesh = dp_mesh()
    plan = persist_plan(grad_compress="int8_ef", sync_mode="manual",
                        microbatch=2)
    _, state, losses, metrics = run_steps(plan, mesh, steps=3)
    assert all(np.isfinite(losses))
    assert float(metrics["ef_norm"]) > 0


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------
def test_manual_one_device_mesh_falls_back_to_local_math():
    """Same guard policy as the mesh-size checks in dist/collectives.py: a
    1-device mesh takes the local math path (wire numerics, no collectives) —
    for both eligibility kinds."""
    mesh = dp_mesh(1)
    for plan in (persist_plan(grad_compress="int8_ef", sync_mode="manual"),
                 zero_plan(grad_compress="int8_ef", sync_mode="manual")):
        _, _, losses, metrics = run_steps(plan, mesh, steps=2)
        assert all(np.isfinite(losses))
        assert float(metrics["ef_norm"]) > 0


def test_manual_rejects_unlowerable_layouts():
    # eligibility is validated on every mesh size — including 1 device, so
    # locally-exercised code fails the same way it would deployed. ZeRO
    # plans lower since the sync-strategy layer; swap/host/zero1 still raise.
    bad = [
        zero_plan(n_swap=1, grad_compress="int8_ef", sync_mode="manual"),
        zero_plan(n_host=2, grad_compress="int8_ef", sync_mode="manual"),
        persist_plan(zero1_persistent=True, grad_compress="int8_ef",
                     sync_mode="manual"),
    ]
    for plan in bad:
        for n in {1, N_DEV}:
            with pytest.raises(ValueError, match="manual"):
                build_train_step(TINY, plan, dp_mesh(n), SHAPE)


def test_search_rejects_manual_sync_without_compression():
    from repro.core import TPU_V5E, build_workload, search
    from repro.core.hardware import MeshSpec

    w = build_workload(TINY, SHAPE, MeshSpec((4,), ("data",)), TPU_V5E)
    with pytest.raises(ValueError, match="manual"):
        search(w, compress="off", sync="manual")


LATTICE = [
    # (n_persist, n_host, n_swap, tp, dp_only, zero1) -> expected kind
    # (default zero_stage=3; the zero_stage=2 mapping is tested below)
    ((4, 0, 0, 1, False, False), "ddp"),
    ((4, 0, 0, 4, False, False), None),    # TP shards the params
    ((4, 0, 0, 4, True, False), "ddp"),    # dp_only absorbs the model axis
    ((0, 0, 0, 1, False, False), "zero3"),  # ISSUE-4: lazy gather by default
    ((2, 0, 0, 1, False, False), "zero3"),  # mixed persist/ZeRO
    ((0, 0, 0, 1, True, False), "zero3"),   # dp_only moot at tp=1
    ((0, 0, 0, 4, False, False), None),    # ZeRO + live TP axis: no kind
    ((0, 0, 0, 4, True, False), None),     # dp_only can't fix shard-axis
    ((0, 2, 0, 1, False, False), None),    # host memory kinds in shard_map
    ((4, 0, 1, 1, False, False), None),    # swap offload in shard_map
    ((0, 0, 1, 1, False, False), None),
    ((4, 0, 0, 1, False, True), None),     # zero1_persistent
    ((2, 0, 0, 1, False, True), None),
]


@pytest.mark.parametrize("cell,kind", LATTICE)
def test_manual_sync_kind_lattice(cell, kind):
    """manual_sync_kind over the plan lattice (persist x host x swap x TP x
    dp_only x zero1): ZeRO-sharded eligible plans report "zero3" (the lazy
    default), ineligible combinations still report None (and raise in
    build_train_step — see test_manual_rejects_unlowerable_layouts)."""
    n_persist, n_host, n_swap, tp, dp_only, zero1 = cell
    plan = MemoryPlan(4, 2, n_persist=n_persist, n_host=n_host, n_swap=n_swap,
                      dp_only=dp_only, zero1_persistent=zero1)
    assert plan.manual_sync_kind(tp_degree=tp) == kind
    # manual_sync_ok stays the "can lower at all" predicate
    assert plan.manual_sync_ok(tp) == (kind is not None)


@pytest.mark.parametrize("cell,kind", LATTICE)
def test_manual_sync_kind_lattice_zero_stage2(cell, kind):
    """zero_stage=2 flips only the ZeRO verdicts ("zero3" -> "zero2"); the
    ddp/None cells are independent of the dataflow knob."""
    n_persist, n_host, n_swap, tp, dp_only, zero1 = cell
    plan = MemoryPlan(4, 2, n_persist=n_persist, n_host=n_host, n_swap=n_swap,
                      dp_only=dp_only, zero1_persistent=zero1, zero_stage=2)
    expected = "zero2" if kind == "zero3" else kind
    assert plan.manual_sync_kind(tp_degree=tp) == expected


# ---------------------------------------------------------------------------
# wire-cost calibration: fit -> JSON -> cost model
# ---------------------------------------------------------------------------
def test_calibration_roundtrip(tmp_path):
    path = tmp_path / "wire_calibration.json"
    doc = {
        "generated_by": "test",
        "backends": {
            jax.default_backend(): {
                "wire_factors": {
                    "xla": {"none": 1.0, "bf16": 1.0, "int8_ef": 0.9},
                    "manual": {"none": 1.0, "bf16": 1.0, "int8_ef": 0.3},
                },
                "ef_residual_factor": 2.5,
            }
        },
    }
    path.write_text(json.dumps(doc))
    try:
        entry = CM.load_wire_calibration(str(path))
        assert entry is not None
        assert CM.wire_factor("xla", "int8_ef") == 0.9
        assert CM.wire_factor("manual", "int8_ef") == 0.3
        assert CM.ef_residual_factor() == 2.5
    finally:
        CM.reset_wire_calibration()


def test_calibration_for_another_backend_is_not_used(tmp_path, monkeypatch):
    """A file with entries for other backends only leaves the analytic
    defaults in force: a CPU-fit factor never prices a TPU plan."""
    path = tmp_path / "wire_calibration.json"
    path.write_text(json.dumps({"version": 2, "backends": {"cpu": {
        "wire_factors": {"manual": {"int8_ef": 0.123}},
        "ef_residual_factor": 7.0}}}))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        assert CM.load_wire_calibration(str(path)) is None
        assert CM.wire_factor("manual", "int8_ef") == \
            CM.DEFAULT_WIRE_FACTORS["manual"]["int8_ef"]
        assert CM.ef_residual_factor() == CM.DEFAULT_EF_RESIDUAL_FACTOR
    finally:
        CM.reset_wire_calibration()


def test_packaged_calibration_overrides_hardcoded_constant():
    """Acceptance: the autotuner's wire costs come from the calibration JSON,
    not the legacy GRAD_WIRE_FACTOR constant — the measured xla-path factor is
    1.0 (in-jit compression never touched the wire), where the constant
    claims 0.5."""
    CM.reset_wire_calibration()
    entry = CM.load_wire_calibration()
    assert entry is not None, "packaged src/repro/core/wire_calibration.json missing"
    assert CM.wire_factor("xla", "int8_ef") == 1.0
    assert CM.wire_factor("xla", "int8_ef") != CM.GRAD_WIRE_FACTOR["int8_ef"]
    assert CM.wire_factor("manual", "int8_ef") < 1.0  # real compression
    # the reduce-scatter pipeline's factor is calibrated too (ISSUE-3): the
    # s8 all_to_all payload is ~half the bf16 bytes at scatter topology
    assert CM.wire_factor("manual", "int8_ef_rs") < 1.0


def test_wire_factor_rs_falls_back_for_pre_zero_calibrations(tmp_path):
    """Calibration JSONs written before the reduce-scatter pipeline existed
    lack the int8_ef_rs key; wire_factor falls back to the analytic default
    instead of KeyError-ing the whole search."""
    path = tmp_path / "old.json"
    path.write_text(json.dumps({"backends": {jax.default_backend(): {
        "wire_factors": {"xla": {"none": 1.0, "bf16": 1.0, "int8_ef": 1.0},
                         "manual": {"none": 1.0, "bf16": 1.0, "int8_ef": 0.5}}}}}))
    try:
        CM.load_wire_calibration(str(path))
        assert CM.wire_factor("manual", "int8_ef_rs") == \
            CM.DEFAULT_WIRE_FACTORS["manual"]["int8_ef_rs"]
    finally:
        CM.reset_wire_calibration()


def test_t_reduce_zero_manual_uses_scatter_topology():
    """For a ZeRO-sharded chunk the manual int8 reduce moves (z-1)/z of the
    compressed bytes (all_to_all), vs the DDP gather pipeline's (z-1) full
    payloads for a persistent chunk — the new term the autotuner ranks with."""
    from repro.core import TPU_V5E, build_workload
    from repro.core.hardware import MeshSpec

    w = build_workload(TINY, SHAPE, MeshSpec((4, 1), ("data", "model")), TPU_V5E)
    chunk = w.chunks[1]
    z = w.mesh.zero_degree
    manual_zero = zero_plan(grad_compress="int8_ef", sync_mode="manual")
    manual_ddp = persist_plan(grad_compress="int8_ef", sync_mode="manual")
    t_rs = w.t_reduce(chunk, manual_zero)
    t_gather = w.t_reduce(chunk, manual_ddp)
    # same payload ratio, topologies differ by ~z: scatter divides by z
    np.testing.assert_allclose(t_gather / t_rs, z, rtol=0.1)
    # and the compressed reduce-scatter beats the uncompressed xla one
    t_xla = w.t_reduce(chunk, zero_plan(grad_compress="none", sync_mode="xla"))
    assert t_rs < t_xla


def test_t_reduce_uses_calibrated_factor(tmp_path):
    from repro.core import TPU_V5E, build_workload
    from repro.core.hardware import MeshSpec

    w = build_workload(TINY, SHAPE, MeshSpec((4, 1), ("data", "model")), TPU_V5E)
    chunk = w.chunks[1]
    base = persist_plan(grad_compress="int8_ef", sync_mode="xla")

    path = tmp_path / "cal.json"
    for factor in (1.0, 0.5):
        doc = {"backends": {jax.default_backend(): {
            "wire_factors": {"xla": {"none": 1.0, "bf16": 1.0, "int8_ef": factor},
                             "manual": {"none": 1.0, "bf16": 1.0, "int8_ef": 0.5}}}}}
        path.write_text(json.dumps(doc))
        CM.load_wire_calibration(str(path))
        if factor == 1.0:
            t_full = w.t_reduce(chunk, base)
        else:
            t_half = w.t_reduce(chunk, base)
    CM.reset_wire_calibration()
    np.testing.assert_allclose(t_half, t_full * 0.5)


# ---------------------------------------------------------------------------
# autotuner integration
# ---------------------------------------------------------------------------
def test_autotuner_searches_manual_sync_on_dp_mesh():
    from repro.core import TPU_V5E, build_workload, search
    from repro.core.hardware import MeshSpec

    w = build_workload(TINY, SHAPE, MeshSpec((4,), ("data",)), TPU_V5E)
    res = search(w, compress="on", sync="manual", allow_host=False, allow_swap=False)
    assert res.feasible
    assert res.plan.sync_mode == "manual"
    assert res.plan.grad_compress == "int8_ef"
    assert res.plan.manual_sync_ok(w.mesh.tp_degree)

    # default search (compress="auto", sync="auto") must also succeed and only
    # ever emit lowerable plans
    res2 = search(w)
    assert res2.feasible
    if res2.plan.sync_mode == "manual":
        assert res2.plan.manual_sync_ok(w.mesh.tp_degree)


def test_autotuner_emits_zero_manual_when_persist_does_not_fit():
    """ISSUE-3: manual candidates are no longer all-persist-or-nothing — when
    the replicated layout busts capacity, the search emits a ZeRO-sharded
    manual plan (kind "zero") ranked with the reduce-scatter wire term."""
    from repro.core import TPU_V5E, build_workload, estimate_memory, search
    from repro.core.hardware import MeshSpec

    w = build_workload(TINY, SHAPE, MeshSpec((4,), ("data",)), TPU_V5E)
    full = estimate_memory(
        w, persist_plan(grad_compress="int8_ef", sync_mode="manual")).peak
    lo = estimate_memory(
        w, zero_plan(grad_compress="int8_ef", sync_mode="manual")).peak
    assert lo < full  # sharding the states must save memory
    cap = (lo + full) / 2
    res = search(w, capacity_bytes=cap, compress="on", sync="manual",
                 allow_host=False, allow_swap=False)
    assert res.feasible
    assert res.plan.sync_mode == "manual"
    assert res.plan.n_persist < w.n_chunks
    assert res.plan.manual_sync_kind(w.mesh.tp_degree) in ("zero2", "zero3")
    assert res.memory.peak < cap
