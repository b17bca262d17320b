"""Per-block activation policies (ISSUE-9).

Tentpole acceptance beyond the plan/cost-model unit checks:

  * quantize-on-save / dequantize-on-use (models/model.compress_act) is a
    faithful save format: 10-step loss parity against the exact (keep-all)
    run within bf16 tolerance for compress8, compress16, and mixed vectors,
    on BOTH sync paths (xla sharded and manual zero3 lazy-gather);
  * the compression is real, not just modeled: on the deeper 8-layer toy the
    compiled XLA buffer assignment keeps strictly less temp memory live for
    a compress8 plan than for keep-all;
  * the greedy policy search (autotuner.search_act_policies) is
    deterministic and, at a budget where keep-all is infeasible, its vector
    models a strictly lower step time than uniform remat-all — the best
    feasible uniform policy;
  * the scalar knobs (n_checkpoint / n_swap) lower onto the vector without
    behavior change, so every pre-ISSUE-9 plan string and test stays valid;
  * the calibration JSON stays forward-compatible: files predating the
    ``act_compress`` factor load with the analytic default.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import ARCHS, reduced
from repro.configs.base import ShapeConfig
from repro.core import cost_model as CM
from repro.core.plan import MemoryPlan
from repro.data.pipeline import SyntheticTokenPipeline
from repro.models.model import compress_act
from repro.optim.adam import AdamConfig
from repro.train.step_builder import build_train_step

N_DEV = len(jax.devices())
TINY = reduced(ARCHS["llama3-405b"])
SHAPE = ShapeConfig("tiny", 32, 16, "train")
DEEP = dataclasses.replace(reduced(ARCHS["llama3-405b"]), num_layers=8,
                           d_model=256, d_ff=1024, vocab_size=1024)

needs_multi_device = pytest.mark.skipif(
    N_DEV < 2 or 16 % N_DEV != 0,
    reason="parity cells assume the CI mesh (4 forced CPU devices)",
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
    losses = []
    for _ in range(steps):
        state, metrics = jfn(state, pipe.next_sync())
        losses.append(float(metrics["loss"]))
    return losses


# ---------------------------------------------------------------------------
# plan lowering / describe
# ---------------------------------------------------------------------------
def test_scalar_knobs_lower_to_uniform_vector():
    """n_checkpoint/n_swap and an equivalent explicit vector agree block by
    block, so the vector is a strict generalization of the scalar plans."""
    scalar = MemoryPlan(n_chunks=4, n_blocks=4, n_checkpoint=2)
    vector = MemoryPlan(n_chunks=4, n_blocks=4,
                        act_policies=("checkpoint", "checkpoint",
                                      "none", "none"))
    assert scalar.block_policies() == vector.block_policies()
    for b in range(4):
        assert scalar.block_policy(b) == vector.block_policy(b)


def test_policy_aliases_and_validation():
    p = MemoryPlan(n_chunks=4, n_blocks=2, act_policies=("keep", "remat"))
    assert tuple(p.block_policies()) == ("none", "checkpoint")
    assert p.compressed_blocks() == 0
    q = MemoryPlan(n_chunks=4, n_blocks=2,
                   act_policies=("compress8", "compress16"))
    assert q.compressed_blocks() == 2
    with pytest.raises(AssertionError):
        MemoryPlan(n_chunks=4, n_blocks=2, act_policies=("none",))  # length
    with pytest.raises(AssertionError):
        MemoryPlan(n_chunks=4, n_blocks=2, act_policies=("none", "fp4"))
    with pytest.raises(AssertionError):  # vector and scalar knobs conflict
        MemoryPlan(n_chunks=4, n_blocks=2, n_checkpoint=1,
                   act_policies=("none", "none"))


def test_describe_reports_policy_vector_overlap_and_zero_stage():
    man = MemoryPlan(n_chunks=4, n_blocks=2, grad_compress="int8_ef",
                     sync_mode="manual", zero_stage=3)
    d = man.describe()
    assert "zstage=3" in d and "overlap=on" in d
    ser = dataclasses.replace(man, overlap=False).describe()
    assert "overlap=off" in ser
    grp = MemoryPlan(n_chunks=4, n_blocks=4, n_checkpoint=4,
                     ckpt_group=2).describe()
    assert "ckptg=2" in grp
    vec = MemoryPlan(n_chunks=4, n_blocks=4,
                     act_policies=("compress8", "compress8", "checkpoint",
                                   "none")).describe()
    assert "acts=compress8x2,checkpoint,none" in vec


# ---------------------------------------------------------------------------
# compress seam round-trip (hypothesis)
# ---------------------------------------------------------------------------
@given(seed=st.integers(0, 2**31 - 1),
       mode=st.sampled_from(["compress8", "compress16"]))
@settings(max_examples=15, deadline=None)
def test_compress_act_roundtrip_and_straight_through_grad(seed, mode):
    """The quantize-on-save custom_vjp: dequantized values stay within the
    format's tolerance (int8 absmax rowwise: half an LSB of the row scale;
    bf16 downcast: one bf16 ulp), and the gradient is exactly the identity
    (straight-through to the uncompressed input — AD never sees the kernel)."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (2, 3, 32), jnp.float32) * 3.0
    y = np.asarray(compress_act(x, mode))
    xr = np.asarray(x).reshape(-1, 32)
    if mode == "compress8":
        scale = np.maximum(np.abs(xr).max(axis=1), 1e-30) / 127.0
        tol = (scale * 0.5 + 1e-7)[:, None]
    else:
        tol = np.abs(xr) * 2.0 ** -8 + 1e-7
    np.testing.assert_array_less(np.abs(y.reshape(-1, 32) - xr),
                                 np.broadcast_to(tol, xr.shape))

    w = jax.random.normal(jax.random.fold_in(key, 1), x.shape, jnp.float32)
    g = jax.grad(lambda x: jnp.sum(compress_act(x, mode) * w))(x)
    # compress8's straight-through is exact; compress16's cotangent rides the
    # bf16 downcast pair, so the identity holds to one bf16 ulp
    rtol = 1e-6 if mode == "compress8" else 2.0 ** -7
    np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=rtol)


# ---------------------------------------------------------------------------
# end-to-end parity (tentpole acceptance)
# ---------------------------------------------------------------------------
@needs_multi_device
@pytest.mark.parametrize("pols", [
    ("compress8", "compress8"),
    ("compress16", "compress16"),
    ("compress8", "checkpoint"),
], ids=lambda p: "+".join(p))
def test_xla_loss_parity_compressed_vs_exact(pols):
    """10-step loss parity: a compressed-activation plan trains within bf16
    noise of the exact keep-all plan on the xla sharded path."""
    mesh = dp_mesh()
    exact = run_steps(MemoryPlan(n_chunks=4, n_blocks=2), mesh)
    comp = run_steps(
        MemoryPlan(n_chunks=4, n_blocks=2, act_policies=pols), mesh)
    np.testing.assert_allclose(comp, exact, rtol=2e-2)


@needs_multi_device
def test_manual_zero3_loss_parity_compressed_vs_exact():
    """Same parity on the manual zero3 lazy-gather path — the compress
    policy must compose with _save_acts_not_fetches (save_only keeps
    int8 payloads, re-gathers weights, never quantizes a gather)."""
    mesh = dp_mesh()
    exact = run_steps(MemoryPlan(n_chunks=4, n_blocks=2), mesh)
    comp = run_steps(
        MemoryPlan(n_chunks=4, n_blocks=2, grad_compress="int8_ef",
                   sync_mode="manual", zero_stage=3,
                   act_policies=("compress8", "compress8")), mesh)
    np.testing.assert_allclose(comp, exact, rtol=2e-2)


@needs_multi_device
def test_compress_shrinks_measured_temp_memory_vs_keep():
    """The compression is real in the compiled program: on the 8-layer toy
    XLA's buffer assignment holds strictly less temp memory for uniform
    compress8 than for keep-all (int8 payloads live FWD->BWD instead of the
    full-width activations)."""
    mesh = dp_mesh()
    shape = ShapeConfig("deep", 32, 16, "train")
    from repro.core import TPU_V5E, build_workload
    from repro.core.hardware import MeshSpec

    w = build_workload(DEEP, shape, MeshSpec((N_DEV, 1), ("data", "model")),
                       TPU_V5E)
    keep = MemoryPlan(w.n_chunks, w.n_blocks, n_persist=w.n_chunks)
    comp = dataclasses.replace(
        keep, act_policies=("compress8",) * w.n_blocks)

    def temp_bytes(plan):
        art = build_train_step(DEEP, plan, mesh, shape)
        return art.lower().compile().memory_analysis().temp_size_in_bytes

    t_keep, t_comp = temp_bytes(keep), temp_bytes(comp)
    assert t_comp < t_keep, (
        f"compress8 temp {t_comp / 1e6:.1f}MB not below "
        f"keep-all {t_keep / 1e6:.1f}MB")


# ---------------------------------------------------------------------------
# cost model + search
# ---------------------------------------------------------------------------
def _deep_workload():
    from repro.core import TPU_V5E, build_workload
    from repro.core.hardware import MeshSpec

    return build_workload(DEEP, ShapeConfig("fid", 32, 16, "train"),
                          MeshSpec((4,), ("data",)), TPU_V5E)


def test_cost_model_orders_policies():
    """Per block the model prices: memory keep > compress8 > remat (saved
    bytes) and time remat > compress8 > keep (recompute + passes) — the
    ordering the greedy ladder exploits."""
    w = _deep_workload()
    nc, nb = w.n_chunks, w.n_blocks
    mk = lambda pol: MemoryPlan(  # noqa: E731
        nc, nb, n_persist=nc, act_policies=(pol,) * nb)
    mem = {p: CM.estimate_memory(w, mk(p)).peak
           for p in ("none", "compress8", "checkpoint")}
    rt = {p: CM.estimate_runtime(w, mk(p)).t_iteration
          for p in ("none", "compress8", "checkpoint")}
    assert mem["checkpoint"] < mem["compress8"] < mem["none"]
    assert rt["none"] < rt["compress8"] < rt["checkpoint"]
    # compress16 keeps twice the bytes of compress8 for the same recompute
    m16 = CM.estimate_memory(w, mk("compress16")).peak
    assert mem["compress8"] < m16 < mem["none"]


def test_act_policy_search_deterministic_and_beats_uniform_remat():
    """At a budget bracketed strictly between the remat-all and keep-all
    peaks, the searched vector fits and models a strictly lower step time
    than uniform remat-all (the best feasible uniform policy); two searches
    return the identical plan."""
    from repro.core.autotuner import search_act_policies

    w = _deep_workload()
    nc, nb = w.n_chunks, w.n_blocks
    keep = MemoryPlan(nc, nb, n_persist=nc)
    remat = dataclasses.replace(keep, n_checkpoint=nb)
    budget = 0.5 * (CM.estimate_memory(w, keep).peak
                    + CM.estimate_memory(w, remat).peak)
    assert CM.estimate_memory(w, keep).peak > budget  # keep-all infeasible

    r1 = search_act_policies(w, keep, capacity_bytes=budget)
    r2 = search_act_policies(w, keep, capacity_bytes=budget)
    assert r1.plan == r2.plan
    assert r1.feasible
    assert CM.estimate_memory(w, r1.plan).peak < budget
    t_remat = CM.estimate_runtime(w, remat).t_iteration
    assert r1.runtime.t_iteration < t_remat


def test_megatrain_plan_fits_single_pod_capacity():
    """MegaTrain satellite: the all-host optimizer tier plans a 100B+ model
    under HardwareSpec.capacity_bytes() on the single production pod —
    every chunk on the host tier, nothing persistent, activations degraded
    until the footprint fits."""
    from repro.configs import get_config, get_shape
    from repro.core import TPU_V5E, SINGLE_POD, build_workload
    from repro.core.autotuner import megatrain_plan

    cfg = get_config("llama3-405b")
    assert cfg.param_count() >= 100e9
    w = build_workload(cfg, get_shape("train_4k"), SINGLE_POD, TPU_V5E)
    plan = megatrain_plan(w)
    assert plan.host_optimizer and not plan.host_params
    assert plan.n_host == w.n_chunks and plan.n_persist == 0
    assert CM.estimate_memory(w, plan).peak < TPU_V5E.capacity_bytes()


# ---------------------------------------------------------------------------
# calibration forward-compat
# ---------------------------------------------------------------------------
def test_calibration_without_act_compress_defaults(tmp_path):
    """A calibration JSON predating the act_compress factor loads without
    KeyError; the factor resolves to the analytic default until refit."""
    path = tmp_path / "old.json"
    path.write_text(json.dumps({"version": 2, "backends": {
        jax.default_backend(): {"wire_factors": {
            "xla": {"none": 1.0, "bf16": 1.0, "int8_ef": 1.0},
            "manual": {"none": 1.0, "bf16": 1.0, "int8_ef": 0.5},
        }}}}))
    try:
        assert CM.load_wire_calibration(str(path)) is not None
        assert CM.wire_factor("manual", "act_compress") == \
            CM.DEFAULT_WIRE_FACTORS["manual"]["act_compress"]
        assert CM.wire_factor("xla", "act_compress") == \
            CM.DEFAULT_WIRE_FACTORS["xla"]["act_compress"]
        assert CM.wire_factor("manual", "int8_ef") == 0.5
    finally:
        CM.reset_wire_calibration()
