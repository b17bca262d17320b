"""Direct coverage of the repro.dist substrate: ParamDef->spec mapping,
placement memory kinds, dp_only collapse, batch/gather/activation shardings,
collective portability across 1- and N-device CPU meshes, and the int8+EF
compressed-gradient training path end to end.

Runs under any local device count; CI forces 4 CPU devices via
XLA_FLAGS=--xla_force_host_platform_device_count=4 so the multi-device
branches are exercised there."""
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.compat import host_memory_kind
from repro.dist import collectives as COLL
from repro.dist import sharding as SH
from repro.models.layers import LAYER, NONE, TP, ZERO, ParamDef

N_DEV = len(jax.devices())


def mesh2d():
    """(data, model) mesh over all local devices, data-major."""
    model = 2 if N_DEV % 2 == 0 and N_DEV >= 2 else 1
    return jax.make_mesh((N_DEV // model, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _expect(mesh, dim, axes):
    """Axis entry the sharder should emit: kept iff the extent divides dim."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = math.prod(sizes[a] for a in axes)
    if n != 1 and (dim % n or dim < n):
        return None
    return axes[0] if len(axes) == 1 else axes


# ---------------------------------------------------------------------------
# sharding_for: axis-tag mapping per placement
# ---------------------------------------------------------------------------
def test_spec_zero_tp_by_placement():
    mesh = mesh2d()
    d = ParamDef((16, 32), (ZERO, TP))
    assert SH.sharding_for(d, mesh, placement="hbm").spec == P("data", "model")
    assert SH.sharding_for(d, mesh, placement="persist").spec == P(None, "model")
    # host keeps the hbm partitioning, only the memory kind changes
    assert SH.sharding_for(d, mesh, placement="host").spec == P("data", "model")


def test_spec_dp_only_collapses_tp():
    mesh = mesh2d()
    d = ParamDef((16, 32), (ZERO, TP))
    assert SH.sharding_for(d, mesh, placement="hbm", dp_only=True).spec == P("data", None)
    assert SH.sharding_for(d, mesh, placement="persist", dp_only=True).spec == P(None, None)
    # batch takes every axis in dp_only mode
    assert SH.batch_axes(mesh, True) == tuple(mesh.axis_names)
    assert SH.batch_axes(mesh, False) == ("data",)


def test_spec_untagged_and_layer_dims_never_shard():
    mesh = mesh2d()
    d = ParamDef((3, 16, 32), (LAYER, ZERO, TP))
    assert SH.sharding_for(d, mesh, placement="hbm").spec == P(None, "data", "model")
    norm = ParamDef((16,), (NONE,))
    assert SH.sharding_for(norm, mesh, placement="hbm").spec == P(None)


def test_spec_indivisible_dim_stays_replicated():
    mesh = mesh2d()
    d = ParamDef((7, 9), (ZERO, TP))
    expect = P(_expect(mesh, 7, ("data",)), _expect(mesh, 9, ("model",)))
    assert SH.sharding_for(d, mesh, placement="hbm").spec == expect


def test_host_placement_memory_kind_and_roundtrip():
    mesh = mesh2d()
    d = ParamDef((8, 8), (ZERO, TP), dtype="float32")
    s = SH.sharding_for(d, mesh, placement="host")
    kind = host_memory_kind(mesh)
    assert s.memory_kind == kind  # pinned_host on TPU, unpinned_host on CPU
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    hosted = jax.device_put(x, s)
    assert hosted.sharding.memory_kind == kind
    # gather_sharding brings it back to device memory, ZeRO axes dropped
    g = SH.gather_sharding(d, mesh)
    assert g.spec == P(None, "model")
    back = jax.device_put(hosted, g)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
    # a platform with no host memory space refuses host placement outright
    # rather than quietly keeping the state in device memory
    dev_only = types.SimpleNamespace(
        addressable_memories=lambda: [types.SimpleNamespace(kind="device")])
    with pytest.raises(RuntimeError, match="no host memory space"):
        host_memory_kind(types.SimpleNamespace(devices=np.array([dev_only])))


# ---------------------------------------------------------------------------
# tree variants
# ---------------------------------------------------------------------------
def test_tree_specs_carry_shapes_dtypes_shardings():
    mesh = mesh2d()
    defs = {"a": ParamDef((8, 16), (ZERO, TP)),
            "n": ParamDef((16,), (NONE,), dtype="float32")}
    sh = SH.tree_shardings(defs, mesh, placement="hbm")
    specs = SH.tree_specs(defs, sh)
    assert specs["a"].shape == (8, 16) and specs["a"].dtype == jnp.bfloat16
    assert specs["n"].dtype == jnp.float32
    assert specs["a"].sharding is sh["a"]


def test_tree_gather_shardings_strip_layer_axis():
    mesh = mesh2d()
    stacked = {"w": ParamDef((3, 8, 16), (LAYER, ZERO, TP))}
    g = SH.tree_gather_shardings(stacked, mesh)
    assert g["w"].spec == P(None, "model")  # per-repeat rank, ZeRO gathered
    assert SH.tree_gather_shardings(stacked, mesh, persistent=True) is None


def test_batch_sharding_rank_handling():
    mesh = mesh2d()
    assert SH.batch_sharding(mesh, 2).spec == P("data", None)
    assert SH.batch_sharding(mesh, 3).spec == P("data", None, None)
    assert SH.batch_sharding(mesh, 2, dp_only=True).spec == P(
        ("data", "model") if "model" in mesh.axis_names else "data", None
    )


def test_activation_sharder_is_identity_math():
    from repro.core.plan import MemoryPlan

    mesh = mesh2d()
    plan = MemoryPlan(n_chunks=4, n_blocks=2, seq_shard_acts=True)
    sharder = SH.make_activation_sharder(mesh, plan)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 32))
    for kind in ("bsd", "enter", "logits"):
        np.testing.assert_array_equal(np.asarray(sharder(x, kind)), np.asarray(x))


# ---------------------------------------------------------------------------
# collectives: portable across 1-device and forced-multi-device meshes
# ---------------------------------------------------------------------------
def full_mesh():
    return jax.make_mesh((N_DEV,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def test_bf16_all_reduce_any_device_count():
    x = jnp.linspace(-3, 3, 256, dtype=jnp.float32)
    out = COLL.bf16_all_reduce(x, full_mesh())
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(x.astype(jnp.bfloat16), np.float32), atol=2e-2
    )


def test_compressed_all_reduce_any_device_count():
    x = jax.random.normal(jax.random.PRNGKey(3), (513,), jnp.float32)
    err0 = jnp.zeros_like(x)
    avg, err1 = COLL.compressed_all_reduce(x, err0, full_mesh())
    np.testing.assert_allclose(np.asarray(avg + err1), np.asarray(x), atol=1e-5)
    # residual bounded by half a quantization step
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    assert float(jnp.abs(err1).max()) <= scale / 2 + 1e-6


def test_compressed_tree_all_reduce_roundtrip():
    tree = {"a": jnp.ones((4, 4)), "b": {"c": jnp.full((8,), -2.0)}}
    errs = COLL.init_error_feedback(tree)
    avg, new_err = COLL.compressed_tree_all_reduce(tree, errs)
    assert jax.tree.structure(avg) == jax.tree.structure(tree)
    total = jax.tree.map(lambda a, e: a + e, avg, new_err)
    for got, want in zip(jax.tree.leaves(total), jax.tree.leaves(tree)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---------------------------------------------------------------------------
# int8+EF gradient compression through the real train step
# ---------------------------------------------------------------------------
def test_train_step_with_int8_ef_compression():
    from repro.configs import ARCHS, reduced
    from repro.configs.base import ShapeConfig
    from repro.core.plan import MemoryPlan
    from repro.data.pipeline import SyntheticTokenPipeline
    from repro.optim.adam import AdamConfig
    from repro.train.step_builder import build_train_step

    tiny = reduced(ARCHS["llama3-405b"])
    shape = ShapeConfig("tiny", 32, 4, "train")
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = MemoryPlan(n_chunks=4, n_blocks=2, n_persist=4, grad_compress="int8_ef")
    art = build_train_step(tiny, plan, mesh, shape, adam=AdamConfig(lr=3e-3))
    assert "ef" in art.state_specs  # error-feedback residuals live in the state
    state = art.init(jax.random.PRNGKey(0))
    jfn = jax.jit(art.fn, donate_argnums=(0,))
    pipe = SyntheticTokenPipeline(tiny, shape, seed=0)
    losses = []
    for _ in range(30):
        state, metrics = jfn(state, pipe.next_sync())
        losses.append(float(metrics["loss"]))
        assert np.isfinite(losses[-1])
    assert "ef_norm" in metrics and float(metrics["ef_norm"]) > 0
    assert np.mean(losses[-5:]) < np.mean(losses[:5])  # still learns under EF


def test_autotuner_exposes_compression_knob():
    from repro.configs import TRAIN_4K, get_config
    from repro.core import SINGLE_POD, TPU_V5E, build_workload, search
    from repro.core.cost_model import estimate_runtime
    from repro.core.plan import MemoryPlan

    w = build_workload(get_config("stablelm-3b"), TRAIN_4K, SINGLE_POD, TPU_V5E)
    res = search(w, compress="on")
    assert res.feasible and res.plan.grad_compress == "int8_ef"
    # halved reduce wire bytes can never slow the modeled iteration down
    base = MemoryPlan(w.n_chunks, w.n_blocks, n_checkpoint=w.n_blocks)
    comp = MemoryPlan(w.n_chunks, w.n_blocks, n_checkpoint=w.n_blocks,
                      grad_compress="int8_ef")
    assert (estimate_runtime(w, comp).t_iteration
            <= estimate_runtime(w, base).t_iteration + 1e-9)
