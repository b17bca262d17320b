"""Substrate tests: optimizer, data pipeline, checkpointing, train loop,
step builder integration (plan variants on a tiny model)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.configs.base import ShapeConfig
from repro.core.plan import MemoryPlan
from repro.data.pipeline import SyntheticTokenPipeline
from repro.ckpt.checkpoint import CheckpointManager
from repro.optim.adam import AdamConfig, adam_update, cosine_schedule, init_opt_state
from repro.train.loop import LoopConfig, train_loop
from repro.train.step_builder import build_train_step, plan_runs

KEY = jax.random.PRNGKey(0)
TINY = reduced(ARCHS["llama3-405b"])
SHAPE = ShapeConfig("tiny", 64, 4, "train")


def local_mesh():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def test_adam_decreases_quadratic():
    params = {"w": jnp.array([5.0, -3.0], jnp.float32)}
    opt = init_opt_state(params)
    cfg = AdamConfig(lr=0.1, grad_clip=100.0)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, opt, _ = adam_update(params, grads, opt, cfg, cfg.lr)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_adam_master_weights_preserve_precision():
    """bf16 params + tiny updates: master fp32 must accumulate what bf16 cannot."""
    params = {"w": jnp.ones((8,), jnp.bfloat16)}
    opt = init_opt_state(params)
    cfg = AdamConfig(lr=1e-5, grad_clip=1e9)
    g = {"w": jnp.full((8,), 1e-3, jnp.bfloat16)}
    for _ in range(10):
        params, opt, _ = adam_update(params, g, opt, cfg, cfg.lr)
    drift = np.asarray(opt["master"]["w"]) - 1.0
    assert np.all(drift != 0.0)  # fp32 master moved even when bf16 rounds away


def test_cosine_schedule_shape():
    lr = cosine_schedule(1e-3, warmup=10, total=100)
    assert float(lr(0)) == 0.0
    assert abs(float(lr(10)) - 1e-3) < 1e-9
    assert float(lr(100)) < 1e-4


def test_grad_clip():
    from repro.optim.adam import global_norm_clip

    g = {"a": jnp.full((10,), 100.0)}
    clip, norm = global_norm_clip(g, 1.0)
    assert float(norm) > 100
    total = jnp.sqrt(jnp.sum(jnp.square(clip(g["a"]))))
    assert abs(float(total) - 1.0) < 1e-3


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_pipeline_deterministic_and_resumable():
    p1 = SyntheticTokenPipeline(TINY, SHAPE, seed=7)
    b1 = [p1.next_sync() for _ in range(3)]
    # resume from state after 1 batch
    p2 = SyntheticTokenPipeline(TINY, SHAPE, seed=7)
    p2.next_sync()
    state = p2.state()
    p3 = SyntheticTokenPipeline.from_state(TINY, SHAPE, state)
    b3 = p3.next_sync()
    np.testing.assert_array_equal(np.asarray(b1[1]["tokens"]), np.asarray(b3["tokens"]))


def test_pipeline_prefetch_thread():
    p = SyntheticTokenPipeline(TINY, SHAPE, seed=1, prefetch=2)
    it = iter(p)
    a = next(it)
    b = next(it)
    assert a["tokens"].shape == (SHAPE.global_batch, SHAPE.seq_len)
    assert not np.array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))
    p.stop()


def test_pipeline_labels_are_shifted_tokens():
    p = SyntheticTokenPipeline(TINY, SHAPE, seed=3)
    b = p.next_sync()
    np.testing.assert_array_equal(
        np.asarray(b["tokens"])[:, 1:], np.asarray(b["labels"])[:, :-1]
    )


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    state = {"a": jnp.arange(10, dtype=jnp.float32), "nested": {"b": jnp.ones((3, 3))}}
    mgr.save(5, state, extra={"data_step": 5}, sync=True)
    specs = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    restored, extra = mgr.restore(5, specs)
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(state["a"]))
    assert extra["data_step"] == 5


def test_checkpoint_atomicity_no_partial_reads(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    # a stale tmp dir (crashed save) must be invisible
    os.makedirs(tmp_path / "step_9.tmp")
    assert mgr.latest_step() is None
    mgr.save(1, {"x": jnp.zeros(4)}, sync=True)
    assert mgr.latest_step() == 1


def test_checkpoint_gc_keeps_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"x": jnp.full(2, s)}, sync=True)
    assert mgr.steps() == [3, 4]


def test_checkpoint_elastic_restore_different_sharding(tmp_path):
    """Save unsharded, restore onto an explicit 1x1 mesh sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mgr = CheckpointManager(str(tmp_path))
    state = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    mgr.save(1, state, sync=True)
    mesh = local_mesh()
    spec = {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32,
                                      sharding=NamedSharding(mesh, P("data", None)))}
    restored, _ = mgr.restore(1, spec)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(state["w"]))


# ---------------------------------------------------------------------------
# plan -> runs layout
# ---------------------------------------------------------------------------
def test_plan_runs_cover_all_repeats():
    plan = MemoryPlan(n_chunks=12, n_blocks=10, n_persist=3, n_buffer=2,
                      n_host=4, n_swap=2, n_checkpoint=5)
    runs = plan_runs(plan, 10)
    assert sum(r.length for r in runs) == 10
    # persist chunks are at the front (chunks 1,2 -> repeats 0,1)
    assert runs[0].placement == "persist"
    # host chunks at the back
    assert runs[-1].placement == "host"
    # swap blocks first
    assert runs[0].act_policy == "swap"


def test_runs_merge_adjacent_same_policy():
    plan = MemoryPlan(n_chunks=10, n_blocks=8, n_persist=0)
    runs = plan_runs(plan, 8)
    assert len(runs) == 1 and runs[0].length == 8


# ---------------------------------------------------------------------------
# end-to-end loop with fault tolerance
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_artifacts():
    mesh = local_mesh()
    plan = MemoryPlan(n_chunks=4, n_blocks=2, n_persist=4)
    return build_train_step(TINY, plan, mesh, SHAPE, adam=AdamConfig(lr=3e-3))


def test_train_loop_runs_and_learns(tiny_artifacts, tmp_path):
    pipe = SyntheticTokenPipeline(TINY, SHAPE, seed=0)
    mgr = CheckpointManager(str(tmp_path))
    res = train_loop(tiny_artifacts, pipe, mgr,
                     LoopConfig(total_steps=30, checkpoint_every=10, log_every=100))
    assert res.steps_run == 30
    assert np.mean(res.losses[-5:]) < np.mean(res.losses[:5])
    assert mgr.latest_step() == 30


def test_train_loop_resumes_from_checkpoint(tiny_artifacts, tmp_path):
    pipe = SyntheticTokenPipeline(TINY, SHAPE, seed=0)
    mgr = CheckpointManager(str(tmp_path))
    train_loop(tiny_artifacts, pipe, mgr,
               LoopConfig(total_steps=10, checkpoint_every=5, log_every=100))
    # second run picks up at step 10 and continues to 15
    pipe2 = SyntheticTokenPipeline(TINY, SHAPE, seed=0)
    res2 = train_loop(tiny_artifacts, pipe2, mgr,
                      LoopConfig(total_steps=15, checkpoint_every=5, log_every=100))
    assert res2.resumed_from == 10
    assert res2.steps_run == 5
    assert pipe2.step >= 15  # data state restored, not restarted


# ---------------------------------------------------------------------------
# host-offloaded Adam: layer-streamed vs whole-leaf updates
# ---------------------------------------------------------------------------
def _host_update_setup(n_layers: int):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compat import host_memory_kind

    mesh = local_mesh()
    dev = NamedSharding(mesh, P())
    host = NamedSharding(mesh, P(), memory_kind=host_memory_kind(mesh))
    # a rank-3 stacked leaf streams; a rank-2 stacked one and an unstacked
    # one take the whole-leaf path
    shapes = {"stack3": (n_layers, 16, 24), "stack2": (n_layers, 16), "flat": (16, 24)}
    keys = jax.random.split(KEY, 3 * len(shapes))
    params = {n: jax.random.normal(keys[i], s, jnp.bfloat16)
              for i, (n, s) in enumerate(shapes.items())}
    grads = [{n: jax.random.normal(keys[len(shapes) * (j + 1) + i], s, jnp.bfloat16)
              for i, (n, s) in enumerate(shapes.items())} for j in range(2)]
    names = jax.tree.leaves({n: n for n in shapes})  # flatten order
    return params, grads, names, dev, host


@pytest.mark.parametrize("n_layers", [2, 5], ids=["two_layers", "odd_layers"])
def test_streamed_host_update_bitwise_equals_whole_leaf(n_layers):
    from repro.optim.adam import HostLeaf, streams

    params, grads, names, dev, host = _host_update_setup(n_layers)
    cfg = AdamConfig(lr=1e-2, grad_clip=0.5, weight_decay=0.1)

    def plan(stream: bool):
        return [HostLeaf(dev, host, dev, stacked=stream and n != "flat") for n in names]

    assert [streams(params[n], h) for n, h in zip(names, plan(True))] == [
        n == "stack3" for n in names]
    assert not any(streams(params[n], h) for n, h in zip(names, plan(False)))

    def two_steps(host_plan):
        step = jax.jit(lambda p, g, o: adam_update(p, g, o, cfg, cfg.lr, host_plan=host_plan))
        p, o = params, init_opt_state(params)
        for g in grads:
            p, o, _ = step(p, g, o)
        return p, o

    streamed, whole, device = two_steps(plan(True)), two_steps(plan(False)), two_steps(None)
    for ref in (whole, device):
        for a, b in zip(jax.tree.leaves(streamed), jax.tree.leaves(ref)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _three_layer_setup(arch: str = "llama3-405b"):
    from repro.core import build_workload
    from repro.core.hardware import LOCAL_CPU_HW, MeshSpec

    cfg = reduced(ARCHS[arch], num_layers=3, d_model=64, d_ff=128,
                  vocab_size=256, num_heads=2, num_kv_heads=2, head_dim=32)
    shape = ShapeConfig("host_stream", 32, 2, "train")
    w = build_workload(cfg, shape, MeshSpec((1, 1), ("data", "model")), LOCAL_CPU_HW)
    return cfg, shape, w


@pytest.mark.parametrize("host_params,arch", [
    pytest.param(True, "llama3-405b", id="params_on_host"),
    pytest.param(False, "llama3-405b", id="params_in_hbm"),
    # partial rotary, LayerNorm with eps 1e-5, SwiGLU, untied head
    pytest.param(True, "stablelm-3b", id="partial_rotary_params_on_host"),
])
def test_all_host_step_equals_all_device_step(host_params, arch):
    """One train step with every chunk's optimizer state on the host (the
    stacked block leaves streamed layer by layer) gives the same bits as
    the same step with nothing on the host."""
    from repro import obs

    cfg, shape, w = _three_layer_setup(arch)
    mesh = local_mesh()
    tel = obs.Telemetry(trace=False)
    with obs.use_telemetry(tel):
        host_art = build_train_step(cfg, MemoryPlan(w.n_chunks, w.n_blocks, n_host=w.n_chunks,
                                                    host_params=host_params), mesh, shape)
    assert tel.registry.snapshot()["offload.streamed_bytes_per_step{dir=fetch}"]["value"] > 0
    dev_art = build_train_step(cfg, MemoryPlan(w.n_chunks, w.n_blocks, n_host=0), mesh, shape)
    state = dev_art.init(KEY)
    batch = SyntheticTokenPipeline(cfg, shape, seed=5).next_sync()
    dev_out, dev_metrics = dev_art.jit(donate=False)(state, batch)
    host_state = jax.tree.map(jax.device_put, state, host_art.state_shardings)
    host_out, host_metrics = host_art.jit(donate=False)(host_state, batch)
    assert float(host_metrics["loss"]) == float(dev_metrics["loss"])
    for a, b in zip(jax.tree.leaves(host_out), jax.tree.leaves(dev_out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_host_runs_keep_two_dim_layer_leaves_in_hbm():
    """Under a plan whose parameters live on the host, a run's stacked
    ``[L, d]`` leaves (the LayerNorm scales and biases) stay in HBM, since
    one layer of them cannot cross the host link alone; the rank-3 leaves
    and every optimizer state stay on the host, and only the rank-3 leaves
    stream their update."""
    from repro.dist.sharding import host_layer_sliceable
    from repro.optim.adam import HostLeaf, streams

    cfg, shape, w = _three_layer_setup("stablelm-3b")
    art = build_train_step(cfg, MemoryPlan(w.n_chunks, w.n_blocks, n_host=w.n_chunks),
                           local_mesh(), shape)

    def on_host(s):
        return s.sharding.memory_kind not in (None, "device")

    run = art.state_specs["params"]["runs"][0]["pos0"]
    for path, s in jax.tree_util.tree_flatten_with_path(run)[0]:
        assert on_host(s) == (s.ndim >= 3) == host_layer_sliceable(s), (path, s)
    assert not on_host(run["norm1"]["scale"]) and on_host(run["attn"]["wq"])
    # outside the stack, whole leaves: the final norm and the head stay on the host
    assert on_host(art.state_specs["params"]["final_norm"]["scale"])
    assert on_host(art.state_specs["params"]["head"]["w"])
    assert all(map(on_host, jax.tree.leaves(art.state_specs["opt"]["master"])))
    for name, leaf, want in (("norm1", run["norm1"]["scale"], False),
                             ("attn", run["attn"]["wq"], True)):
        sh = leaf.sharding
        assert streams(leaf, HostLeaf(sh, sh, sh, stacked=True)) == want, name


def test_host_memory_is_what_the_host_has_free(tmp_path):
    from repro.core.hardware import host_memory_bytes

    meminfo = tmp_path / "meminfo"
    meminfo.write_text("MemTotal:       47185920 kB\nMemFree:        30000000 kB\n"
                       "MemAvailable:   32505856 kB\n")
    assert host_memory_bytes(str(meminfo)) == 32505856 * 1024
    meminfo.write_text("MemTotal:       47185920 kB\n")
    with pytest.raises(RuntimeError, match="MemAvailable"):
        host_memory_bytes(str(meminfo))


def test_search_offers_no_gradient_compression_on_one_device():
    """One device has no wire: ``compress="auto"`` never picks int8+EF
    there (it would only round the gradients), while ``"on"`` still can."""
    from repro.configs.paper_models import GPT2_1B
    from repro.core import build_workload, search
    from repro.core.hardware import TPU_V5E, MeshSpec

    w = build_workload(GPT2_1B, ShapeConfig("paper", 1024, 8, "train"),
                       MeshSpec((1, 1), ("data", "model")), TPU_V5E)
    for cap in (None, 10.7e9):
        assert search(w, capacity_bytes=cap, sp="auto").plan.grad_compress == "none"
    assert search(w, compress="on").plan.grad_compress == "int8_ef"
