"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret mode runs kernel bodies op by op and accepts blocks the TPU
compiler refuses (unaligned tiles, scalar stores to VMEM, mixed-dtype
stores). These tests compile each kernel for a *described* v5e chip — the
TPU compiler is installed, no chip is needed — and check the kernel is in
the program (``tpu_custom_call``). Widths are gpt2-1b's (d_model 2048,
d_ff 8192, 16 heads of 128, seq 1024, batch 8).

The topology is described inside a module fixture only: loading libtpu at
import time would take its lock in every test worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_adam import fused_adam
from repro.kernels.fused_quant import fused_quantize_ef
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rmsnorm import rmsnorm


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache, topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", [(8192, 2048), (4, 512, 2048), (4, 12565, 2048)],
                         ids=["activation_rows", "wire_chunk", "wire_chunk_ragged"])
def test_fused_quantize_ef_compiles(one_chip, shape):
    _assert_kernel_compiles(fused_quantize_ef,
                            _sds(shape, jnp.float32, one_chip),
                            _sds((), jnp.int32, one_chip))


def test_fused_adam_compiles(one_chip):
    p = _sds((2048, 8192), jnp.bfloat16, one_chip)
    f32 = _sds((2048, 8192), jnp.float32, one_chip)
    _assert_kernel_compiles(fused_adam, p, p, f32, f32, f32,
                            _sds((8,), jnp.float32, one_chip))


@pytest.mark.parametrize("scale_dtype", [jnp.bfloat16, jnp.float32])
def test_rmsnorm_bf16_compiles(one_chip, scale_dtype):
    _assert_kernel_compiles(rmsnorm, _sds((8, 1024, 2048), jnp.bfloat16, one_chip),
                            _sds((2048,), scale_dtype, one_chip))


def test_flash_attention_compiles(one_chip):
    qkv = _sds((8, 16, 1024, 128), jnp.bfloat16, one_chip)
    _assert_kernel_compiles(flash_attention, qkv, qkv, qkv)


def test_paged_attention_decode_compiles(one_chip):
    b, h, hd, page, n_hot, s = 8, 16, 128, 128, 2, 2048
    hot = _sds((b, page * n_hot, h, hd), jnp.bfloat16, one_chip)
    cold = _sds((b, s, h, hd), jnp.bfloat16, one_chip)
    _assert_kernel_compiles(
        lambda *a: paged_attention(*a, n_hot=n_hot),
        _sds((b, 1, h, hd), jnp.bfloat16, one_chip), hot, hot, cold, cold,
        _sds((b, s), jnp.bool_, one_chip), _sds((b, s), jnp.float32, one_chip))


def test_streamed_host_adam_compiles_in_layer_slices(one_chip):
    """The host-offloaded Adam update of one gpt2-1b MLP leaf (18 layers of
    2048 x 8192, fp32 master/m/v in pinned_host) streams layer by layer:
    its device temporaries stay under four layer slices per state, where
    the whole-leaf form holds whole leaves."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.optim.adam import AdamConfig, HostLeaf, adam_update

    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1), ("data", "model"))
    dev = NamedSharding(mesh, P())
    host = dev.with_memory_kind("pinned_host")
    shape = (18, 2048, 8192)
    layer_bytes = 2048 * 8192 * 4

    def temp_bytes(stacked: bool) -> int:
        plan = [HostLeaf(dev, host, dev, stacked=stacked)]
        state = {k: {"w": _sds(shape, jnp.float32, host)} for k in ("master", "m", "v")}
        state["count"] = _sds((), jnp.int32, dev)
        step = jax.jit(lambda p, g, o: adam_update(p, g, o, AdamConfig(), 3e-4, host_plan=plan),
                       donate_argnums=(0, 2))
        compiled = step.lower({"w": _sds(shape, jnp.bfloat16, dev)},
                              {"w": _sds(shape, jnp.bfloat16, dev)}, state).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    assert temp_bytes(stacked=True) < 3 * 4 * layer_bytes
    assert temp_bytes(stacked=False) >= 3 * 18 * layer_bytes


@pytest.mark.parametrize("fault", [False, True], ids=["norms_in_hbm", "norms_on_host"])
def test_host_params_step_compiles_at_stablelm_widths(one_chip, monkeypatch, fault):
    """A train step at StableLM-3B-4E1T's widths (2 layers, seq 256 x batch
    2) whose every chunk, bf16 parameters included, lives in host memory
    compiles: the layer scan fetches each rank-3 weight one layer at a
    time, and the ``[L, d]`` norm leaves stay in HBM. Put on the host
    (``fault``), one layer of them is a slice across the tile's sublanes,
    which the compiler refuses."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh

    from repro.configs.archs import STABLELM_3B
    from repro.configs.base import ShapeConfig
    from repro.core.plan import MemoryPlan
    from repro.dist import sharding as SH
    from repro.train.step_builder import build_train_step

    # the chip's program: StepArtifacts.jit keeps host state pinned off the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if fault:
        monkeypatch.setattr(SH, "host_layer_sliceable", lambda d: True)
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1), ("data", "model"))
    art = build_train_step(dataclasses.replace(STABLELM_3B, num_layers=2),
                           MemoryPlan(4, 2, n_host=4, microbatch=2, host_params=True),
                           mesh, ShapeConfig("t", 256, 2, "train"))
    norm = art.state_specs["params"]["runs"][0]["pos0"]["norm1"]["scale"]
    assert (norm.sharding.memory_kind == "pinned_host") == fault
    lowered = art.lower()
    if fault:
        with pytest.raises(jax.errors.JaxRuntimeError, match="Sublane slicing"):
            lowered.compile()
    else:
        lowered.compile()
