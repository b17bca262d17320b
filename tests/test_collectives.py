"""Gradient compression tests (shard_map collectives on a multi-device mesh
require >1 device; these run the math path on a 1-device mesh and assert the
error-feedback invariant)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.collectives import bf16_all_reduce, compressed_all_reduce, _quantize_int8, _dequantize_int8


def mesh1():
    return jax.make_mesh((1,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))


def test_bf16_all_reduce_identity_on_one_device():
    x = jnp.linspace(-2, 2, 64, dtype=jnp.float32)
    out = bf16_all_reduce(x, mesh1())
    np.testing.assert_allclose(np.asarray(out), np.asarray(x.astype(jnp.bfloat16), np.float32),
                               atol=2e-2)


def test_quantize_roundtrip_bounded_error():
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,), jnp.float32)
    q, scale = _quantize_int8(x)
    back = _dequantize_int8(q, scale)
    err = np.abs(np.asarray(back - x))
    assert err.max() <= float(scale.max()) / 2 + 1e-6  # half-step rounding


def test_compressed_all_reduce_error_feedback():
    """Residual + sent == input (+ prior residual): nothing is lost."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1000,), jnp.float32)
    err0 = jnp.zeros_like(x)
    avg, err1 = compressed_all_reduce(x, err0, mesh1())
    # on 1 device: avg + err == x exactly (modulo float assoc)
    np.testing.assert_allclose(np.asarray(avg + err1), np.asarray(x), atol=1e-4)
    # feeding the error back converges toward the true mean over steps
    avg2, err2 = compressed_all_reduce(x, err1, mesh1())
    assert float(jnp.abs(err2).mean()) <= float(jnp.abs(err1).mean()) + 1e-3


# ---------------------------------------------------------------------------
# manual reduce-scatter primitives (ISSUE-3): shard_map on the real mesh
# ---------------------------------------------------------------------------
import math

import pytest

from repro.dist.collectives import (
    manual_bf16_reduce_scatter,
    manual_int8_ef_reduce_scatter,
    manual_reduce_scatter,
)

N_DEV = len(jax.devices())
needs_multi = pytest.mark.skipif(N_DEV < 2, reason="reduce-scatter needs >1 device")


def data_mesh():
    return jax.make_mesh((N_DEV,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def _run_rs(fn, local_inputs, in_specs, out_specs):
    mesh = data_mesh()
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))(*local_inputs)


@needs_multi
def test_int8_ef_reduce_scatter_each_owner_gets_the_mean_shard():
    from jax.sharding import PartitionSpec as P

    rows = 2 * N_DEV
    g = jax.random.normal(jax.random.PRNGKey(0), (N_DEV, rows, 6), jnp.float32)
    err0 = jnp.zeros((N_DEV, rows // N_DEV, 6), jnp.float32)

    def body(gl, el):
        s, ne = manual_int8_ef_reduce_scatter(gl[0], el[0], ("data",), 0)
        return s[None], ne[None]

    shards, errs = _run_rs(
        body, (g, err0),
        (P("data", None, None), P("data", None, None)),
        (P("data", None, None), P("data", None, None)))
    got = np.asarray(shards).reshape(rows, 6)
    want = np.asarray(g).mean(0)
    step = np.abs(np.asarray(g)).max() / 127.0
    assert np.abs(got - want).max() <= step  # within one quantization step
    # per-device EF is nonzero (quantization dropped something) and bounded
    e = np.asarray(errs)
    assert e.shape == (N_DEV, rows // N_DEV, 6)  # shard-sized residuals
    assert np.abs(e).max() <= step / 2 + 1e-6
    assert np.abs(e).max() > 0


@needs_multi
def test_int8_ef_reduce_scatter_pads_uneven_divisors():
    """Leaves whose dim does not divide the sync extent are padded to the
    next multiple; owners hold the padded shard, reconstruction drops the
    tail (the train-state layout never shards such dims — this keeps the
    primitive composable on its own)."""
    from jax.sharding import PartitionSpec as P

    rows = 2 * N_DEV + 1  # uneven
    pad_rows = math.ceil(rows / N_DEV) * N_DEV
    shard_rows = pad_rows // N_DEV
    g = jax.random.normal(jax.random.PRNGKey(1), (N_DEV, rows, 3), jnp.float32)
    err0 = jnp.zeros((N_DEV, shard_rows, 3), jnp.float32)

    def body(gl, el):
        s, ne = manual_int8_ef_reduce_scatter(gl[0], el[0], ("data",), 0)
        return s[None], ne[None]

    shards, errs = _run_rs(
        body, (g, err0),
        (P("data", None, None), P("data", None, None)),
        (P("data", None, None), P("data", None, None)))
    got = np.asarray(shards).reshape(pad_rows, 3)[:rows]
    want = np.asarray(g).mean(0)
    step = np.abs(np.asarray(g)).max() / 127.0
    assert np.abs(got - want).max() <= step
    assert np.asarray(errs).shape == (N_DEV, shard_rows, 3)


@needs_multi
@pytest.mark.parametrize("rs,tol", [(manual_reduce_scatter, 1e-6),
                                    (manual_bf16_reduce_scatter, 2e-2)])
def test_uncompressed_reduce_scatter_variants(rs, tol):
    from jax.sharding import PartitionSpec as P

    rows = 2 * N_DEV
    g = jax.random.normal(jax.random.PRNGKey(2), (N_DEV, rows, 4), jnp.float32)

    def body(gl):
        return rs(gl[0], ("data",), 0)[None]

    shards = _run_rs(body, (g,), P("data", None, None), P("data", None, None))
    got = np.asarray(shards).reshape(rows, 4)
    np.testing.assert_allclose(got, np.asarray(g).mean(0), atol=tol, rtol=tol)


@needs_multi
def test_int8_reduce_scatter_ef_feedback_reduces_own_shard_error():
    """Feeding the shard residual back biases the next transmission so the
    own-shard contribution converges (EF invariant at shard granularity)."""
    from jax.sharding import PartitionSpec as P

    rows = 2 * N_DEV
    g = jax.random.normal(jax.random.PRNGKey(3), (N_DEV, rows, 5), jnp.float32)
    err = jnp.zeros((N_DEV, rows // N_DEV, 5), jnp.float32)

    def body(gl, el):
        s, ne = manual_int8_ef_reduce_scatter(gl[0], el[0], ("data",), 0)
        return s[None], ne[None]

    mesh = data_mesh()
    f = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data", None, None), P("data", None, None)),
        out_specs=(P("data", None, None), P("data", None, None)), check_vma=False))
    _, err1 = f(g, err)
    _, err2 = f(g, err1)
    # the EF invariant: transmitted + residual == input + prior residual for
    # the own chunk, so the residual stays bounded rather than accumulating
    assert float(jnp.abs(err2).max()) <= 2 * float(jnp.abs(err1).max()) + 1e-6
