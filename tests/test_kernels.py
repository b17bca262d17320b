"""Per-kernel validation: shape/dtype sweeps in interpret mode against the
pure-jnp oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as R
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_adam import fused_adam
from repro.kernels.rmsnorm import rmsnorm

KEY = jax.random.PRNGKey(0)


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention: sweep shapes, GQA ratios, dtypes, masks
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,hq,hkv,sq,sk,hd", [
    (1, 4, 4, 128, 128, 64),     # MHA square
    (2, 8, 2, 128, 128, 64),     # GQA 4:1
    (1, 8, 1, 64, 256, 32),      # MQA, cross lengths
    (2, 4, 4, 100, 100, 64),     # non-block-multiple (padding path)
    (1, 16, 8, 256, 256, 128),   # MXU-aligned head dim
])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 48)])
def test_flash_attention_matches_ref(b, hq, hkv, sq, sk, hd, causal, window):
    if not causal and sq != sk:
        pytest.skip("cross-attn non-causal covered by square case")
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (b, hq, sq, hd), jnp.float32)
    k = rand(ks[1], (b, hkv, sk, hd), jnp.float32)
    v = rand(ks[2], (b, hkv, sk, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    ref = R.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.bfloat16, 2e-2), (jnp.float32, 2e-5)])
def test_flash_attention_dtypes(dtype, atol):
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (2, 4, 128, 64), dtype)
    k = rand(ks[1], (2, 4, 128, 64), dtype)
    v = rand(ks[2], (2, 4, 128, 64), dtype)
    out = flash_attention(q, k, v, interpret=True)
    ref = R.flash_attention_ref(q, k, v)
    assert out.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=atol, rtol=atol
    )


def test_flash_attention_block_shape_independence():
    """Result must not depend on the VMEM tiling."""
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (1, 4, 256, 64), jnp.float32)
    k = rand(ks[1], (1, 4, 256, 64), jnp.float32)
    v = rand(ks[2], (1, 4, 256, 64), jnp.float32)
    o1 = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    o2 = flash_attention(q, k, v, block_q=128, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


# ---------------------------------------------------------------------------
# fused adam
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(1000,), (128, 257), (3, 5, 7), (4096,)])
@pytest.mark.parametrize("pdtype", [jnp.bfloat16, jnp.float32])
def test_fused_adam_matches_ref(shape, pdtype):
    ks = jax.random.split(KEY, 5)
    p = rand(ks[0], shape, pdtype)
    g = rand(ks[1], shape, pdtype)
    master = rand(ks[2], shape, jnp.float32)
    m = rand(ks[3], shape, jnp.float32) * 0.1
    v = jnp.abs(rand(ks[4], shape, jnp.float32)) * 0.01
    hp = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, bc1=0.4, bc2=0.3)
    scal = jnp.array([hp["lr"], hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"],
                      hp["bc1"], hp["bc2"], 0.0], jnp.float32)
    got = fused_adam(p, g, master, m, v, scal, interpret=True)
    want = R.fused_adam_ref(p, g, master, m, v, **hp)
    for a, b_ in zip(got, want):
        assert a.shape == b_.shape and a.dtype == b_.dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b_, np.float32), atol=1e-5, rtol=1e-5
        )


def test_fused_adam_integrates_with_optimizer():
    from repro.optim.adam import AdamConfig, adam_update, init_opt_state

    params = {"w": rand(KEY, (64, 64), jnp.bfloat16)}
    grads = {"w": rand(jax.random.PRNGKey(1), (64, 64), jnp.bfloat16)}
    s0 = init_opt_state(params)
    ref_p, ref_s, _ = adam_update(params, grads, s0, AdamConfig(), 1e-3)
    s1 = init_opt_state(params)
    fus_p, fus_s, _ = adam_update(
        params, grads, s1, AdamConfig(use_fused_kernel=True), 1e-3
    )
    np.testing.assert_allclose(
        np.asarray(ref_p["w"], np.float32), np.asarray(fus_p["w"], np.float32), atol=1e-3
    )
    np.testing.assert_allclose(
        np.asarray(ref_s["m"]["w"]), np.asarray(fus_s["m"]["w"]), atol=1e-5
    )


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(8, 256), (2, 64, 512), (100, 384)])
@pytest.mark.parametrize("dtype,atol", [(jnp.bfloat16, 2e-2), (jnp.float32, 1e-5)])
def test_rmsnorm_matches_ref(shape, dtype, atol):
    x = rand(KEY, shape, dtype)
    scale = rand(jax.random.PRNGKey(1), shape[-1:], dtype) + 1.0
    got = rmsnorm(x, scale, interpret=True)
    want = R.rmsnorm_ref(x, scale)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol, rtol=atol
    )


# ---------------------------------------------------------------------------
# package dispatch: the Pallas mode follows the backend
# ---------------------------------------------------------------------------
def test_package_dispatch_routes_through_capability_check(monkeypatch):
    """The public ops come from the package and run the Pallas kernel:
    interpret mode on the CPU backend, compiled on TPU, and an error on any
    other backend (never a silent reference fallback). The fused kernel
    agrees with the reference oracle."""
    from repro import compat
    from repro import kernels as K

    probe = compat.pallas_interpret_required.__wrapped__
    assert probe() is (jax.default_backend() == "cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert probe() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no Pallas mode"):
        probe()
    monkeypatch.undo()
    p = rand(KEY, (64, 32), jnp.bfloat16)
    g = rand(jax.random.fold_in(KEY, 1), (64, 32), jnp.bfloat16)
    master = p.astype(jnp.float32)
    m = jnp.zeros_like(master)
    v = jnp.zeros_like(master)
    kw = dict(lr=1e-2, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.0,
              bc1=0.1, bc2=0.05)
    got = K.fused_adam_update(p, g, master, m, v, **kw)
    want = R.fused_adam_ref(p, g, master, m, v, **kw)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-2)


def test_step_builder_can_request_fused_adam():
    """AdamConfig(use_fused_kernel=True) must lower and run on the CPU test
    backend (interpret mode) — the ROADMAP's capability-check wiring."""
    from repro.optim.adam import AdamConfig, adam_update, init_opt_state

    params = {"w": rand(KEY, (32, 16), jnp.bfloat16)}
    grads = {"w": rand(jax.random.fold_in(KEY, 2), (32, 16), jnp.bfloat16)}
    opt = init_opt_state(params)
    cfg = AdamConfig(lr=1e-2, use_fused_kernel=True)
    new_p, new_opt, gnorm = jax.jit(
        lambda p, g, o: adam_update(p, g, o, cfg, cfg.lr))(params, grads, opt)
    ref_p, ref_opt, _ = adam_update(params, grads, opt,
                                    AdamConfig(lr=1e-2), 1e-2)
    np.testing.assert_allclose(
        np.asarray(new_p["w"], np.float32), np.asarray(ref_p["w"], np.float32),
        atol=2e-2)
    assert float(gnorm) > 0
