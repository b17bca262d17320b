"""Per-architecture smoke tests: reduced config, one forward/train step on CPU,
asserting output shapes and no NaNs. Full configs are only exercised via the
dry-run (ShapeDtypeStruct, no allocation)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, reduced, shapes_for
from repro.models import model as M
from repro.models import kvcache as KV

KEY = jax.random.PRNGKey(0)
B, S = 2, 64


def make_batch(cfg, key=KEY, batch=B, seq=S):
    dt = jnp.dtype(cfg.dtype)
    batch_d = {"tokens": jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)}
    if cfg.kind == "encdec":
        batch_d["frames"] = jax.random.normal(key, (batch, seq, cfg.d_model), dt)
    if cfg.frontend == "vision_patches":
        batch_d["patches"] = jax.random.normal(key, (batch, 8, cfg.d_model), dt)
    return batch_d


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_forward_shapes_no_nan(name):
    cfg = reduced(ARCHS[name])
    params = M.init_params(cfg, KEY)
    h, aux = M.forward(params, make_batch(cfg), cfg)
    assert h.shape == (B, S, cfg.d_model)
    assert not jnp.any(jnp.isnan(h.astype(jnp.float32)))
    assert not jnp.isnan(aux)


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_train_step_decreases_loss(name):
    """One SGD step on the reduced config should be finite and reduce loss."""
    cfg = reduced(ARCHS[name])
    params = M.init_params(cfg, KEY)
    batch = make_batch(cfg)
    labels = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)

    def loss_fn(p):
        h, aux = M.forward(p, batch, cfg)
        logits = M.lm_head(p, h, cfg).astype(jnp.float32)
        ll = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(ll, labels[..., None], axis=-1).mean()
        return nll + aux

    l0, grads = jax.value_and_grad(loss_fn)(params)
    assert jnp.isfinite(l0)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads)))
    assert jnp.isfinite(gnorm) and gnorm > 0
    params2 = jax.tree.map(lambda p, g: p - 0.5 * g.astype(p.dtype), params, grads)
    l1 = loss_fn(params2)
    assert jnp.isfinite(l1)
    assert l1 < l0 + 1e-3  # non-increase (small step)


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_decode_step_matches_prefill(name):
    """Decoding token-by-token must match the teacher-forced forward pass.

    Run in fp32: in bf16, tiny path differences flip MoE top-k routing
    decisions and amplify — algorithmic equivalence is what we assert here.
    """
    import dataclasses

    cfg = dataclasses.replace(reduced(ARCHS[name]), dtype="float32")
    if cfg.frontend == "vision_patches":
        pytest.skip("decode tested on the LM part only for VLM")
    params = M.init_params(cfg, KEY)
    seq = 16
    batch = make_batch(cfg, seq=seq)
    h, _ = M.forward(params, batch, cfg, attn_impl="naive")
    logits_ref = M.lm_head(params, h, cfg).astype(jnp.float32)

    cache = KV.init_cache(cfg, B, seq)
    if cfg.kind == "encdec":
        # prime cross-attention cache from the encoder output
        memory = M.encode(params, batch["frames"], cfg)
        p = M.superblock_period(cfg)
        r = M.num_repeats(cfg)
        hd = cfg.resolved_head_dim
        for j in range(p):
            ap = params["blocks"][f"pos{j}"]["xattn"]
            xk = jnp.einsum("bsd,rdk->rbsk", memory, ap["wk"]).reshape(
                r, B, seq, cfg.num_kv_heads, hd
            )
            xv = jnp.einsum("bsd,rdk->rbsk", memory, ap["wv"]).reshape(
                r, B, seq, cfg.num_kv_heads, hd
            )
            cache[f"pos{j}"]["xk"] = xk.astype(cache[f"pos{j}"]["xk"].dtype)
            cache[f"pos{j}"]["xv"] = xv.astype(cache[f"pos{j}"]["xv"].dtype)

    step = jax.jit(lambda c, t, p: KV.decode_step(params, c, t, p, cfg))
    outs = []
    for t in range(seq):
        logits, cache = step(cache, batch["tokens"][:, t : t + 1], jnp.int32(t))
        outs.append(logits)
    logits_dec = jnp.stack(outs, axis=1).astype(jnp.float32)
    assert logits_dec.shape == logits_ref.shape
    err = jnp.abs(logits_dec - logits_ref).max() / (jnp.abs(logits_ref).max() + 1e-6)
    assert err < 2e-3, f"decode/prefill mismatch {err}"


def test_sliding_window_cache_is_ring():
    cfg = reduced(ARCHS["mixtral-8x22b"], sliding_window=8)
    specs = KV.cache_specs(cfg, B, 64)
    assert specs["pos0"]["k"].shape[2] == 8  # ring of window size, not 64


def test_mamba_cache_is_constant_size():
    cfg = reduced(ARCHS["mamba2-130m"])
    s1 = KV.cache_specs(cfg, B, 64)
    s2 = KV.cache_specs(cfg, B, 4096)
    assert jax.tree.map(lambda a: a.shape, s1) == jax.tree.map(lambda a: a.shape, s2)


# ---------------------------------------------------------------------------
# partial rotary embeddings and the norm's eps (StableLM-3B-4E1T)
# ---------------------------------------------------------------------------
def _rope_before(x, positions, theta):
    """apply_rope as it was before ``fraction``: the whole head rotates."""
    from repro.models.layers import rope_frequencies

    freqs = rope_frequencies(x.shape[-1], theta)
    angles = positions[..., :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[..., :, None, :], jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_full_rotary_is_bitwise_the_rope_before(dtype):
    from repro.models.layers import apply_rope

    x = jax.random.normal(KEY, (2, 16, 4, 32), dtype)
    pos = jnp.arange(16)
    fn = jax.jit(lambda x: apply_rope(x, pos, 10000.0, 1.0))
    assert jnp.array_equal(fn(x), jax.jit(lambda x: _rope_before(x, pos, 10000.0))(x))


@pytest.mark.parametrize("head_dim", [80, 32])
def test_partial_rotary_matches_the_reference(head_dim):
    """A quarter of each head rotates, as the benchmark's float32 reference
    (``benchmarks/chip/reference/model.rope``) and StableLM rotate it."""
    import sys
    from pathlib import Path

    import numpy as np

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks" / "chip"))
    from reference.model import rope

    from repro.models.layers import apply_rope

    x = jax.random.normal(KEY, (2, 64, 4, head_dim), jnp.float32)
    got = apply_rope(x, jnp.arange(64), 10000.0, 0.25)
    np.testing.assert_allclose(got, rope(x, 10000.0, 0.25), rtol=1e-5, atol=1e-5)
    rd = int(0.25 * head_dim)
    assert jnp.array_equal(got[..., rd:], x[..., rd:])  # the rest passes through


def test_norm_eps_reaches_layernorm(monkeypatch):
    """Every LayerNorm of the forward, the final one included, takes the
    configuration's eps."""
    from repro.models import layers as L

    cfg = reduced(ARCHS["stablelm-3b"])
    assert (cfg.rotary_fraction, cfg.norm_eps, cfg.norm) == (0.25, 1e-5, "layernorm")
    seen = []
    layernorm = L.layernorm

    def spy(x, scale, bias, eps=1e-6):
        seen.append(eps)
        return layernorm(x, scale, bias, eps)

    monkeypatch.setattr(L, "layernorm", spy)
    params = M.init_params(cfg, KEY)
    h, _ = M.forward(params, make_batch(cfg), cfg)
    M.lm_head(params, h, cfg)
    assert seen and set(seen) == {1e-5}
    # and the eps changes the result: scale-1 rows of tiny variance
    x = jnp.full((1, 4, 8), 1.0) + 1e-3 * jnp.arange(8.0)
    ones, zeros = jnp.ones(8), jnp.zeros(8)
    assert not jnp.allclose(layernorm(x, ones, zeros, 1e-5), layernorm(x, ones, zeros, 1e-6))
