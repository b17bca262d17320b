"""The reference's equations on the CPU: attention by blocks of queries,
partial rotary embeddings, gradients kept in host memory, and the config
keys the program has no field for yet."""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import program
from conftest import BENCH, TINY, TINY_JOB
from reference import model
from reference.train import Reference
from tokens import make_batch

GPT2 = json.loads((BENCH / "configs" / "gpt2-1b.json").read_text())


def tiny_cfg(**kw):
    return {**GPT2, **TINY, **kw}


def rope_full_head_before(x, theta):
    """The reference's rope before partial rotation existed, verbatim."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def stablelm_rope(q, fraction, theta):
    """StableLM's ``apply_rotary_pos_emb`` (Hugging Face ``modeling_stablelm``)
    in NumPy float64: ``rotary_ndims = int(head_dim * partial_rotary_factor)``,
    the rotary embedding built over those dims, ``q * cos + rotate_half(q) *
    sin`` on them, and the rest of the head concatenated unchanged.
    q: (B, S, H, hd)."""
    q = np.asarray(q, np.float64)
    rd = int(q.shape[-1] * fraction)
    inv_freq = 1.0 / (theta ** (np.arange(0, rd, 2, dtype=np.float64) / rd))
    freqs = np.outer(np.arange(q.shape[1], dtype=np.float64), inv_freq)
    emb = np.concatenate([freqs, freqs], axis=-1)
    cos, sin = np.cos(emb)[None, :, None, :], np.sin(emb)[None, :, None, :]
    q_rot, q_pass = q[..., :rd], q[..., rd:]
    rotate_half = np.concatenate([-q_rot[..., rd // 2:], q_rot[..., : rd // 2]], axis=-1)
    return np.concatenate([q_rot * cos + rotate_half * sin, q_pass], axis=-1)


def test_full_rotary_is_bitwise_the_rope_before():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 48, 4, 80), jnp.float32)
    before = np.asarray(rope_full_head_before(x, 10000.0))
    assert np.array_equal(np.asarray(model.rope(x, 10000.0)), before)
    assert np.array_equal(np.asarray(model.rope(x, 10000.0, 1.0)), before)


def test_partial_rotary_matches_stablelm():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, 4, 80), jnp.float32)
    got = np.asarray(model.rope(x, 10000.0, 0.25))
    np.testing.assert_allclose(got, stablelm_rope(x, 0.25, 10000.0), rtol=0, atol=2e-5)
    assert np.array_equal(got[..., 20:], np.asarray(x)[..., 20:])
    assert not np.allclose(got[..., :20], np.asarray(x)[..., :20])


@pytest.mark.parametrize("positions", [
    {"positions": "rope_full_head"},
    {"positions": "rope_partial", "rotary_fraction": 1.0},
    {"positions": "rope_partial", "rotary_fraction": 0.25},
])
def test_rotary_fraction_from_the_config(positions):
    want = positions.get("rotary_fraction", 1.0)
    assert model.rotary_fraction(tiny_cfg(**positions)) == want


@pytest.mark.parametrize("positions", [
    {"positions": "learned"},
    {"positions": "rope_partial", "rotary_fraction": 0.0},
    {"positions": "rope_partial", "rotary_fraction": 1.5},
])
def test_rotary_fraction_refuses_what_the_reference_does_not_run(positions):
    with pytest.raises(ValueError):
        model.rotary_fraction(tiny_cfg(**positions))


@pytest.mark.parametrize("score_bytes", [4 * 2 * 4 * 48 * 16, 4 * 2 * 4 * 48 * 20])
@pytest.mark.parametrize("fraction", [1.0, 0.25])
def test_query_blocks_match_one_block(monkeypatch, score_bytes, fraction):
    """Attention over blocks of 16 or 20 queries (3 blocks of 48, the last
    one short) against one block, forward and gradient."""
    cfg = tiny_cfg(positions="rope_partial", rotary_fraction=fraction)
    d = cfg["hidden_size"]
    keys = jax.random.split(jax.random.PRNGKey(2), 6)
    p = {n: jax.random.normal(k, (d, d), jnp.float32) / np.sqrt(d)
         for n, k in zip(("attn.wq", "attn.wk", "attn.wv", "attn.wo"), keys)}
    h = jax.random.normal(keys[4], (2, 48, d), jnp.float32)
    dy = jax.random.normal(keys[5], (2, 48, d), jnp.float32)

    def run():
        out, vjp = jax.vjp(lambda p, h: model.attention(p, h, cfg), p, h)
        return out, vjp(dy)

    assert model.query_block(2, 4, 48) == 48
    one = run()
    monkeypatch.setattr(model, "SCORE_BYTES", score_bytes)
    assert model.query_block(2, 4, 48) in (16, 20)
    blocked = run()
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(blocked)):
        # float32 sums over the blocks in another order: within 1e-6 of the
        # array's largest magnitude
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(b - a)) <= 1e-6 * np.max(np.abs(a)), np.max(np.abs(b - a))


def test_stored_gradients_are_host_arrays():
    cfg = tiny_cfg(mlp="swiglu", tie_word_embeddings=False, positions="rope_partial",
                   rotary_fraction=0.25, norm_eps=1e-5)
    b, s = TINY_JOB["global_batch"], TINY_JOB["seq_len"]
    batches = [make_batch(3, i, b, s, cfg["vocab_size"]) for i in range(2)]
    ref = Reference(cfg, TINY_JOB["optimizer"])
    store, clips, losses = ref.steps(3, batches)
    grads = [g for gs in store.values() for g in gs]
    assert all(len(gs) == 2 for gs in store.values()) and len(clips) == 2
    assert all(type(g) is np.ndarray and g.dtype == np.float32 for g in grads)
    readings = ref.readings(3, store, clips, losses)
    assert all(np.isfinite([*readings.losses, *readings.grad_norms.values(),
                            *readings.change_norms.values()]))


def test_model_config_of_gpt2_is_unchanged():
    from repro.configs.base import ModelConfig

    assert program.model_config(GPT2) == ModelConfig(
        name="gpt2-1b", family="dense", num_layers=18, d_model=2048, num_heads=16,
        num_kv_heads=16, d_ff=8192, vocab_size=50257, mlp="gelu", norm="layernorm",
        rope_theta=10000.0, tie_embeddings=True, dtype="bfloat16")


@pytest.mark.parametrize("key, stated", [
    ("rotary_fraction", {"positions": "rope_partial", "rotary_fraction": 0.25}),
    ("norm_eps", {"norm_eps": 1e-5}),
])
def test_model_config_refuses_what_the_program_has_no_field_for(key, stated):
    with pytest.raises(ValueError, match=key):
        program.model_config(tiny_cfg(**stated))


def test_model_config_passes_the_fields_the_program_has(monkeypatch):
    from repro.configs import base

    @dataclasses.dataclass(frozen=True)
    class WithFields(base.ModelConfig):
        rotary_fraction: float = 1.0
        norm_eps: float = 1e-6

    monkeypatch.setattr(base, "ModelConfig", WithFields)
    mcfg = program.model_config(tiny_cfg(positions="rope_partial", rotary_fraction=0.25,
                                         norm_eps=1e-5))
    assert (mcfg.rotary_fraction, mcfg.norm_eps) == (0.25, 1e-5)
