"""Seeded initial weights of a dense decoder, named leaf by leaf.

The benchmark makes the weights itself, so that the plain reference can
make the same ones again without taking anything from the program under
test. A leaf is named canonically: ``embed.tok``, ``final_norm.scale``,
``final_norm.bias``, ``head.w`` (untied models), and per layer
``norm1.scale``, ``norm1.bias``, ``attn.wq``, ``attn.wk``, ``attn.wv``,
``attn.wo``, ``norm2.scale``, ``norm2.bias``, ``mlp.w1``, ``mlp.w3`` (gated
MLPs), ``mlp.w2``. Each (name, layer) draws from its own key, so a leaf's
values do not depend on how a plan stacks or shards the layers.
"""
from __future__ import annotations

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Leaf:
    name: str
    shape: tuple[int, ...]
    init: str  # normal | ones | zeros
    std: float = 0.0


def layer_leaves(cfg: dict) -> list[Leaf]:
    """The leaves of one decoder layer, from a configuration file's sizes."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    nq, nkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    if cfg["norm"] != "layernorm":
        raise ValueError(f"unsupported norm {cfg['norm']!r}")

    def mat(name, shape):
        return Leaf(name, shape, "normal", 1.0 / np.sqrt(shape[0]))

    out = [Leaf("norm1.scale", (d,), "ones"), Leaf("norm1.bias", (d,), "zeros"),
           mat("attn.wq", (d, nq)), mat("attn.wk", (d, nkv)), mat("attn.wv", (d, nkv)),
           mat("attn.wo", (nq, d)),
           Leaf("norm2.scale", (d,), "ones"), Leaf("norm2.bias", (d,), "zeros"),
           mat("mlp.w1", (d, ff))]
    if cfg["mlp"] == "swiglu":
        out.append(mat("mlp.w3", (d, ff)))
    elif cfg["mlp"] != "gelu_tanh":
        raise ValueError(f"unsupported mlp {cfg['mlp']!r}")
    out.append(mat("mlp.w2", (ff, d)))
    return out


def global_leaves(cfg: dict) -> list[Leaf]:
    """The leaves outside the layer stack."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    out = [Leaf("embed.tok", (v, d), "normal", 0.02),
           Leaf("final_norm.scale", (d,), "ones"), Leaf("final_norm.bias", (d,), "zeros")]
    if not cfg["tie_word_embeddings"]:
        out.append(Leaf("head.w", (d, v), "normal", 0.02))
    return out


def seed_key(seed: int) -> jax.Array:
    """Raw uint32[2] key data for any non-negative seed, 64 bits of it used
    (``PRNGKey`` alone drops the bits above 32). Passed to jitted makers as
    an argument, so their programs do not depend on the seed."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF)


def make_leaf(key_data: jax.Array, leaf: Leaf, layer, dtype) -> jax.Array:
    """One leaf's initial value (traceable, ``layer`` may be traced too).
    ``layer`` None for leaves outside the stack. Values are drawn in float32
    and rounded to ``dtype``, the type the program keeps its parameters in."""
    if leaf.init == "ones":
        return jnp.ones(leaf.shape, dtype)
    if leaf.init == "zeros":
        return jnp.zeros(leaf.shape, dtype)
    key = jax.random.fold_in(key_data, zlib.crc32(leaf.name.encode()) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, 0 if layer is None else layer + 1)
    return (jax.random.normal(key, leaf.shape, jnp.float32) * leaf.std).astype(dtype)


def make_stack(key_data: jax.Array, leaf: Leaf, layers: range, dtype) -> jax.Array:
    """The leaf for consecutive layers, stacked on a leading axis."""
    return jnp.stack([make_leaf(key_data, leaf, l, dtype) for l in layers])
