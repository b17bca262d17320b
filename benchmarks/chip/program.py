"""The benchmark's side of the program under test (``src/repro``).

Everything here goes through the program's normal training path: its
``ModelConfig``, ``launch.train.fit_plan`` (search, ``build_train_step``,
compile, re-search on a refusal) and the step ``StepArtifacts.jit()``
returns, fed as ``train.loop.train_loop`` feeds it. The benchmark makes the
initial state itself, from the seed (``weights.py``), so that the reference
can make the same weights again; and it reads Adam's state back for the
correctness check.

The program's parameter tree is ``{"embed": {"tok"}, "final_norm":
{"scale", "bias"}, "head": {"w"}, "runs": [{"pos0": {...}}, ...]}``: each
run stacks the layers ``[start, start + length)`` of one plan placement.
``leaf_map`` names each program leaf canonically (``weights.py``).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference.model import rotary_fraction
from weights import Leaf, global_leaves, layer_leaves, make_leaf, make_stack

MLP_KINDS = {"gelu_tanh": "gelu", "swiglu": "swiglu"}
# what the program's blocks do where its ModelConfig has no field to say so
BUILT_IN = {"rotary_fraction": 1.0, "norm_eps": 1e-6}


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file. The rotary
    fraction and the norm's eps go to ``ModelConfig`` fields of those names
    where it has them; where it has not, a file that asks for another value
    than the program's built-in one is refused, naming the key, so that the
    program never runs other equations than the file states."""
    from repro.configs.base import ModelConfig

    if cfg["norm"] != "layernorm":
        raise ValueError(f"{cfg['name']}: the program runs layernorm only")
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    stated = {"rotary_fraction": rotary_fraction(cfg), "norm_eps": cfg["norm_eps"]}
    extra = {}
    for key, value in stated.items():
        if key in fields:
            extra[key] = value
        elif value != BUILT_IN[key]:
            raise ValueError(f"{cfg['name']}: {key} {value} is not the program's built-in "
                             f"{BUILT_IN[key]}, and its ModelConfig has no field {key!r}")
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        mlp=MLP_KINDS[cfg["mlp"]], norm="layernorm", rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["param_dtype"], **extra,
    )


def adam_config(opt: dict):
    from repro.optim.adam import AdamConfig

    return AdamConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                      weight_decay=opt["weight_decay"], grad_clip=opt["grad_clip"])


def fit(cfg: dict, traffic: dict, mesh, hw, log):
    """``fit_plan`` for the job, with the program's own builder."""
    from repro.configs.base import ShapeConfig
    from repro.launch.train import fit_plan
    from repro.train.step_builder import build_train_step

    mcfg = model_config(cfg)
    shape = ShapeConfig("bench", traffic["seq_len"], traffic["global_batch"], "train")
    adam = adam_config(traffic["optimizer"])

    def build(plan):
        return build_train_step(mcfg, plan, mesh, shape, adam=adam)

    return fit_plan(mcfg, shape, mesh, hw, build, log=log)


@dataclasses.dataclass(frozen=True)
class ProgramLeaf:
    path: tuple  # key path below state["params"] (and state["opt"][...])
    leaf: Leaf  # canonical leaf, per layer for stacked ones
    layers: range | None  # stacked layers, None outside the stack

    def names(self) -> list[str]:
        if self.layers is None:
            return [self.leaf.name]
        return [f"layers.{l}.{self.leaf.name}" for l in self.layers]


def leaf_map(cfg: dict, art) -> list[ProgramLeaf]:
    """Every program parameter leaf with its canonical name and layers."""
    glob = {l.name: l for l in global_leaves(cfg)}
    per_layer = {l.name: l for l in layer_leaves(cfg)}
    out = []
    flat, _ = jax.tree_util.tree_flatten_with_path(art.state_specs["params"])
    for path, spec in flat:
        keys = [_key(k) for k in path]
        if keys[0] == "runs":
            run = art.runs[keys[1]]
            if keys[2] != "pos0":
                raise ValueError(f"superblocks of more than one layer: {keys}")
            leaf = per_layer[".".join(keys[3:])]
            layers = range(run.start, run.start + run.length)
            want = (run.length, *leaf.shape)
        else:
            leaf, layers = glob[".".join(keys)], None
            want = leaf.shape
        if tuple(spec.shape) != want:
            raise ValueError(f"program leaf {keys} has shape {spec.shape}, expected {want}")
        out.append(ProgramLeaf(tuple(path), leaf, layers))
    names = [n for pl in out for n in pl.names()]
    expected = len(glob) + cfg["num_hidden_layers"] * len(per_layer)
    if len(set(names)) != len(names) or len(names) != expected:
        raise ValueError(f"program leaves do not cover the model once: {len(names)} "
                         f"of {expected}")
    return out


def _key(k):
    """A dict key or list index from a ``tree_flatten_with_path`` entry."""
    return getattr(k, "key", getattr(k, "idx", None))


def _get(tree, path):
    for k in path:
        tree = tree[_key(k)]
    return tree


def _set(tree, path, value):
    _get(tree, path[:-1])[_key(path[-1])] = value


def make_state(cfg: dict, art, leaves: list[ProgramLeaf], key_data):
    """The program's initial train state from the seed, with nothing in
    device memory beyond the state itself, so that the process's peak is
    the step's: parameters made in device memory in one jitted call, in the
    type the program keeps them in; fp32 masters cast on the host and
    placed where ``state_specs`` says (host memory included); zero Adam
    moments made where they live, so a host-placed one never crosses the
    host link. Returns the state and the fp32 masters as placed, by leaf
    path, for ``change_norms``."""
    dtype = jnp.dtype(cfg["param_dtype"])
    p_specs = art.state_specs["params"]
    p_dev = jax.tree.map(lambda s: s.sharding.with_memory_kind("device"), p_specs)

    def gen(key_data):
        out = jax.tree.map(lambda s: None, p_specs)
        for pl in leaves:
            v = (make_leaf(key_data, pl.leaf, None, dtype) if pl.layers is None
                 else make_stack(key_data, pl.leaf, pl.layers, dtype))
            _set(out, pl.path, v)
        return out

    params = jax.jit(gen, out_shardings=p_dev)(key_data)
    state = jax.tree.map(lambda s: None, art.state_specs)
    masters = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(art.state_specs)
    for path, spec in flat:
        keys = [_key(k) for k in path]
        if keys[0] == "params":
            v = jax.device_put(_get(params, path[1:]), spec.sharding)
        elif keys[:2] == ["opt", "master"]:
            w0 = np.asarray(_get(params, path[2:])).astype(np.float32)
            masters[tuple(keys[2:])] = w0
            v = jax.device_put(w0, spec.sharding)
        elif _in_host_memory(spec.sharding):
            v = jax.device_put(np.zeros(spec.shape, spec.dtype), spec.sharding)
        else:
            v = jnp.zeros(spec.shape, spec.dtype, device=spec.sharding)
        _set(state, path, v)
    return state, masters


def _in_host_memory(sharding) -> bool:
    return getattr(sharding, "memory_kind", None) not in (None, "device")


def _layer_rows(x, pl: ProgramLeaf) -> np.ndarray:
    """A leaf on the host, one float32 row per layer."""
    n = 1 if pl.layers is None else len(pl.layers)
    return np.asarray(x, np.float32).reshape(n, -1)


def _norm(row: np.ndarray) -> float:
    # np.sum adds pairwise: float32 round-off far below the limits
    return math.sqrt(float(np.sum(np.square(row), dtype=np.float64)))


def grad_norms(state, leaves: list[ProgramLeaf], b1: float) -> dict[str, float]:
    """The first clipped gradient per canonical leaf, as Adam's first moment
    holds it after one step (m_1 = (1 - b1) g_1), read on the host."""
    out = {}
    for pl in leaves:
        rows = _layer_rows(_get(state["opt"]["m"], pl.path), pl)
        out.update(zip(pl.names(), (_norm(r) / (1 - b1) for r in rows)))
    return out


def change_norms(state, leaves: list[ProgramLeaf], masters: dict) -> dict[str, float]:
    """|master - master_0| per canonical leaf, read on the host (host-placed
    masters included), against the masters ``make_state`` placed."""
    out = {}
    for pl in leaves:
        rows = _layer_rows(_get(state["opt"]["master"], pl.path), pl)
        rows0 = _layer_rows(masters[tuple(_key(k) for k in pl.path)], pl)
        out.update(zip(pl.names(), (_norm(r - r0) for r, r0 in zip(rows, rows0))))
    return out
