"""The plain reference's training steps, layer by layer.

Each step runs the forward pass one layer at a time (keeping only the
layer inputs), the loss head, and the backward pass one layer at a time
through ``jax.vjp`` of ``model.layer``. Rows go through in blocks, and
attention within a block of rows in blocks of queries (``model.py``), so
that one block's attention scores stay under ``model.SCORE_BYTES``.

The raw fp32 gradients of the checked steps live in host memory (NumPy
arrays), each layer's copied there as soon as its backward pass ends. The
device holds the layer inputs, one layer's parameters and gradients, the
global leaves, one block's attention and the loss head: never the whole
fp32 training state (master, m and v), nor the gradients of a whole step,
each of which is larger than one chip at a few billion parameters.

Adam is replayed leaf by leaf from the initial weights and the stored
gradients, which go to the device for that leaf's replay alone: ``clip ->
m, v -> bias correction -> master -= lr * update``, the update rule the
configuration states (fp32 state, global-norm clipping, no weight decay
unless the job sets it).

``opt_dtype`` and ``rows`` exist for the control and the planted fault:
``opt_dtype=bfloat16`` rounds master, m and v to bfloat16 after each
update (the precision below the configuration's float32 optimizer
state), ``rows`` trains on a subset of each batch's rows.
"""
from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from reference import model
from weights import global_leaves, layer_leaves, make_leaf, seed_key


@dataclasses.dataclass
class Readings:
    """What the correctness check compares, for the program or the reference."""

    losses: list[float]  # loss of each step, as the step computed it
    grad_norms: dict[str, float]  # first clipped gradient, per leaf, from Adam's m
    change_norms: dict[str, float]  # |master_n - master_0| per leaf after n steps


def leaf_name(leaf, layer) -> str:
    return leaf.name if layer is None else f"layers.{layer}.{leaf.name}"


class Reference:
    def __init__(self, cfg: dict, opt: dict, *, opt_dtype=jnp.float32, rows=None,
                 device=None):
        model.rotary_fraction(cfg)  # refuses positions the reference does not know
        if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
            raise ValueError("grouped-query attention is not in the reference")
        self.cfg, self.opt, self.rows = cfg, opt, rows
        self.device = device or jax.devices()[0]
        self.layer = layer_leaves(cfg)
        self.glob = {l.name: l for l in global_leaves(cfg)}
        self.fns = _fns(json.dumps(cfg, sort_keys=True), json.dumps(opt, sort_keys=True),
                        jnp.dtype(opt_dtype).name)

    def _put(self, x):
        return jax.device_put(x, self.device)

    def _params(self, key, leaf, layer, grads, clips):
        """master_{k-1} of one leaf, as step k reads it."""
        gs = tuple(self._put(g) for g in grads)
        return self.fns.replay(key, gs, jnp.asarray(clips, jnp.float32),
                               -1 if layer is None else layer, leaf=leaf)[0]

    def _layer_params(self, key, l, store, clips):
        return {lf.name: self._params(key, lf, l, store[leaf_name(lf, l)], clips)
                for lf in self.layer}

    def run(self, seed: int, batches: list[dict]) -> Readings:
        store, clips, losses = self.steps(seed, batches)
        return self.readings(seed, store, clips, losses)

    def steps(self, seed: int, batches: list[dict]):
        """The training steps: the stored gradients (host arrays, per leaf,
        one per step), the clipping factor and the loss of each step."""
        key = self._put(seed_key(seed))
        names = [leaf_name(lf, None) for lf in self.glob.values()] + [
            leaf_name(lf, l) for l in range(self.cfg["num_hidden_layers"]) for lf in self.layer]
        store: dict[str, list[np.ndarray]] = {n: [] for n in names}
        clips: list[float] = []
        losses: list[float] = []
        for batch in batches:
            loss, sumsq = self._step(key, batch, store, clips)
            losses.append(loss)
            clips.append(min(1.0, self.opt["grad_clip"] / max(np.sqrt(sumsq), 1e-12)))
        return store, clips, losses

    def _step(self, key, batch: dict, store: dict, clips: list[float]):
        """One step: appends each leaf's gradient to ``store``; returns the
        loss and the gradients' sum of squares. Its device arrays end with
        it."""
        cfg, f = self.cfg, self.fns
        n_layers = cfg["num_hidden_layers"]
        tokens, labels = batch["tokens"], batch["labels"]
        if self.rows is not None:
            tokens, labels = tokens[self.rows], labels[self.rows]
        n_tok = tokens.size
        rb = max(1, min(tokens.shape[0], model.SCORE_BYTES // (
            4 * cfg["num_attention_heads"] * tokens.shape[1] ** 2)))
        blocks = [slice(i, i + rb) for i in range(0, tokens.shape[0], rb)]
        g = {n: self._params(key, lf, None, store[n], clips) for n, lf in self.glob.items()}
        emb = g["embed.tok"]
        w_out = emb.T if cfg["tie_word_embeddings"] else g["head.w"]
        xs = [[f.embed(emb, self._put(tokens[b])) for b in blocks]]
        for l in range(n_layers):
            p = self._layer_params(key, l, store, clips)
            xs.append([f.layer_fwd(p, x) for x in xs[-1]])
        del p
        fin = {k: g[k] for k in ("final_norm.scale", "final_norm.bias")}
        total, grads, dxs = 0.0, {}, []
        for b, x in zip(blocks, xs[-1]):
            ls, (d_fin, d_w, dx) = f.head(fin, w_out, x, self._put(labels[b]),
                                          jnp.float32(n_tok))
            total += float(ls)
            _acc(grads, d_fin)
            _acc(grads, {"w_out": d_w})
            dxs.append(dx)
        sumsq = 0.0
        for l in reversed(range(n_layers)):
            sumsq += self._layer_backward(key, l, store, clips, xs[l], dxs)
            xs[l + 1] = None
        d_emb = sum(f.embed_bwd(dx, self._put(tokens[b]), cfg["vocab_size"])
                    for b, dx in zip(blocks, dxs))
        if cfg["tie_word_embeddings"]:
            d_emb = d_emb + grads.pop("w_out").T
        else:
            grads["head.w"] = grads.pop("w_out")
        grads["embed.tok"] = d_emb
        for k, v in grads.items():
            sumsq += float(f.sumsq(v))
            store[k].append(np.asarray(v))
        return total / n_tok, sumsq

    def _layer_backward(self, key, l: int, store: dict, clips: list[float], xs: list,
                        dxs: list) -> float:
        """Layer ``l``'s backward over the row blocks: ``dxs`` become the
        gradients of the layer's inputs ``xs``; the layer's gradients go to
        ``store`` on the host. Returns their sum of squares."""
        f = self.fns
        p = self._layer_params(key, l, store, clips)
        dp = {}
        for i, x in enumerate(xs):
            d, dxs[i] = f.layer_bwd(p, x, dxs[i])
            _acc(dp, d)
        sumsq = 0.0
        for k, v in dp.items():
            sumsq += float(f.sumsq(v))
            store[f"layers.{l}.{k}"].append(np.asarray(v))
        return sumsq

    def readings(self, seed: int, store: dict, clips: list[float],
                 losses: list[float]) -> Readings:
        """Adam replayed over the stored gradients, leaf by leaf."""
        f, key = self.fns, self._put(seed_key(seed))
        grad_norms, change_norms = {}, {}
        for l in [None, *range(self.cfg["num_hidden_layers"])]:
            for lf in (self.glob.values() if l is None else self.layer):
                n = leaf_name(lf, l)
                gs = tuple(self._put(x) for x in store[n])
                g1, ch = f.readings(key, gs, jnp.asarray(clips, jnp.float32),
                                    -1 if l is None else l, leaf=lf)
                grad_norms[n], change_norms[n] = float(g1), float(ch)
        return Readings(losses, grad_norms, change_norms)


def _acc(acc: dict, new: dict) -> None:
    for k, v in new.items():
        acc[k] = v if k not in acc else acc[k] + v


@dataclasses.dataclass(frozen=True)
class _Fns:
    embed: callable
    layer_fwd: callable
    layer_bwd: callable
    head: callable
    embed_bwd: callable
    sumsq: callable
    replay: callable
    readings: callable


@functools.lru_cache(maxsize=None)
def _fns(cfg_json: str, opt_json: str, opt_dtype: str) -> _Fns:
    """The jitted pieces for one configuration, optimizer and optimizer
    precision (cached, so that the control and the fault reuse them)."""
    cfg, opt = json.loads(cfg_json), json.loads(opt_json)
    pdtype = jnp.dtype(cfg["param_dtype"])
    finfo = jnp.finfo(jnp.dtype(opt_dtype))

    def rnd(a):
        # reduce_precision, not a convert pair: XLA may drop f32->bf16->f32
        return jax.lax.reduce_precision(a, exponent_bits=finfo.nexp, mantissa_bits=finfo.nmant)

    glob = {l.name for l in global_leaves(cfg)}

    def init(key, layer, leaf):
        return make_leaf(key, leaf, None if leaf.name in glob else layer,
                         pdtype).astype(jnp.float32)

    def run_adam(key, gs, clips, layer, leaf):
        w0 = init(key, layer, leaf)
        master, m, v = w0, jnp.zeros_like(w0), jnp.zeros_like(w0)
        m1 = m
        for j, g in enumerate(gs, 1):
            g = g * clips[j - 1]
            m = rnd(opt["b1"] * m + (1 - opt["b1"]) * g)
            v = rnd(opt["b2"] * v + (1 - opt["b2"]) * g * g)
            upd = (m / (1 - opt["b1"] ** j)) / (jnp.sqrt(v / (1 - opt["b2"] ** j)) + opt["eps"])
            if opt["weight_decay"]:
                upd = upd + opt["weight_decay"] * master
            master = rnd(master - opt["lr"] * upd)
            if j == 1:
                m1 = m
        return master, w0, m1

    @functools.partial(jax.jit, static_argnames=("leaf",))
    def replay(key, gs, clips, layer, *, leaf):
        master, _, _ = run_adam(key, gs, clips, layer, leaf)
        return (master,)

    @functools.partial(jax.jit, static_argnames=("leaf",))
    def readings(key, gs, clips, layer, *, leaf):
        master, w0, m1 = run_adam(key, gs, clips, layer, leaf)
        return (jnp.sqrt(jnp.sum(jnp.square(m1))) / (1 - opt["b1"]),
                jnp.sqrt(jnp.sum(jnp.square(master - w0))))

    layer_fn = functools.partial(model.layer, cfg=cfg)

    @jax.jit
    def layer_bwd(p, x, dy):
        _, vjp = jax.vjp(layer_fn, p, x)
        return vjp(dy)

    @jax.jit
    def head(fin, w_out, x, labels, n_tok):
        def f(fin, w_out, x):
            return model.loss_sum(fin, w_out, x, labels, cfg) / n_tok

        ls, vjp = jax.vjp(f, fin, w_out, x)
        return ls * n_tok, vjp(jnp.ones_like(ls))

    return _Fns(
        embed=jax.jit(lambda emb, t: jnp.take(emb, t, axis=0)),
        layer_fwd=jax.jit(layer_fn),
        layer_bwd=layer_bwd,
        head=head,
        embed_bwd=jax.jit(lambda dx, t, v: jnp.zeros((v, dx.shape[-1]), jnp.float32)
                          .at[t].add(dx), static_argnums=2),
        sumsq=jax.jit(lambda a: jnp.sum(jnp.square(a))),
        replay=replay,
        readings=readings,
    )
