"""``correct`` comes out false when the timed path is broken underneath, and
when the control (the reference with bfloat16 optimizer state) stands in
for the program. A tiny float32 cell on the CPU, under a host-chunk plan,
judged by the limits of ``gpt2-1b.s1024-b8``. The checks the harness runs
before the window (the look for a chip) are skipped; everything else is a
whole run."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import check
import run
from conftest import tiny_hw
from reference.train import Reference
from tokens import make_batch


def unchanged_state(step):
    """A step that returns its state unchanged (it still reports a loss)."""

    def f(state, batch):
        _, metrics = step(jax.tree.map(jnp.copy, state), batch)
        return state, metrics

    return f


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest: the second
    half of the rows repeats the first."""

    def f(state, batch):
        h = batch["tokens"].shape[0] // 2
        return step(state, {k: jnp.concatenate([v[:h], v[:h]]) for k, v in batch.items()})

    return f


@pytest.mark.parametrize("fault", [unchanged_state, half_batch])
def test_broken_step_is_not_correct(tiny_root, fault):
    res = run.run_cell(tiny_root(), "tiny.t", 2**31 + 9, 0.2, False, require_tpu=False,
                       hw=tiny_hw("gelu_tanh"), step_wrapper=fault)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("mlp", ["gelu_tanh", "swiglu"])
def test_control_is_not_correct(tiny_root, mlp):
    """The control: the reference with bfloat16 master, m and v, in the
    program's place, against the float32 reference."""
    cell = run.load_cell(tiny_root(mlp, param_dtype="bfloat16"), "tiny.t")
    b, s, v = cell.traffic["global_batch"], cell.traffic["seq_len"], cell.cfg["vocab_size"]
    for seed in (11, 2**31 + 11, 2**40 + 11):
        batches = [make_batch(seed, i, b, s, v) for i in range(run.CHECKED_STEPS)]
        want = Reference(cell.cfg, cell.traffic["optimizer"]).run(seed, batches)
        got = Reference(cell.cfg, cell.traffic["optimizer"], opt_dtype=jnp.bfloat16).run(
            seed, batches)
        assert not check.verdict(check.gaps(got, want), cell.limits)
