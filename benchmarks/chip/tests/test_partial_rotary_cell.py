"""A StableLM-shaped cell through the program's normal path on the CPU: the
tiny cell rewritten to partial rotary (a quarter of each head), LayerNorm
eps 1e-5, SwiGLU and an untied head, under a plan whose bf16 parameters
live in host memory; the program against the plain reference."""
from __future__ import annotations

import dataclasses
import json

import pytest

import run
from conftest import tiny_hw


def _stablelm_shaped(root):
    path = root / "benchmarks" / "chip" / "configs" / "tiny.json"
    cfg = json.loads(path.read_text())
    cfg.update(positions="rope_partial", rotary_fraction=0.25, norm_eps=1e-05,
               departures=[])
    path.write_text(json.dumps(cfg))
    return root


@pytest.fixture
def host_params_plan(monkeypatch):
    """The planner's search, with its plan replaced by one that keeps every
    chunk in host memory, parameters included, over two microbatches, the
    first two layers' activations swapped: at the tiny size the search buys
    memory with lossy activation compression first, which the float32
    comparison would read as a fault."""
    from repro.core.plan import MemoryPlan
    from repro.launch import train

    search = train.search

    def fixed(w, **kw):
        res = search(w, **kw)
        plan = MemoryPlan(w.n_chunks, w.n_blocks, n_host=w.n_chunks, n_swap=2,
                          microbatch=2, host_params=True)
        return dataclasses.replace(res, plan=plan)

    monkeypatch.setattr(train, "search", fixed)


def test_program_matches_reference_with_partial_rotary(tiny_root, host_params_plan, capsys):
    # float32 parameters: the program and the reference do the same
    # arithmetic, so every gap is float32 round-off (test_reference.py's
    # tolerances)
    root = _stablelm_shaped(tiny_root("swiglu"))
    res = run.run_cell(root, "tiny.t", 2**31 + 17, 0.2, False, require_tpu=False,
                       hw=tiny_hw("swiglu"))
    err = capsys.readouterr().err
    assert "persist=0/6 buffer=0 host=6 swap=2" in err, err
    assert res["correct"] and res["failed"] == 0
    gaps = {k: v["value"] for k, v in res["checks"].items()}
    assert gaps["loss_gap"] < 1e-5, gaps
    assert gaps["grad_norm_gap"] < 1e-4, gaps
    assert gaps["update_norm_gap"] < 1e-4, gaps
