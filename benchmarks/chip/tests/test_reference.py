"""The plain reference against the program's own train step, on the CPU,
at a tiny size, under a plan that offloads chunks to host memory."""
from __future__ import annotations

import pytest

import run
from conftest import tiny_hw


@pytest.mark.parametrize("mlp", ["gelu_tanh", "swiglu"])
def test_program_matches_reference_in_float32(tiny_root, mlp, capsys):
    # float32 parameters: the program and the reference do the same
    # arithmetic, so every gap is float32 round-off
    res = run.run_cell(tiny_root(mlp), "tiny.t", 2**31 + 5, 0.2, False,
                       require_tpu=False, hw=tiny_hw(mlp))
    err = capsys.readouterr().err
    assert "host=0 " not in err and "acts=" not in err and "int8" not in err, err
    assert res["correct"] and res["failed"] == 0
    gaps = {k: v["value"] for k, v in res["checks"].items()}
    assert gaps["loss_gap"] < 1e-5, gaps
    assert gaps["grad_norm_gap"] < 1e-4, gaps
    assert gaps["update_norm_gap"] < 1e-4, gaps
