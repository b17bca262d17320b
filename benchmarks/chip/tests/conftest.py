"""Benchmark tests run on the CPU: put the benchmark and the program on the
path, and give them tiny cells built the way a later change adds a cell,
by files alone."""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parents[1]
sys.path[:0] = [str(BENCH), str(REPO / "src")]

TINY = {"name": "tiny", "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 128, "vocab_size": 256}
TINY_JOB = {"seq_len": 32, "global_batch": 4,
            "mesh": {"shape": [1, 1], "axes": ["data", "model"]},
            "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
                          "weight_decay": 0.0, "grad_clip": 1.0}}
# planning capacities at which the planner offloads chunks to the host with
# no lossy activation or gradient compression (tiny models, jax 0.9 planner)
HOST_CHUNK_HBM = {"gelu_tanh": 5e6, "swiglu": 6e6}


def tiny_hw(mlp: str):
    from repro.core.hardware import TPU_V5E

    return dataclasses.replace(TPU_V5E, hbm_bytes=HOST_CHUNK_HBM[mlp])


@pytest.fixture
def tiny_root(tmp_path):
    """``make(mlp, param_dtype, limits_of)``: a checkout-like root holding
    the benchmark's data files plus a tiny cell ``tiny.t`` (a GELU decoder
    with tied embeddings, or a SwiGLU one with an untied head) whose limits
    are those of the cell ``limits_of``."""

    def make(mlp="gelu_tanh", param_dtype="float32", limits_of="gpt2-1b.s1024-b8"):
        bench = tmp_path / "benchmarks" / "chip"
        for d in ("configs", "traffic", "limits", "metrics"):
            shutil.copytree(BENCH / d, bench / d, ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
        cfg = json.loads((BENCH / "configs" / "gpt2-1b.json").read_text())
        cfg.update(TINY, param_dtype=param_dtype, mlp=mlp,
                   tie_word_embeddings=mlp == "gelu_tanh")
        (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
        (bench / "traffic" / "t.json").write_text(json.dumps(TINY_JOB))
        shutil.copy(bench / "limits" / f"{limits_of}.json", bench / "limits" / "tiny.t.json")
        spec = json.loads((REPO / "BENCHMARK.json").read_text())
        spec["workloads"].append({"name": "tiny.t", "config": "tiny", "traffic": "t",
                                  "chips": 1, "why": "tiny CPU cell"})
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
        return tmp_path

    return make
