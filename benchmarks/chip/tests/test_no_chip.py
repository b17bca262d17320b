"""Without a TPU, or without the program beside it, the benchmark exits
non-zero and prints no result line."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

import run
from conftest import BENCH, REPO

ARGS = ["--workload", "gpt2-1b.s1024-b8", "--seed", "3000000001", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_refuses_the_cpu():
    p = _run(REPO)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def test_device_check_wants_enough_tpus():
    cell = run.load_cell(REPO, "gpt2-1b.s1024-b8")
    with pytest.raises(run.NoResult, match="no TPU"):
        run.check_devices(cell, require_tpu=True)
    cell.entry = dict(cell.entry, chips=4)
    with pytest.raises(run.NoResult, match="needs 4 chips"):
        run.check_devices(cell, require_tpu=False)
