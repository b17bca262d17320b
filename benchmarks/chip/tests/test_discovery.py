"""The harness is driven by data: every cell, configuration, job, limit and
per-layer metric is found by its name in BENCHMARK.json, and new ones are
added by adding files."""
from __future__ import annotations

import json
import re
import types

import pytest

import run
from conftest import BENCH, REPO

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files(cell):
    c = run.load_cell(REPO, cell)
    assert c.cfg["name"] == c.entry["config"]
    assert set(c.limits) == {"loss_gap", "grad_norm_gap", "update_norm_gap"}
    assert math_prod(c.traffic["mesh"]["shape"]) == c.entry["chips"]
    assert c.traffic["global_batch"] % c.entry["chips"] == 0
    # every per-layer metric listed for the cell moves an end-to-end metric
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert c.per_layer and all(m["moves"] in e2e for m in c.per_layer)


def math_prod(xs):
    out = 1
    for x in xs:
        out *= x
    return out


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_every_config_file_is_its_own(cfg):
    data = json.loads((REPO / cfg["file"]).read_text())
    assert data["name"] == cfg["name"] and data["source"] == cfg["source"]
    assert data["reduced"] == cfg["reduced"]
    assert cfg["name"] in {w["config"] for w in SPEC["workloads"]}


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    read = run.metric_reader(BENCH, metric["name"])
    assert callable(read)


def test_names_and_files_keep_to_the_contract():
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(set(names)) == len(names)
        assert all(name.match(n) for n in names)
    for path in BENCH.rglob("*"):
        if "__pycache__" not in path.parts and path.is_file():
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", str(path.relative_to(REPO))), path


def test_added_files_are_picked_up(tiny_root):
    """A configuration, a job, a cell and a per-layer metric dropped into
    their directories (and named in BENCHMARK.json) are found without any
    change to the harness."""
    root = tiny_root()
    bench = root / "benchmarks" / "chip"
    (bench / "metrics" / "tiny.steps.py").write_text(
        "def read(ctx):\n    return ctx.n_steps\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({"name": "tiny.steps", "unit": "count", "better": "higher",
                              "source": "host_clock", "layer": "step",
                              "moves": "tokens_per_s", "workloads": ["tiny.t"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = run.load_cell(root, "tiny.t")
    assert cell.cfg["hidden_size"] == 64 and cell.traffic["seq_len"] == 32
    # the cell gets the metrics that list it, and no others
    assert [m["name"] for m in cell.per_layer] == ["tiny.steps"]
    read = run.metric_reader(cell.bench, "tiny.steps")
    assert read(types.SimpleNamespace(n_steps=7)) == 7


def test_unknown_cell_gives_no_result():
    with pytest.raises(run.NoResult):
        run.load_cell(REPO, "no-such.cell")
