"""The fetches of host-resident layer weights, read from a trace recorded on a
TPU v5e (``record_param_fetch_trace.py``): three steps of the program's
own train step for a small StableLM-shaped decoder whose every chunk,
bf16 parameters included, lives in host memory, over two microbatches,
two of its four layers swapping their activations to host memory."""
from __future__ import annotations

import math
import shutil
import types
from pathlib import Path

import pytest

import param_fetch
import trace_reduce as tr
from record_param_fetch_trace import CONFIG, PLAN, STEPS
from weights import layer_leaves

TRACE = Path(__file__).parent / "data" / "param_fetch_step.xplane.pb"
LAYERS = CONFIG["num_hidden_layers"]
# the rank-3 leaves of a layer, one layer of each fetched from the host
MATRICES = [leaf for leaf in layer_leaves(CONFIG) if len(leaf.shape) == 2]
PASSES = PLAN["microbatch"] * 2  # every microbatch's forward, and its backward again


@pytest.fixture(scope="module")
def fetched():
    return param_fetch.reduce_file(str(TRACE), CONFIG)


def test_every_layer_weight_fetched_forward_and_backward(fetched):
    per_pass = LAYERS * len(MATRICES)
    assert fetched["fetches"] == STEPS * PASSES * per_pass
    assert fetched["by_phase"] == {"forward": STEPS * PLAN["microbatch"] * per_pass,
                                   "backward": STEPS * PLAN["microbatch"] * per_pass}


def test_fetched_bytes_are_the_weights_bf16_bytes(fetched):
    # the swapped activations and the optimizer's fp32 states also cross the
    # link by per-layer slices: none of them is counted
    per_pass = LAYERS * sum(math.prod(leaf.shape) for leaf in MATRICES) * 2
    assert fetched["bytes"] == STEPS * PASSES * per_pass


def test_link_rate_and_wait(fetched):
    assert 0 < fetched["wait_s"] <= fetched["inflight_s"] < fetched["window_s"]
    assert 1 < fetched["bytes"] / fetched["inflight_s"] / 1e9 < 25  # the link: 13.70 GB/s


def test_weight_slices_are_one_layer_of_each_matrix():
    d, ff = CONFIG["hidden_size"], CONFIG["intermediate_size"]
    assert param_fetch.weight_slices(CONFIG) == {
        ("bf16", (1, d, d)), ("bf16", (1, d, ff)), ("bf16", (1, ff, d))}


def test_readers(tmp_path, fetched):
    """The metric readers on a checkout whose newest traced run wrote the
    trace; nothing where the window is not the harness's."""
    from run import metric_reader

    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(Path(param_fetch.__file__).parent / "metrics", bench / "metrics")
    (tmp_path / ".bench_trace" / "cell").mkdir(parents=True)
    shutil.copy(TRACE, tmp_path / ".bench_trace" / "cell" / "t.xplane.pb")
    ctx = types.SimpleNamespace(cfg=dict(CONFIG, departures=[], published={"num_hidden_layers": 32}), n_steps=STEPS,
                                trace=tr.reduce_file(str(TRACE)))
    assert metric_reader(bench, "param_fetch.wait_ms")(ctx) == pytest.approx(
        1e3 * fetched["wait_s"] / STEPS)
    assert metric_reader(bench, "param_fetch.gbps")(ctx) == pytest.approx(
        fetched["bytes"] / fetched["inflight_s"] / 1e9)
    ctx.trace = dict(ctx.trace, window_s=ctx.trace["window_s"] + 1.0)
    for name in ("param_fetch.wait_ms", "param_fetch.gbps"):
        assert metric_reader(bench, name)(ctx) is None


def test_readers_without_weight_fetches(tmp_path):
    """A trace of a step whose weights stay in HBM (``scoped_step``) reads
    nothing."""
    from run import metric_reader

    scoped = Path(__file__).parent / "data" / "scoped_step.xplane.pb"
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(Path(param_fetch.__file__).parent / "metrics", bench / "metrics")
    (tmp_path / ".bench_trace" / "cell").mkdir(parents=True)
    shutil.copy(scoped, tmp_path / ".bench_trace" / "cell" / "t.xplane.pb")
    ctx = types.SimpleNamespace(cfg=CONFIG, n_steps=3, trace=tr.reduce_file(str(scoped)))
    assert metric_reader(bench, "param_fetch.wait_ms")(ctx) is None
    assert metric_reader(bench, "param_fetch.gbps")(ctx) is None
