"""The split of the window's compute by phase and of the host link by
direction, on two traces recorded on a TPU v5e: ``offload_step`` (three
64 MiB host round trips around a matmul, no scopes; ``record_trace.py``) and
``scoped_step`` (a step with ``model`` and ``optimizer`` scopes whose Adam
state makes the host round trip; ``record_scoped_trace.py``, with the
step's compiled HLO text beside it)."""
from __future__ import annotations

import re
import shutil
import types
from pathlib import Path

import pytest

import trace_reduce as tr
import trace_split as ts

DATA = Path(__file__).parent / "data"
PLAIN = DATA / "offload_step.xplane.pb"
SCOPED = DATA / "scoped_step.xplane.pb"
MIB64 = 64 * 2 ** 20


@pytest.fixture(scope="module")
def plain():
    return ts.split_file(str(PLAIN))


@pytest.fixture(scope="module")
def scoped():
    return ts.split_file(str(SCOPED))


def test_link_rate_per_direction(plain):
    # each step fetches one 64 MiB array (4.74 ms in flight) and writes one back (4.55 ms)
    fetch, wb = plain["link"]["fetch"], plain["link"]["writeback"]
    assert fetch["copies"] == wb["copies"] == 3
    assert fetch["bytes"] == wb["bytes"] == 3 * MIB64
    assert fetch["bytes"] / fetch["inflight_s"] / 1e9 == pytest.approx(14.16, rel=2e-3)
    assert wb["bytes"] / wb["inflight_s"] / 1e9 == pytest.approx(14.75, rel=2e-3)
    # the core waits the whole flight of each copy, and the two never overlap
    assert fetch["exposed_s"] == pytest.approx(fetch["inflight_s"], rel=1e-3)
    assert plain["both_inflight_s"] == 0.0


def test_exposed_waits_sum_to_host_transfer_class(plain):
    red = tr.reduce_file(str(PLAIN))
    link = plain["link"]
    assert link["fetch"]["exposed_s"] + link["writeback"]["exposed_s"] == pytest.approx(
        red["class_s"]["host_transfer"], rel=1e-9)


def test_unscoped_program_has_no_phases(plain):
    assert plain["unresolved"] == []
    assert plain["phase_ops"]["forward"] == plain["phase_ops"]["backward"] == 0
    assert plain["phase_ops"]["optimizer"] == 0


def test_phases_add_up_to_compute(scoped):
    red = tr.reduce_file(str(SCOPED))
    assert scoped["unresolved"] == []
    assert sum(scoped["phase_s"].values()) == pytest.approx(red["class_s"]["compute"], rel=1e-6)
    for p in ("forward", "backward", "optimizer"):
        assert scoped["phase_s"][p] > 0, p
    assert scoped["phase_ops"]["accumulate"] == 0  # the recorded step has no microbatch loop
    assert scoped["window_s"] == pytest.approx(red["window_s"], rel=1e-12)


def test_scoped_step_round_trips_adam_state(scoped):
    # fp32 master, m and v of one 4096 x 4096 leaf, three steps
    for d in ts.DIRECTIONS:
        assert scoped["link"][d]["bytes"] == 3 * 3 * MIB64, d
        assert scoped["link"][d]["inflight_s"] > 0


def test_op_names_from_trace_match_hlo_text():
    """The HLO the profiler stored with the trace names each instruction's
    op_name as the compiled text does."""
    names = ts.hlo_op_names(SCOPED.read_bytes())
    assert len(names) == 1
    ops, = names.values()
    text = (DATA / "scoped_step.hlo.txt").read_text()
    want = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = ", line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            want[m.group(1)] = op.group(1) if op else ""
    assert want and ops == want


def _ctx_for(trace: Path, tmp_path: Path, n_steps: int):
    """A checkout-like root whose newest traced run wrote ``trace``, with
    the metric readers beside it; and the harness context of that run."""
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(Path(ts.__file__).parent / "metrics", bench / "metrics")
    (tmp_path / ".bench_trace" / "cell").mkdir(parents=True)
    shutil.copy(trace, tmp_path / ".bench_trace" / "cell" / "t.xplane.pb")
    red = tr.reduce_file(str(trace))
    return bench, types.SimpleNamespace(trace=red, n_steps=n_steps)


def test_readers_on_a_scoped_run(tmp_path):
    from run import metric_reader

    bench, ctx = _ctx_for(SCOPED, tmp_path, 3)
    split = ts.split_file(str(SCOPED))
    for name, phase in (("step.forward_ms", "forward"), ("step.backward_ms", "backward"),
                        ("step.optimizer_ms", "optimizer")):
        assert metric_reader(bench, name)(ctx) == pytest.approx(
            1e3 * split["phase_s"][phase] / 3)
    gbps = metric_reader(bench, "offload.fetch_gbps")(ctx)
    assert 1 < gbps < 100


def test_readers_without_scopes_or_their_trace(tmp_path):
    """An unscoped program reads no phase; a trace whose window is not the
    harness's reads nothing at all."""
    from run import metric_reader

    bench, ctx = _ctx_for(PLAIN, tmp_path, 3)
    assert metric_reader(bench, "step.forward_ms")(ctx) is None
    assert metric_reader(bench, "offload.writeback_gbps")(ctx) == pytest.approx(14.75, rel=2e-3)
    ctx.trace = dict(ctx.trace, window_s=ctx.trace["window_s"] + 1.0)
    for name in ("offload.fetch_gbps", "step.optimizer_ms"):
        assert metric_reader(bench, name)(ctx) is None


@pytest.mark.parametrize("op_name,phase", [
    ("jit(step_fn)/jvp(model)/while/body/dot_general", "forward"),
    ("jit(step_fn)/transpose(jvp(model))/while/body/dot_general", "backward"),
    ("jit(step_fn)/optimizer/sub", "optimizer"),
    ("jit(step_fn)/accumulate/while/body/closed_call/accumulate/jvp(model)/mul", "forward"),
    ("jit(step_fn)/accumulate/while/body/closed_call/add", "accumulate"),
    ("jit(step_fn)/while/body/closed_call", "unscoped"),
    ("jit(step_fn)/jvp(model_other)/mul", "unscoped"),
    ("", "unscoped"),
])
def test_phase(op_name, phase):
    assert ts.phase(op_name) == phase


@pytest.mark.parametrize("text,want", [
    ("%copy-start = (f32[4096,4096]{1,0:T(8,128)}, f32[4096,4096]{1,0:T(8,128)S(5)}, u32[]{:S(2)})"
     " copy-start(f32[4096,4096]{1,0:T(8,128)S(5)} %x.1)", ("fetch", MIB64)),
    ("%copy-done.15 = f32[18,2048,2048]{2,1,0:T(8,128)S(5)} copy-done((f32[18,2048,2048]"
     "{2,1,0:T(8,128)S(5)}, f32[18,2048,2048]{2,1,0:T(8,128)}, u32[]{:S(2)}) %copy-start.15)",
     ("writeback", 18 * 2048 * 2048 * 4)),
    ("%copy-start.2 = (bf16[4096,4096]{1,0:T(8,128)(2,1)S(1)}, bf16[4096,4096]{1,0:T(8,128)(2,1)},"
     " u32[]{:S(2)}) copy-start(bf16[4096,4096]{1,0:T(8,128)(2,1)} %w.1)", None),
    ("%fusion.3 = bf16[1024,2048]{1,0:T(8,128)(2,1)S(5)} fusion(bf16[1024,2048]{1,0} %p), "
     "kind=kLoop, calls=%fused_computation", None),
])
def test_host_copy(text, want):
    assert ts.host_copy(text) == want
