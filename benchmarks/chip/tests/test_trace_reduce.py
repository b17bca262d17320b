"""The trace reduction, on a small trace recorded on a TPU v5e
(``record_trace.py``: three steps that each bring a 64 MiB host-placed
array to the device, multiply it, and send the result back) and on HLO
instruction texts as a TPU trace names them."""
from __future__ import annotations

from pathlib import Path

import pytest

import trace_reduce as tr

TRACE = Path(__file__).parent / "data" / "offload_step.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_file(str(TRACE))


def test_window_busy_and_idle(reduced):
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["idle_share"] == pytest.approx(1 - reduced["busy_s"] / reduced["window_s"])
    # the host dispatches three small steps: the device waits on it most of the time
    assert reduced["idle_share"] > 0.5


def test_offload_copies_are_host_transfer(reduced):
    cls = reduced["class_s"]
    assert set(cls) == {"compute", "host_transfer"}
    # three 64 MiB host->device copies take longer than three 4096^3 matmuls
    assert cls["host_transfer"] > cls["compute"] > 0
    # self times never exceed the busy union
    assert sum(cls.values()) == pytest.approx(reduced["busy_s"], rel=1e-6)
    names = dict(reduced["breakdown"]["device_ops"])
    assert names["copy-done host"] == pytest.approx(cls["host_transfer"], rel=1e-6)


def test_idle_gaps_named_by_host_span(reduced):
    gaps = reduced["breakdown"]["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert {n for n, _ in gaps} <= {"bench.dispatch", "bench.wait", "bench.batch", "none"}
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)


@pytest.mark.parametrize("text,cls", [
    ("%copy-done.15 = f32[18,2048,2048]{2,1,0:T(8,128)S(5)} copy-done((f32[18,2048,2048]"
     "{2,1,0:T(8,128)S(5)}, f32[18,2048,2048]{2,1,0:T(8,128)}, u32[]{:S(2)}) %copy-start.15)",
     "host_transfer"),
    ("%copy-start.197 = (s32[8,1024]{1,0:T(8,128)S(1)}, s32[8,1024]{1,0:T(8,128)}, u32[]{:S(2)})"
     " copy-start(s32[8,1024]{1,0:T(8,128)} %param.2)", "compute"),
    ("%all-gather-start.3 = (bf16[2048,512]{1,0}, bf16[2048,2048]{1,0}) all-gather-start("
     "bf16[2048,512]{1,0} %param.7), replica_groups={{0,1,2,3}}, dimensions={1}", "collective"),
    ("%reduce-scatter.1 = f32[512,2048]{1,0} reduce-scatter(f32[2048,2048]{1,0} %fusion.9),"
     " dimensions={0}, to_apply=%add", "collective"),
    ("%async-start.2 = ((f32[64]{0}), f32[256]{0}, u32[]{:S(2)}) async-start(f32[64]{0}"
     " %all-gather-done.4), calls=%all-gather.5", "collective"),
    ("%slice-start = ((bf16[8192,2048]{1,0:T(8,128)(2,1)}), bf16[2048,2048]{1,0:T(8,128)(2,1)S(1)},"
     " s32[]{:S(2)}) async-start(bf16[8192,2048]{1,0:T(8,128)(2,1)} %dynamic-slice_bitcast_fusion.61),"
     " calls=%async_computation", "compute"),
    ("%fusion.456 = bf16[1024,2048]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[50257,2048]{1,0} "
     "%all-gather-done.5, s32[1024]{0} %broadcast_clamp_fusion.2), kind=kCustom", "compute"),
])
def test_op_class(text, cls):
    assert tr.op_class(text) == cls


def test_self_times_of_nested_events():
    # a while op spanning two body ops and a gap: its self time is the gap
    events = [(0, 100, "while"), (10, 40, "a"), (50, 90, "b"), (120, 130, "c")]
    got = {t: st for _, st, t in tr.self_times(events)}
    assert got == {"while": 30, "a": 30, "b": 40, "c": 10}


def test_union_and_subtract():
    u = tr.union([(5, 8), (0, 3), (2, 4), (10, 12)])
    assert u == [(0, 4), (5, 8), (10, 12)]
    assert tr.subtract([(0, 12)], u) == [(4, 5), (8, 10)]
