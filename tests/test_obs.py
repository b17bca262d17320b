"""Telemetry subsystem tests (repro.obs): registry/tracer/logger units, the
Chrome-trace export schema, the two load-bearing system properties —
telemetry changes no jitted program (HLO identity) and costs <5% of a toy
step when enabled — and the end-to-end smoke (20-step drift report in band,
every documented metric live, docs table in sync)."""
import importlib.util
import json
import math
import pathlib
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import ARCHS, reduced
from repro.configs.base import ShapeConfig
from repro.core import build_workload
from repro.core.hardware import LOCAL_CPU_HW, MeshSpec
from repro.core.plan import MemoryPlan
from repro.launch.mesh import make_local_mesh
from repro.obs.metrics import DOCUMENTED_METRICS, MetricsRegistry, quantile
from repro.obs.trace import Tracer
from repro.train import step_builder as SB

REPO = pathlib.Path(__file__).parent.parent


def _load_bench(name: str):
    spec = importlib.util.spec_from_file_location(
        name, REPO / "benchmarks" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# quantile: the shared nearest-rank estimator (engine percentiles use it too)
# ---------------------------------------------------------------------------
def test_quantile_empty_is_zero():
    assert quantile([], 0.5) == 0.0
    assert quantile([], 0.99) == 0.0


def test_quantile_single_sample_every_q():
    """1-sample edge case: every quantile IS the sample (p50 == p99)."""
    for q in (0.0, 0.01, 0.5, 0.99, 1.0):
        assert quantile([7.25], q) == 7.25


def test_quantile_nearest_rank():
    xs = [1.0, 2.0, 3.0, 4.0]
    assert quantile(xs, 0.5) == 2.0
    assert quantile(xs, 0.99) == 4.0
    assert quantile(xs, 0.25) == 1.0


def test_engine_report_percentiles_share_quantile():
    """EngineReport's percentile properties go through the same estimator
    (satellite fix: 0-/1-sample behavior is consistent everywhere)."""
    from repro.serve.engine import EngineReport

    rep = EngineReport(steps=0, generated_tokens=0, finished={}, rejected={},
                       evictions=0, wall_s=0.0, hbm_cache_bytes=0,
                       host_cache_bytes=0, resident_cache_bytes=0)
    assert rep.p50_latency_s == 0.0 and rep.p99_latency_s == 0.0
    rep.request_latency_s[1] = 0.5
    rep.ttft_s[1] = 0.125
    assert rep.p50_latency_s == rep.p99_latency_s == 0.5
    assert rep.p50_ttft_s == rep.p99_ttft_s == 0.125


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("x.count")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("x.gauge")
    g.set(2.0)
    g.set_max(1.0)  # lower: no change
    g.set_max(5.0)
    assert g.value == 5.0
    h = reg.histogram("x.hist")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.count == 4 and h.total == 10.0 and h.mean == 2.5
    assert h.q(0.5) == 2.0


def test_labeled_series_are_distinct_and_render():
    reg = MetricsRegistry()
    reg.counter("ticks", phase="prefill").inc(2)
    reg.counter("ticks", phase="decode").inc(5)
    reg.counter("ticks").inc(7)
    snap = reg.snapshot()
    assert snap["ticks{phase=prefill}"]["value"] == 2
    assert snap["ticks{phase=decode}"]["value"] == 5
    assert snap["ticks"]["value"] == 7
    assert reg.names() >= {"ticks"}


def test_same_handle_for_same_name_labels():
    reg = MetricsRegistry()
    assert reg.counter("a", k="v") is reg.counter("a", k="v")
    assert reg.counter("a", k="v") is not reg.counter("a", k="w")


def test_null_registry_is_inert():
    from repro.obs.metrics import NULL_REGISTRY

    NULL_REGISTRY.counter("x").inc()
    NULL_REGISTRY.gauge("y").set(1.0)
    NULL_REGISTRY.histogram("z").observe(1.0)
    assert NULL_REGISTRY.snapshot() == {}


# ---------------------------------------------------------------------------
# tracer + Chrome trace export
# ---------------------------------------------------------------------------
def test_spans_nest_and_record():
    tr = Tracer()
    with tr.span("outer", step=1):
        with tr.span("inner"):
            pass
    names = [e["name"] for e in tr.events]
    assert names == ["inner", "outer"]  # inner exits (records) first
    ids = {e["name"]: e["id"] for e in tr.events}
    parent = {e["name"]: e["parent"] for e in tr.events}
    assert parent == {"outer": None, "inner": ids["outer"]}
    assert tr.events[1]["args"] == {"step": 1}


def test_span_parents_are_per_thread():
    """A span opened on another thread while ``outer`` is open on this one
    is a root there: parents come from the span's own thread."""
    tr = Tracer()
    with tr.span("outer"):
        t = threading.Thread(target=lambda: tr.span("other").__enter__().__exit__(
            None, None, None))
        t.start()
        t.join()
        with tr.span("inner"):
            pass
    by = {e["name"]: e for e in tr.events}
    assert by["other"]["parent"] is None
    assert by["inner"]["parent"] == by["outer"]["id"]
    assert len({e["id"] for e in tr.events}) == 3


def _profiled_host_events(tmp_path, body) -> list:
    """Run ``body`` under a CPU ``jax.profiler`` session; return the
    (name, stats) of every host-plane event in the written trace."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return [(ev.name, dict(ev.stats)) for plane in pd.planes
            if plane.name.startswith("/host:") for line in plane.lines
            for ev in line.events]


def test_span_lands_on_profiler_trace(tmp_path):
    """Spans open a TraceAnnotation of their own name even when the tracer
    retains nothing, so they appear in the profiler's trace."""
    tr = Tracer(enabled=False)

    def body():
        with tr.span("obs.outer"):
            with tr.span("obs.inner"):
                jnp.ones(3).block_until_ready()

    names = [n for n, _ in _profiled_host_events(tmp_path, body)]
    assert "obs.outer" in names and "obs.inner" in names
    assert tr.events == []


def test_step_span_is_a_step_annotation(tmp_path):
    tr = Tracer()

    def body():
        with tr.span("train.step", step=3):
            jnp.ones(3).block_until_ready()

    stats = [st for n, st in _profiled_host_events(tmp_path, body) if n == "train.step"]
    assert len(stats) == 1 and stats[0]["step_num"] == 3


def test_disabled_tracer_still_measures():
    tr = Tracer(enabled=False)
    with tr.span("t") as sp:
        time.sleep(0.01)
    assert sp.dur_s >= 0.01
    assert tr.events == []


def test_tracer_thread_safety_and_thread_split():
    tr = Tracer()
    # hold all four threads alive together: thread idents are reused after
    # join, and the tid split below needs four distinct ones
    barrier = threading.Barrier(4)

    def work():
        barrier.wait()
        for _ in range(50):
            with tr.span("w"):
                pass
        barrier.wait()

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.events) == 200
    doc = tr.to_chrome_trace()
    tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert len(tids) == 4


def _assert_valid_chrome_trace(doc: dict):
    """The schema contract Perfetto/chrome://tracing require: a JSON object
    with a traceEvents list; every event has a string name and a phase; "X"
    (complete) events carry numeric microsecond ts + dur."""
    assert isinstance(doc, dict) and isinstance(doc["traceEvents"], list)
    assert doc["traceEvents"], "empty trace"
    phases = set()
    for e in doc["traceEvents"]:
        assert isinstance(e["name"], str) and isinstance(e["ph"], str)
        phases.add(e["ph"])
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    assert "X" in phases and "M" in phases  # spans + process/thread names


def test_chrome_trace_schema(tmp_path):
    tr = Tracer()
    with tr.span("step", step=0):
        with tr.span("fwd"):
            pass
    path = tr.write_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    _assert_valid_chrome_trace(doc)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"step", "fwd", "process_name"} <= names


# ---------------------------------------------------------------------------
# structured logger
# ---------------------------------------------------------------------------
def test_logger_keeps_human_line_and_records(tmp_path):
    seen = []
    jl = tmp_path / "log.jsonl"
    log = obs.StructuredLogger("loop", sink=seen.append, jsonl_path=str(jl))
    log.info("step", "[loop] step 3 loss=1.0000 (12 ms)", step=3, loss=1.0)
    assert seen == ["[loop] step 3 loss=1.0000 (12 ms)"]  # byte-identical
    rec = log.records[0]
    assert rec["event"] == "step" and rec["step"] == 3 and rec["loss"] == 1.0
    disk = json.loads(jl.read_text().splitlines()[0])
    assert disk["event"] == "step" and disk["level"] == "info"
    log.close()


def test_logger_legacy_callable_surface():
    """train_loop(log=my_list.append) still works: as_logger wraps plain
    callables, and a StructuredLogger is itself a Callable[[str], None]."""
    seen = []
    log = obs.as_logger(seen.append)
    log("[loop] resumed from checkpoint step 5")
    assert seen == ["[loop] resumed from checkpoint step 5"]
    assert log.records[0]["event"] == "log"
    assert obs.as_logger(log) is log  # passthrough, no double wrap


def test_logger_min_level_filters():
    seen = []
    log = obs.StructuredLogger("x", sink=seen.append, min_level="warning")
    log.info("quiet", "nope")
    log.warning("loud", "yep")
    assert seen == ["yep"] and len(log.records) == 1


# ---------------------------------------------------------------------------
# telemetry handle plumbing
# ---------------------------------------------------------------------------
def test_use_telemetry_scopes_default():
    assert obs.current_telemetry() is obs.NULL_TELEMETRY
    tel = obs.Telemetry()
    with obs.use_telemetry(tel):
        assert obs.current_telemetry() is tel
    assert obs.current_telemetry() is obs.NULL_TELEMETRY


def test_null_telemetry_is_fully_inert():
    tel = obs.NULL_TELEMETRY
    assert not tel.enabled
    with tel.tracer.span("x"):
        tel.registry.counter("c").inc()
    assert tel.tracer.events == [] and tel.registry.snapshot() == {}


# ---------------------------------------------------------------------------
# system property: telemetry never changes the jitted program
# ---------------------------------------------------------------------------
def _micro_train_setup():
    cfg = reduced(ARCHS["llama3-405b"], num_layers=2, d_model=64, d_ff=128,
                  vocab_size=256, num_heads=2, num_kv_heads=2, head_dim=32)
    shape = ShapeConfig("obs_hlo", 32, 2, "train")
    mesh = make_local_mesh()
    w = build_workload(cfg, shape, MeshSpec((1, 1), ("data", "model")),
                       LOCAL_CPU_HW)
    plan = MemoryPlan(w.n_chunks, w.n_blocks, n_persist=w.n_chunks)
    return cfg, plan, mesh, shape, w


def test_hlo_identical_with_and_without_telemetry():
    """All instrumentation is host-side: building (and lowering) the train
    step under an installed, fully-enabled telemetry handle produces the
    byte-identical program to building it with telemetry off."""
    cfg, plan, mesh, shape, _ = _micro_train_setup()
    text_off = SB.build_train_step(cfg, plan, mesh, shape).lower().as_text()
    with obs.use_telemetry(obs.Telemetry()):
        text_on = SB.build_train_step(cfg, plan, mesh, shape).lower().as_text()
    assert text_on == text_off


def test_sync_inventory_recorded_at_build():
    cfg, plan, mesh, shape, _ = _micro_train_setup()
    tel = obs.Telemetry(trace=False)
    with obs.use_telemetry(tel):
        SB.build_train_step(cfg, plan, mesh, shape)
    snap = tel.registry.snapshot()
    grad = snap["sync.wire_bytes_per_step{op=grad_sync,strategy=xla}"]
    assert grad["value"] > 0
    # fp32 payload under grad_compress="none"
    assert snap["sync.wire_payload{strategy=xla}"]["value"] == 4


def test_step_phases_named_in_compiled_metadata():
    """The step's phases carry their scopes into the compiled program's
    op_name metadata: forward under jvp(model), backward under
    transpose(jvp(model)), the microbatch loop under accumulate, the update
    under optimizer."""
    import re

    cfg, plan, mesh, shape, _ = _micro_train_setup()
    text = SB.build_train_step(cfg, plan, mesh, shape).lower().compile().as_text()
    comps = {c for n in re.findall(r'op_name="([^"]*)"', text) for c in n.split("/")}
    assert {"jvp(model)", "transpose(jvp(model))", "accumulate", "optimizer"} <= comps


def test_offload_inventory_is_host_placed_leaf_bytes():
    """A plan with every chunk on the host: each step fetches and writes
    back every host-placed state leaf once (one microbatch), counted per
    device."""
    cfg, _, mesh, shape, w = _micro_train_setup()
    plan = MemoryPlan(w.n_chunks, w.n_blocks, n_host=w.n_chunks)
    tel = obs.Telemetry(trace=False)
    with obs.use_telemetry(tel):
        art = SB.build_train_step(cfg, plan, mesh, shape)
    host = [s for s in jax.tree.leaves(art.state_specs)
            if s.sharding.memory_kind not in (None, "device")]
    assert host  # the fp32 optimizer state at least
    nbytes = sum(math.prod(s.sharding.shard_shape(s.shape)) * s.dtype.itemsize for s in host)
    snap = tel.registry.snapshot()
    assert snap["offload.bytes_per_step{dir=fetch}"]["value"] == nbytes
    assert snap["offload.bytes_per_step{dir=writeback}"]["value"] == nbytes


def test_offload_inventory_zero_without_host_chunks():
    cfg, plan, mesh, shape, _ = _micro_train_setup()
    tel = obs.Telemetry(trace=False)
    with obs.use_telemetry(tel):
        SB.build_train_step(cfg, plan, mesh, shape)
    snap = tel.registry.snapshot()
    assert snap["offload.bytes_per_step{dir=fetch}"]["value"] == 0
    assert snap["offload.bytes_per_step{dir=writeback}"]["value"] == 0
    assert snap["offload.streamed_bytes_per_step{dir=fetch}"]["value"] == 0
    assert snap["offload.streamed_bytes_per_step{dir=writeback}"]["value"] == 0


def test_streamed_offload_share_for_gpt2_1b():
    """gpt2-1b under its one-chip plan (every chunk's fp32 state on the host,
    bf16 params in HBM): the six stacked projection leaves stream, 0.898 of
    the host-link bytes each way; the embedding and the norms do not."""
    from repro.configs.paper_models import GPT2_1B

    shape = ShapeConfig("paper", 1024, 8, "train")
    plan = MemoryPlan(20, 18, n_host=20, microbatch=8, host_params=False)
    tel = obs.Telemetry(trace=False)
    with obs.use_telemetry(tel):
        SB.build_train_step(GPT2_1B, plan, make_local_mesh(), shape)
    snap = tel.registry.snapshot()
    for d in ("fetch", "writeback"):
        total = snap[f"offload.bytes_per_step{{dir={d}}}"]["value"]
        streamed = snap[f"offload.streamed_bytes_per_step{{dir={d}}}"]["value"]
        assert (total, streamed) == (12_108_570_624, 10_871_635_968)
        assert round(streamed / total, 3) == 0.898


# ---------------------------------------------------------------------------
# planner spans: fit_plan's tries on the tracer
# ---------------------------------------------------------------------------
class _FakeArtifacts:
    """Stands in for a StepArtifacts whose compile the chip's compiler
    refuses over HBM (``refuse``) or accepts."""

    def __init__(self, plan, refuse: bool):
        self.plan, self.refuse = plan, refuse

    def lower(self):
        time.sleep(0.01)
        return self

    def compile(self):
        time.sleep(0.02)
        if self.refuse:
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm. "
                "Used 17.6G of 15.75G hbm. Exceeded hbm capacity by 1.5G.")
        return "compiled"


def _fit_with_one_refusal():
    from repro.core.hardware import TPU_V5E
    from repro.launch.train import fit_plan

    cfg, _, mesh, shape, _ = _micro_train_setup()
    builds = []

    def build(plan):
        builds.append(plan)
        return _FakeArtifacts(plan, refuse=len(builds) == 1)

    tel = obs.Telemetry()
    with obs.use_telemetry(tel):
        fit = fit_plan(cfg, shape, mesh, TPU_V5E, build, log=lambda _: None)
    return fit, tel.tracer.events


def test_fit_plan_records_refused_and_accepted_attempts():
    fit, events = _fit_with_one_refusal()
    attempts = [e for e in events if e["name"] == "plan.attempt"]
    assert [a["args"]["accepted"] for a in attempts] == [False, True]
    refused, accepted = attempts
    assert refused["args"]["overshoot_bytes"] == 1.5 * 1024 ** 3
    assert accepted["args"]["overshoot_bytes"] == 0.0
    assert accepted["args"]["plan"] == fit.art.plan.describe()
    assert refused["args"]["modeled_peak_bytes"] == fit.misses[0][0]
    for a in attempts:
        kids = [e["name"] for e in events if e["parent"] == a["id"]]
        assert kids == ["plan.search", "plan.build", "plan.lower", "plan.compile"]


def test_fit_plan_compile_s_is_accepted_lower_plus_compile():
    fit, events = _fit_with_one_refusal()
    accepted = [e for e in events if e["name"] == "plan.attempt"][-1]
    kids = {e["name"]: e["dur_s"] for e in events if e["parent"] == accepted["id"]}
    assert fit.compile_s == kids["plan.lower"] + kids["plan.compile"]
    assert fit.compile_s >= 0.03


def test_compile_cache_keys_include_metadata(tmp_path, monkeypatch):
    """A cached executable must carry its own program's scopes: the cache
    key includes the HLO metadata (op names), which JAX leaves out by
    default."""
    from repro.launch.train import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    prev = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key", prev)


# ---------------------------------------------------------------------------
# system property: enabled-path overhead < 5% of a toy step
# ---------------------------------------------------------------------------
def test_enabled_overhead_under_5pct_of_toy_step():
    """The full per-step telemetry work (span + histogram + gauges +
    counters + device-memory watermark + drift observation) costs < 5% of
    one 8-layer-toy training step."""
    cfg = reduced(ARCHS["llama3-405b"], num_layers=8, d_model=128, d_ff=512,
                  vocab_size=1024, num_heads=4, num_kv_heads=4, head_dim=32)
    shape = ShapeConfig("obs_overhead", 64, 2, "train")
    mesh = make_local_mesh()
    w = build_workload(cfg, shape, MeshSpec((1, 1), ("data", "model")),
                       LOCAL_CPU_HW)
    plan = MemoryPlan(w.n_chunks, w.n_blocks, n_persist=w.n_chunks)
    art = SB.build_train_step(cfg, plan, mesh, shape)
    from repro.data.pipeline import SyntheticTokenPipeline

    pipe = SyntheticTokenPipeline(cfg, shape, seed=0)
    state = art.init(jax.random.PRNGKey(0))
    jfn = jax.jit(art.fn)
    batch = pipe.next_sync()
    jfn(state, batch)[1]["loss"].block_until_ready()  # compile
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        _, m = jfn(state, batch)
        m["loss"].block_until_ready()
        times.append(time.perf_counter() - t0)
    step_s = sorted(times)[1]  # median of 3

    tel = obs.Telemetry()
    mon = obs.DriftMonitor(w, plan, registry=tel.registry)
    reg, tracer = tel.registry, tel.tracer
    h = reg.histogram("train.step_time_s")
    g_loss = reg.gauge("train.loss")
    g_mem = reg.gauge("train.device_mem_watermark_bytes")
    c_steps = reg.counter("train.steps")
    n = 200
    t0 = time.perf_counter()
    for i in range(n):
        with tracer.span("train.step", step=i):
            pass
        h.observe(step_s)
        c_steps.inc()
        g_loss.set(1.0)
        mem, src = obs.device_memory_watermark()
        g_mem.set_max(mem)
        mon.observe_step(step_s, mem, mem_source=src)
    per_step_overhead = (time.perf_counter() - t0) / n
    assert per_step_overhead < 0.05 * step_s, (
        f"telemetry overhead {per_step_overhead * 1e6:.0f}us/step vs step "
        f"{step_s * 1e3:.1f}ms")


# ---------------------------------------------------------------------------
# end-to-end: drift report in band, trace loads, docs table in sync
# ---------------------------------------------------------------------------
def test_telemetry_smoke_end_to_end(tmp_path, monkeypatch):
    """The CI telemetry-smoke gate as a test: 20 real train steps + a paged
    serve load under one registry; drift ratios inside the 3.0 band; the
    exported trace.json is valid Chrome-trace JSON; every documented metric
    exists."""
    mod = _load_bench("telemetry_smoke")
    monkeypatch.setattr(sys, "argv",
                        ["telemetry_smoke", "--out-dir", str(tmp_path)])
    assert mod.main() == 0
    drift = json.loads((tmp_path / "drift_report.json").read_text())
    assert drift["kind"] == "drift_report" and drift["steps"] == 20
    assert drift["ok"]
    for dim in ("runtime", "memory"):
        assert drift[dim]["in_band"]
        assert 1 / 3.0 <= drift[dim]["ratio"] <= 3.0
    with open(tmp_path / "trace.json") as f:
        _assert_valid_chrome_trace(json.load(f))
    snap = json.loads((tmp_path / "telemetry_metrics.json").read_text())
    assert snap  # non-empty registry snapshot rides along


def test_documented_metrics_match_docs_table():
    """docs/observability.md's metric table and DOCUMENTED_METRICS move
    together: every name in the tuple appears in the doc, and every
    `name`-style metric row in the doc's table exists in the tuple."""
    doc = (REPO / "docs" / "observability.md").read_text()
    for name in DOCUMENTED_METRICS:
        assert f"`{name}`" in doc, f"{name} missing from docs/observability.md"
