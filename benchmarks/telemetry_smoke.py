"""Telemetry end-to-end smoke: train + serve under one registry, with gates.

Runs the two runtime paths the obs subsystem instruments — a short real
training run (the 8-layer toy, 20 jitted steps through ``train_loop`` with a
``DriftMonitor``) and a small paged serving load (``DecodeEngine`` under
chunked admission) — with one ``Telemetry`` handle installed, then writes

  * ``drift_report.json``     — the online measured-vs-modeled report;
  * ``trace.json``            — Chrome-trace/Perfetto export of every span;
  * ``telemetry_metrics.json``— the registry snapshot.

and gates (exit 1 on failure):

  * the drift report parses and both drift ratios sit inside the same
    [1/T, T] band ``estimator_fidelity --fail-threshold`` enforces
    (default 3.0);
  * ``trace.json`` is valid Chrome trace-event JSON (a ``traceEvents``
    list whose "X" events carry numeric ``ts``/``dur``) and non-trivial;
  * every metric documented in ``obs.metrics.DOCUMENTED_METRICS`` (the
    table in docs/observability.md) exists in the registry — a new metric
    that skips the docs, or a doc row that rotted, goes red here.

    PYTHONPATH=src python benchmarks/telemetry_smoke.py --out-dir reports
"""
import argparse
import json
import os
import sys

import jax

from repro import obs
from repro.configs import ARCHS, reduced
from repro.configs.base import ShapeConfig
from repro.core import build_workload
from repro.core.hardware import LOCAL_CPU_HW, MeshSpec
from repro.core.plan import MemoryPlan
from repro.data.pipeline import SyntheticTokenPipeline
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.models import kvcache as KV
from repro.serve import DecodeEngine, Request, choose_paging
from repro.train import step_builder as SB
from repro.train.loop import LoopConfig, train_loop

# the 8-layer toy: small enough for ~1 s CPU steps, big enough that the cost
# model's CPU pricing and the live-array watermark both land well inside the
# acceptance band (measured margins: runtime ~0.9x, memory ~1.1x)
TOY = dict(num_layers=8, d_model=256, d_ff=1024, vocab_size=2048,
           num_heads=4, num_kv_heads=4, head_dim=64)


def train_phase(tel: obs.Telemetry, steps: int, band: float) -> obs.DriftMonitor:
    cfg = reduced(ARCHS["llama3-405b"], **TOY)
    shape = ShapeConfig("tel_smoke", 128, 4, "train")
    mesh = make_local_mesh()
    w = build_workload(cfg, shape, MeshSpec((1, 1), ("data", "model")),
                       LOCAL_CPU_HW)
    plan = MemoryPlan(w.n_chunks, w.n_blocks, n_persist=w.n_chunks)
    mon = obs.DriftMonitor(w, plan, band=band, registry=tel.registry)
    with obs.use_telemetry(tel):  # build records the sync wire inventory
        art = SB.build_train_step(cfg, plan, mesh, shape)
    pipe = SyntheticTokenPipeline(cfg, shape, seed=0)
    train_loop(art, pipe, None,
               LoopConfig(total_steps=steps, checkpoint_every=1 << 30,
                          log_every=max(1, steps // 2)),
               log=tel.log, telemetry=tel, drift=mon)
    return mon


def serve_phase(tel: obs.Telemetry) -> None:
    cfg = reduced(ARCHS["llama3-405b"], **TOY)
    shape = ShapeConfig("tel_smoke_serve", 64, 2, "decode")
    mesh = make_local_mesh()
    s_kv = KV.cache_len(cfg, shape.seq_len)
    paging = choose_paging(s_kv, 8, 2)
    plan = MemoryPlan(3, 2, n_persist=3, n_host=paging.n_cold)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = DecodeEngine(cfg, plan, mesh, shape, params, paging=paging,
                          admission="chunked", telemetry=tel)
    engine.warmup()
    reqs = [Request(rid, [1 + rid] * (5 + 3 * rid), 6) for rid in range(4)]
    engine.run(reqs, max_steps=500)


def check_chrome_trace(doc: dict) -> list[str]:
    bad = []
    evs = doc.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        return ["traceEvents missing or empty"]
    if not any(e.get("ph") == "X" for e in evs):
        bad.append("no complete ('X') span events")
    for e in evs:
        if not isinstance(e.get("name"), str) or "ph" not in e:
            bad.append(f"malformed event: {e}")
            break
        if e["ph"] == "X" and not (
                isinstance(e.get("ts"), (int, float))
                and isinstance(e.get("dur"), (int, float))):
            bad.append(f"X event without numeric ts/dur: {e}")
            break
    return bad


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default="reports")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--band", type=float, default=3.0,
                    help="drift acceptance band [1/T, T] (matches "
                         "estimator_fidelity --fail-threshold)")
    args = ap.parse_args()
    os.makedirs(args.out_dir, exist_ok=True)

    tel = obs.Telemetry(
        logger=obs.StructuredLogger(
            "telemetry_smoke",
            jsonl_path=os.path.join(args.out_dir, "telemetry_log.jsonl")))
    mon = train_phase(tel, args.steps, args.band)
    serve_phase(tel)

    drift_path = mon.write(os.path.join(args.out_dir, "drift_report.json"))
    trace_path = tel.tracer.write_chrome_trace(
        os.path.join(args.out_dir, "trace.json"), process_name="telemetry_smoke")
    snap_path = os.path.join(args.out_dir, "telemetry_metrics.json")
    with open(snap_path, "w") as f:
        json.dump(tel.registry.snapshot(), f, indent=2)
        f.write("\n")

    failures = []
    with open(drift_path) as f:
        drift = json.load(f)
    for dim in ("runtime", "memory"):
        ratio = drift[dim]["ratio"]
        if not drift[dim]["in_band"]:
            failures.append(f"{dim} drift ratio {ratio} outside "
                            f"[1/{args.band}, {args.band}]")
        else:
            print(f"[telemetry_smoke] {dim} drift ratio "
                  f"{ratio:.3f} in band (band={args.band})")
    with open(trace_path) as f:
        failures += check_chrome_trace(json.load(f))
    missing = sorted(set(obs.DOCUMENTED_METRICS) - tel.registry.names())
    if missing:
        failures.append(f"documented metrics never registered: {missing}")
    else:
        print(f"[telemetry_smoke] all {len(obs.DOCUMENTED_METRICS)} "
              "documented metrics present")
    print(f"[telemetry_smoke] wrote {drift_path}, {trace_path}, {snap_path} "
          f"({len(tel.tracer.events)} trace events)")
    if failures:
        for msg in failures:
            print(f"[telemetry_smoke] FAIL: {msg}", file=sys.stderr)
        return 1
    print("[telemetry_smoke] smoke OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
