"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes and record memory/cost/collective evidence.

A compile-only tool: it pins itself to 512 virtual CPU devices (appending to
``XLA_FLAGS``, overriding ``JAX_PLATFORMS``) before jax initializes, so it
runs the same on a machine that has a TPU.

    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b --shape train_4k

Writes one JSON line per cell to reports/dryrun_cells.jsonl (append; completed
cells are skipped on re-run, so a crashed sweep resumes).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

import argparse
import json
import traceback

import jax

from repro.configs import ARCHS, get_config, shapes_for, get_shape
from repro.core import TPU_V5E, build_workload, search
from repro.core.cost_model import serve_totals, step_totals
from repro.core.plan import MemoryPlan
from repro.core.serve_plan import serve_memory_estimate, serve_plan
from repro import obs
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh, mesh_spec
from repro.train.step_builder import build_decode_step, build_prefill_step, build_train_step

REPORT = os.path.join(os.path.dirname(__file__), "..", "..", "..", "reports")


def run_cell(arch: str, shape_name: str, multi_pod: bool, *, sp: str = "off",
             plan_override: MemoryPlan | None = None, hlo_out: str | None = None) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mspec = mesh_spec(multi_pod=multi_pod)
    hw = TPU_V5E
    rec: dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "mode": shape.mode, "sp": sp,
    }
    # one clock: the lower/compile timings come from obs spans (a disabled
    # tracer still measures dur_s), so an installed telemetry handle sees
    # the same regions the report records. The lower span brackets the
    # whole mode-specific build+lower branch, so it is entered manually.
    tracer = obs.current_telemetry().tracer
    lower_span = tracer.span("dryrun.lower", arch=arch, shape=shape_name)
    lower_span.__enter__()

    if shape.is_training:
        from repro.core import estimate_memory, estimate_runtime

        w = build_workload(cfg, shape, mspec, hw)
        if plan_override is not None:
            plan = plan_override
            w_eval = w
            if plan.dp_only:
                import dataclasses as _dc

                from repro.core.hardware import MeshSpec as _MS

                new = (_MS((mspec.axis_size("pod"), mspec.n_chips // mspec.axis_size("pod")),
                           ("pod", "data")) if "pod" in mspec.axes
                       else _MS((mspec.n_chips,), ("data",)))
                w_eval = _dc.replace(w, mesh=new)
            rt, mem = estimate_runtime(w_eval, plan), estimate_memory(w_eval, plan)
            w = w_eval
            rec["plan_feasible"] = mem.peak < hw.capacity_bytes()
        else:
            res = search(w, sp=sp)
            plan = res.plan
            rt, mem = res.runtime, res.memory
            rec["plan_feasible"] = res.feasible
        rec["plan"] = plan.describe() + (" dp" if plan.dp_only else "") + (
            " sp" if plan.seq_shard_acts else "")
        rec["modeled"] = {
            "t_iteration_s": rt.t_iteration,
            "tokens_per_s": rt.tokens_per_second,
            "peak_gb_per_chip": mem.peak / 1e9,
        }
        art = build_train_step(cfg, plan, mesh, shape)
        lowered = art.lower()
        flops_dev, bytes_dev = step_totals(w, plan)
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * cfg.active_param_count() * tokens / mspec.n_chips
    else:
        plan = plan_override or serve_plan(cfg, shape, mspec, hw)
        rec["plan"] = plan.describe()
        rec["modeled"] = serve_memory_estimate(cfg, shape, mspec, plan)
        w = None
        if shape.mode == "prefill":
            art = build_prefill_step(cfg, plan, mesh, shape)
            lowered = jax.jit(art.fn).lower(art.state_specs, art.batch_specs)
            w = build_workload(cfg, shape, mspec, hw)
            flops_dev, bytes_dev = serve_totals(w, plan)
            tokens = shape.global_batch * shape.seq_len
            model_flops = 2.0 * cfg.active_param_count() * tokens / mspec.n_chips
        else:
            art = build_decode_step(cfg, plan, mesh, shape)
            lowered = art.lower(donate=True)
            from repro.core.chunks import chunk_inventory
            from repro.core.serve_plan import cache_bytes_per_device

            b_loc = shape.global_batch / mspec.zero_degree
            flops_dev = 2.0 * cfg.active_param_count() * b_loc / mspec.tp_degree
            bytes_dev = (
                sum(c.param_bytes for c in chunk_inventory(cfg)) / mspec.tp_degree
                + cache_bytes_per_device(cfg, shape, mspec)
            )
            model_flops = 2.0 * cfg.active_param_count() * shape.global_batch / mspec.n_chips

    lower_span.__exit__(None, None, None)
    rec["lower_s"] = round(lower_span.dur_s, 1)
    with tracer.span("dryrun.compile", arch=arch, shape=shape_name) as csp:
        compiled = lowered.compile()
    rec["compile_s"] = round(csp.dur_s, 1)

    mem = compiled.memory_analysis()
    rec["xla_memory"] = {
        "argument_gb": mem.argument_size_in_bytes / 1e9,
        "output_gb": mem.output_size_in_bytes / 1e9,
        "temp_gb": mem.temp_size_in_bytes / 1e9,
        "host_gb": (mem.host_argument_size_in_bytes + mem.host_temp_size_in_bytes) / 1e9,
        "alias_gb": mem.alias_size_in_bytes / 1e9,
    }
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):  # older jax: one dict per computation
        ca = ca[0] if ca else {}
    hlo = compiled.as_text()
    if hlo_out:
        import zstandard

        with open(hlo_out, "wb") as f:
            f.write(zstandard.ZstdCompressor().compress(hlo.encode()))
    rep = RL.analyze(
        hlo=hlo,
        flops_per_chip=flops_dev,
        hbm_bytes_per_chip=bytes_dev,
        model_flops_per_chip=model_flops,
        hw=hw,
        xla_flops=float(ca.get("flops", 0.0)),
        xla_bytes=float(ca.get("bytes accessed", 0.0)),
    )
    rec["roofline"] = {
        "t_compute_s": rep.t_compute,
        "t_memory_s": rep.t_memory,
        "t_collective_s": rep.t_collective,
        "bottleneck": rep.bottleneck,
        "flops_per_chip": rep.flops_per_chip,
        "hbm_gb_per_chip": rep.hbm_bytes_per_chip / 1e9,
        "collective_gb_raw": rep.collective_bytes_raw / 1e9,
        "collective_gb_corrected": rep.collective_bytes_corrected / 1e9,
        "by_kind_gb": {k: v / 1e9 for k, v in rep.by_kind.items()},
        "model_flops_per_chip": rep.model_flops,
        "useful_flops_ratio": rep.useful_flops_ratio,
        "xla_flops_raw": rep.xla_flops_raw,
        "xla_bytes_raw": rep.xla_bytes_raw,
    }
    rec["ok"] = True
    return rec


def run_megatrain(arch: str, shape_name: str) -> dict:
    """MegaTrain demo (PAPERS.md): plan a 100B+ config with every chunk on
    the all-host optimizer tier — bf16 param/grad shards in HBM, fp32 Adam
    state + the update itself on host (autotuner.megatrain_plan) — then
    lower/compile it like any dryrun cell. Asserts the *planned* device
    footprint fits HardwareSpec.capacity_bytes() before spending the
    compile; the compiled record's host_gb shows the state tier landing in
    pinned host memory."""
    from repro.core import estimate_memory
    from repro.core.autotuner import megatrain_plan

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    assert shape.is_training, "--megatrain is a training-path demo"
    assert cfg.param_count() >= 100e9, (
        f"--megatrain demonstrates the 100B+ tier; {arch} is too small")
    mspec = mesh_spec(multi_pod=False)
    hw = TPU_V5E
    w = build_workload(cfg, shape, mspec, hw)
    plan = megatrain_plan(w)
    mem = estimate_memory(w, plan)
    assert mem.peak < hw.capacity_bytes(), (
        f"MegaTrain plan overflows the chip: planned {mem.peak / 1e9:.1f} GB "
        f">= capacity {hw.capacity_bytes() / 1e9:.1f} GB")
    rec = run_cell(arch, shape_name, False, plan_override=plan)
    rec["megatrain"] = {
        "planned_peak_gb": round(mem.peak / 1e9, 3),
        "capacity_gb": round(hw.capacity_bytes() / 1e9, 3),
        "model_states_gb": round(mem.model_states / 1e9, 3),
    }
    return rec


def cells(archs, shapes_filter=None):
    for arch in archs:
        cfg = get_config(arch)
        for shape in shapes_for(cfg):
            if shapes_filter and shape.name not in shapes_filter:
                continue
            yield arch, shape.name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--sp", default="off", choices=["off", "on", "auto"])
    ap.add_argument("--megatrain", action="store_true",
                    help="one-cell MegaTrain demo: all-host optimizer tier "
                         "on a 100B+ model (default llama3-405b x train_4k)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    out_dir = args.out or os.path.abspath(os.path.join(os.path.dirname(__file__), "../../../reports"))
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, "dryrun_cells.jsonl")

    if args.megatrain:
        rec = run_megatrain(args.arch or "llama3-405b", args.shape or "train_4k")
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        mt, xm = rec["megatrain"], rec["xla_memory"]
        print(f"[dryrun] MEGATRAIN OK {rec['arch']} x {rec['shape']}: "
              f"plan [{rec['plan']}] planned {mt['planned_peak_gb']}GB "
              f"< capacity {mt['capacity_gb']}GB; compiled temp "
              f"{xm['temp_gb']:.2f}GB host {xm['host_gb']:.2f}GB")
        return 0
    done = set()
    if os.path.exists(out_path):
        with open(out_path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("ok"):
                        done.add((r["arch"], r["shape"], r["mesh"], r.get("sp", "off")))
                except json.JSONDecodeError:
                    pass

    archs = [args.arch] if args.arch else sorted(ARCHS)
    shape_filter = {args.shape} if args.shape else None
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    todo = [(a, s, mp) for a, s in cells(archs, shape_filter) for mp in meshes]
    print(f"[dryrun] {len(todo)} cells ({len(done)} already done)")
    failures = 0
    for arch, shape, mp in todo:
        key = (arch, shape, "multi" if mp else "single", args.sp)
        if key in done:
            continue
        tag = f"{arch} x {shape} x {key[2]}"
        try:
            rec = run_cell(arch, shape, mp, sp=args.sp)
            rl = rec["roofline"]
            print(f"[dryrun] OK  {tag}: bottleneck={rl['bottleneck']} "
                  f"comp={rl['t_compute_s']:.3f}s mem={rl['t_memory_s']:.3f}s "
                  f"coll={rl['t_collective_s']:.3f}s (compile {rec['compile_s']}s)",
                  flush=True)
        except Exception as e:
            failures += 1
            rec = {"arch": arch, "shape": shape, "mesh": key[2], "sp": args.sp,
                   "ok": False, "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
            print(f"[dryrun] FAIL {tag}: {type(e).__name__}: {str(e)[:200]}", flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
    print(f"[dryrun] complete, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
