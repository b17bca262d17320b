#!/usr/bin/env python3
"""ProTrain chip benchmark: one run of one cell.

    python3 benchmarks/chip/run.py --workload gpt2-1b.s1024-b8 --seed 7 \
        --seconds 10 --trace 0

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json``) and a job (``traffic/<traffic>.json``: sequence
length, global batch, mesh, optimizer). A run:

1. reads the cell's files and refuses to run (exit 1, no result) unless JAX
   sees enough TPUs whose ``device_kind`` is in ``peaks.json``;
2. plans and compiles the step through the program's ``fit_plan`` on the
   cell's mesh, makes the initial state from the seed, and drives the step
   as ``train_loop`` does: two steps whose losses, first gradient and
   parameter change it keeps for the check, and one more to warm up
   (set-up ends here);
3. runs whole steps for ``--seconds`` (the window), with ``--trace 1`` under
   the profiler;
4. frees the program's state and runs the plain float32 reference over the
   same two batches from the same weights (``check.py`` compares);
5. prints one JSON line: the cell's end-to-end metrics (``--trace 0``) or
   per-layer metrics (``--trace 1``, each read by ``metrics/<name>.py``).

JAX's persistent compilation cache lives in ``.jax_cache/`` at the checkout
root (or where ``JAX_COMPILATION_CACHE_DIR`` says), so only a cell's first
run in a checkout compiles.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, for setup_s

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
CHECKED_STEPS = 2  # set-up steps whose results the reference checks
WARM_STEPS = 3  # set-up steps in all, before the window


class NoResult(Exception):
    """The run cannot produce a result (no chip, missing files, ...)."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_cell(root: Path, name: str) -> types.SimpleNamespace:
    """The cell's entry and files, found by the names in BENCHMARK.json."""
    bench = root / "benchmarks" / "chip"
    try:
        spec = json.loads((root / "BENCHMARK.json").read_text())
        cell = next((w for w in spec["workloads"] if w["name"] == name), None)
        if cell is None:
            raise NoResult(f"no workload {name!r} in BENCHMARK.json")
        cfg = json.loads((bench / "configs" / f"{cell['config']}.json").read_text())
        traffic = json.loads((bench / "traffic" / f"{cell['traffic']}.json").read_text())
        limits = json.loads((bench / "limits" / f"{name}.json").read_text())["limits"]
        peaks = json.loads((bench / "peaks.json").read_text())
    except (OSError, KeyError, json.JSONDecodeError) as e:
        raise NoResult(f"cell {name!r}: {e!r}") from e
    per_layer = [m for m in spec["per_layer"] if name in m.get("workloads", [name])]
    return types.SimpleNamespace(name=name, entry=cell, cfg=cfg, traffic=traffic,
                                 limits=limits, peaks=peaks, spec=spec, bench=bench,
                                 per_layer=per_layer)


def metric_reader(bench: Path, name: str):
    path = bench / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    if spec is None:
        raise NoResult(f"no reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_devices(cell, require_tpu: bool):
    import jax

    devs = jax.devices()
    need = cell.entry["chips"]
    if require_tpu:
        kinds = {d.device_kind for d in devs}
        if devs[0].platform != "tpu":
            raise NoResult(f"no TPU: JAX's devices are {devs}")
        if not kinds <= set(cell.peaks):
            raise NoResult(f"device kinds {sorted(kinds)} not in peaks.json")
    if len(devs) < need:
        raise NoResult(f"the cell needs {need} chips, JAX sees {len(devs)}")
    return devs[:need]


def make_mesh(devs, traffic):
    import numpy as np
    from jax.sharding import Mesh

    shape = tuple(traffic["mesh"]["shape"])
    if math.prod(shape) != len(devs):
        raise NoResult(f"mesh {shape} does not match {len(devs)} chips")
    return Mesh(np.array(devs).reshape(shape), tuple(traffic["mesh"]["axes"]))


def _time_gc(pauses: list, phase: str, info: dict) -> None:
    """gc callback: appends -start and +stop clock readings, so that the
    sum is the time spent collecting."""
    pauses.append(time.perf_counter() * (-1 if phase == "start" else 1))


def memory_peak(devs) -> int:
    """Device memory at its peak, highest over chips: buffers
    (``peak_bytes_in_use``) plus the memory executables reserve for their
    temporaries (``peak_bytes_reserved``), which a TPU does not count as in
    use."""
    stats = [d.memory_stats() or {} for d in devs]
    return max(int(s.get("peak_bytes_in_use", 0)) + int(s.get("peak_bytes_reserved", 0))
               for s in stats)


def run_cell(root: Path, workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, hw=None, step_wrapper=None) -> dict:
    """One run; returns the result object. ``require_tpu``, ``hw`` and
    ``step_wrapper`` are for tests on the CPU: they skip the look for a
    chip, plan against a given hardware spec, and break the timed step."""
    import jax

    import check
    import program
    from reference.train import Readings, Reference
    from tokens import make_batch
    from weights import seed_key

    cell = load_cell(root, workload)
    devs = check_devices(cell, require_tpu)
    cfg, traffic = cell.cfg, cell.traffic
    b, s, v = traffic["global_batch"], traffic["seq_len"], cfg["vocab_size"]
    say(f"{workload}: {cfg['name']} seq {s} batch {b} on {len(devs)} x "
        f"{devs[0].device_kind}, seed {seed}")
    mesh = make_mesh(devs, traffic)
    if hw is None:
        from repro.core.hardware import hardware_for_device

        hw = hardware_for_device(devs[0])
    t = time.perf_counter()
    fit = program.fit(cfg, traffic, mesh, hw, log=say)
    art = fit.art
    say(f"plan {art.plan.describe()}; {len(fit.misses)} refused compiles; fit_plan "
        f"{time.perf_counter() - t:.2f} s (accepted compile {fit.compile_s:.2f} s)")
    t = time.perf_counter()
    leaves = program.leaf_map(cfg, art)
    key = seed_key(seed)
    state, masters = program.make_state(cfg, art, leaves, key)
    jax.block_until_ready(state)
    say(f"initial state: {time.perf_counter() - t:.2f} s; device peak so far "
        f"{memory_peak(devs) / 1e9:.3f} GB")
    ma = fit.compiled.memory_analysis()
    say(f"step executable: arguments {ma.argument_size_in_bytes / 1e9:.3f} GB, temp "
        f"{ma.temp_size_in_bytes / 1e9:.3f} GB, output {ma.output_size_in_bytes / 1e9:.3f} GB,"
        f" aliased {ma.alias_size_in_bytes / 1e9:.3f} GB")
    jfn = art.jit()
    if step_wrapper is not None:
        jfn = step_wrapper(jfn)

    def step(i, state):
        with jax.profiler.TraceAnnotation("bench.batch"):
            batch = jax.device_put(make_batch(seed, i, b, s, v), art.batch_shardings)
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            new_state, metrics = jfn(state, batch)
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready(new_state)
            loss = float(metrics["loss"])
        return new_state, loss

    losses = []
    for i in range(WARM_STEPS):
        t = time.perf_counter()
        state, loss = step(i, state)
        say(f"set-up step {i}: {time.perf_counter() - t:.4f} s, loss {loss:.6f}")
        losses.append(loss)
        if i == 0:
            g1 = program.grad_norms(state, leaves, traffic["optimizer"]["b1"])
        if i == CHECKED_STEPS - 1:
            changes = program.change_norms(state, leaves, masters)
            del masters
            say(f"checked steps read back: device peak so far {memory_peak(devs) / 1e9:.3f} GB")
    prog = Readings(losses[:CHECKED_STEPS], g1, changes)

    trace_dir = root / ".bench_trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    pauses: list[float] = []
    gc.callbacks.append(functools.partial(_time_gc, pauses))
    window_compiles: list[str] = []

    def on_compile(name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            window_compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    n_steps, failed, i, ends = 0, 0, WARM_STEPS, []
    t_start = time.perf_counter()
    setup_s = t_start - T0
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            state, loss = step(i, state)
            i += 1
            n_steps += 1
            failed += not math.isfinite(loss)
            ends.append(time.perf_counter() - t_start)
            if ends[-1] >= seconds:
                break
    window_s = ends[-1]
    gc.callbacks.pop()
    jax.monitoring.unregister_event_duration_listener(on_compile)
    if trace:
        jax.profiler.stop_trace()
    tokens_per_s = n_steps * b * s / window_s
    peak = memory_peak(devs)
    say(f"window: {n_steps} steps in {window_s:.4f} s, {tokens_per_s:.1f} tokens/s, "
        f"last loss {loss:.6f}; set-up {setup_s:.2f} s; peak HBM {peak / 1e9:.3f} GB")
    say("window step seconds: " + " ".join(
        f"{b - a:.4f}" for a, b in zip([0.0, *ends], ends)))
    say(f"window garbage collections: {len(pauses) // 2}, {sum(pauses):.4f} s; "
        f"compiles in the window: {len(window_compiles)}")

    compiles = len(fit.misses) + 1
    del state, art, jfn, fit
    gc.collect()
    batches = [make_batch(seed, i, b, s, v) for i in range(CHECKED_STEPS)]
    t_ref = time.perf_counter()
    ref = Reference(cfg, traffic["optimizer"], device=devs[0]).run(seed, batches)
    numbers = check.gaps(prog, ref)
    correct = check.verdict(numbers, cell.limits) and failed == 0
    say(f"reference: {time.perf_counter() - t_ref:.2f} s; losses program "
        f"{prog.losses} reference {ref.losses}")

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": n_steps, "failed": failed}
    if trace:
        import trace_reduce

        red = trace_reduce.reduce_file(trace_reduce.find_trace(str(trace_dir)))
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        ctx = types.SimpleNamespace(
            cfg=cfg, traffic=traffic, chips=len(devs), peaks=cell.peaks[dev.device_kind]
            if dev.device_kind in cell.peaks else None, tokens_per_s=tokens_per_s,
            n_steps=n_steps, compiles=compiles, memory_peak_bytes=peak, trace=red)
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(cell.bench, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = red["breakdown"]
        say(f"trace: busy {red['busy_s']:.4f} s of {red['window_s']:.4f} s; self time by "
            f"class {red['class_s']}")
    else:
        metrics = {"tokens_per_s": {"value": tokens_per_s, "unit": "tokens/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    result.update(metrics=metrics, device=device, checks=check.report(numbers, cell.limits))
    for n, c in result["checks"].items():
        say(f"check {n} {c['value']:.6e} limit {c['limit']:.6e}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = ROOT / "src"
    sys.path.insert(0, str(src))
    try:
        import repro
        from repro.launch.train import enable_compile_cache
    except ImportError as e:
        say(f"the program is not beside the benchmark: {e}")
        return 1
    if src not in {Path(d).resolve().parent for d in repro.__path__}:
        say(f"imported repro from {list(repro.__path__)}, not from {src}")
        return 1
    import jax

    say(f"compile cache: {enable_compile_cache(ROOT)}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace))
    except NoResult as e:
        say(f"no result: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
