"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
        --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/run1

Selects the architecture, runs the ProTrain automatic memory-management
search, builds the plan-realized train step, and runs the fault-tolerant
loop with checkpointing + auto-resume.

On an accelerator the search plans against the spec of the chip
``jax.devices()[0].device_kind`` names (``--target-hw`` overrides it), the
plan is realised as searched — host chunks included — and its step is
compiled before training (``fit_plan``). On the CPU backend the plan is
searched for a TPU v5e (or ``--target-hw``) for inspection, and without
``--target-hw`` its chunks are parked on device: host offload means nothing
on a CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
from pathlib import Path
from typing import Callable

import jax

from repro import obs
from repro.configs import get_config, reduced
from repro.configs.base import ShapeConfig
from repro.core import TPU_V5E, build_workload, search
from repro.core.autotuner import SearchResult
from repro.core.hardware import HARDWARE, HardwareSpec, MeshSpec, hardware_for_device
from repro.core.plan import MemoryPlan, fully_resident_plan
from repro.ckpt.checkpoint import CheckpointManager
from repro.data.pipeline import SyntheticTokenPipeline
from repro.launch.mesh import make_local_mesh
from repro.optim.adam import AdamConfig, cosine_schedule
from repro.train.loop import LoopConfig, train_loop
from repro.train.step_builder import StepArtifacts, build_train_step

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache(root: Path = CHECKOUT_ROOT) -> str:
    """Keep JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (jax reads it itself), else in
    ``.jax_cache/`` at the checkout root — a fixed path, because the path is
    part of the cache key. Returns the directory in use.

    Cache keys include the HLO's metadata: by default JAX leaves it out, and
    an executable loaded from the cache then carries the op names of
    whichever program compiled it first, so a profile would attribute the
    step's device time to stale ``jax.named_scope`` phases."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


_OVER_HBM = re.compile(r"Exceeded hbm capacity by ([\d.]+)([KMGT]?)")


def hbm_overshoot(err: Exception) -> float | None:
    """Bytes by which the TPU compiler found a program over device memory,
    parsed from its RESOURCE_EXHAUSTED message; None for any other error."""
    m = _OVER_HBM.search(str(err))
    if m is None:
        return None
    return float(m.group(1)) * 1024 ** " KMGT".index(m.group(2) or " ")


@dataclasses.dataclass
class FitResult:
    """A searched plan whose step the chip's compiler accepted."""

    search: SearchResult
    art: StepArtifacts
    compiled: jax.stages.Compiled
    compile_s: float  # the accepted attempt's plan.lower + plan.compile seconds
    capacity_bytes: float  # planning capacity the accepted search used
    misses: list[tuple[float, float]]  # (modeled peak, compiler overshoot) per refusal


def fit_plan(cfg, shape: ShapeConfig, mesh, hw: HardwareSpec,
             build: Callable[[MemoryPlan], StepArtifacts], *,
             max_tries: int = 4, log: Callable[[str], None] = print) -> FitResult:
    """Search the plan and compile its step for the devices of ``mesh``.

    The cost model's peak is a prediction; the compiler's verdict is the
    fact. When the compiler refuses the step for exceeding device memory
    by X bytes, the search runs again with its capacity lowered by X, so
    the plan that runs is still the planner's fastest one — under the
    capacity the compiler can actually deliver.

    Each try is a ``plan.attempt`` span (attributes ``plan``, ``accepted``,
    ``modeled_peak_bytes``, ``overshoot_bytes``) around ``plan.search``,
    ``plan.build``, ``plan.lower`` and ``plan.compile`` spans, on the
    installed telemetry's tracer.
    """
    mspec = MeshSpec(tuple(mesh.devices.shape), tuple(mesh.axis_names))
    w = build_workload(cfg, shape, mspec, hw)
    cap = hw.capacity_bytes()
    misses: list[tuple[float, float]] = []
    tracer = obs.current_telemetry().tracer
    for _ in range(max_tries):
        with tracer.span("plan.attempt", capacity_bytes=cap, accepted=False) as attempt:
            with tracer.span("plan.search"):
                res = search(w, capacity_bytes=cap, sp="auto")
            attempt.attrs.update(plan=res.plan.describe(), modeled_peak_bytes=res.memory.peak)
            with tracer.span("plan.build"):
                art = build(res.plan)
            with tracer.span("plan.lower") as lowering:
                lowered = art.lower()
            try:
                with tracer.span("plan.compile") as compiling:
                    compiled = lowered.compile()
            except jax.errors.JaxRuntimeError as e:
                over = hbm_overshoot(e)
                if over is None:
                    raise
                attempt.attrs["overshoot_bytes"] = over
                misses.append((res.memory.peak, over))
                log(f"[fit] {res.plan.describe()}: modeled peak "
                    f"{res.memory.peak / 1e9:.3f} GB, compiler needs {over / 1e9:.3f} GB "
                    f"more than the device has; searching again at capacity "
                    f"{(cap - over) / 1e9:.3f} GB")
                cap -= over
                continue
            attempt.attrs.update(accepted=True, overshoot_bytes=0.0)
        return FitResult(res, art, compiled, lowering.dur_s + compiling.dur_s, cap, misses)
    raise RuntimeError(f"no plan fits after {max_tries} compiles: {misses}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (smoke-scale) variant of the arch")
    ap.add_argument("--target-hw", default=None, choices=[None, *HARDWARE],
                    help="plan against this hardware spec instead of the local chip")
    ap.add_argument("--plan", default="auto", choices=["auto", "resident", "fsdp"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    mesh = make_local_mesh()
    n_dev = len(jax.devices())
    on_cpu = jax.default_backend() == "cpu"
    adam = AdamConfig(lr=args.lr)
    lr_schedule = cosine_schedule(args.lr, warmup=min(20, args.steps // 10 + 1),
                                  total=args.steps)

    def build(plan: MemoryPlan) -> StepArtifacts:
        return build_train_step(cfg, plan, mesh, shape, adam=adam,
                                lr_schedule=lr_schedule)

    from repro.core.chunks import chunk_inventory
    from repro.models.model import num_repeats

    nc = len(chunk_inventory(cfg))
    nb = num_repeats(cfg)
    art = None
    if args.plan == "auto" and not on_cpu:
        hw = (HARDWARE[args.target_hw] if args.target_hw
              else hardware_for_device(jax.devices()[0]))
        fit = fit_plan(cfg, shape, mesh, hw, build)
        art, plan = fit.art, fit.art.plan
        print(f"[train] searched plan: {plan.describe()} (modeled "
              f"t_iter={fit.search.runtime.t_iteration:.3f}s on {hw.name}, "
              f"compiled in {fit.compile_s:.1f}s)")
    elif args.plan == "auto":
        hw = HARDWARE[args.target_hw] if args.target_hw else TPU_V5E
        mspec = MeshSpec(tuple(mesh.devices.shape), tuple(mesh.axis_names))
        res = search(build_workload(cfg, shape, mspec, hw), sp="auto")
        plan = res.plan
        print(f"[train] searched plan: {plan.describe()} "
              f"(modeled t_iter={res.runtime.t_iteration:.3f}s on {hw.name})")
        if args.target_hw is None:
            # CPU run: memory-kind offload means nothing here; keep the
            # block policies but park every chunk on device
            plan = dataclasses.replace(plan, n_host=0, n_persist=plan.n_chunks,
                                       n_buffer=0)
    elif args.plan == "fsdp":
        plan = MemoryPlan(n_chunks=nc, n_blocks=nb, n_checkpoint=nb)
    else:
        plan = fully_resident_plan(nc, nb)
    print(f"[train] arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"devices={n_dev} plan={plan.describe()}")

    art = art or build(plan)
    pipe = SyntheticTokenPipeline(cfg, shape, seed=args.seed)
    mgr = CheckpointManager(args.ckpt_dir, keep=2) if args.ckpt_dir else None
    res = train_loop(
        art, pipe, mgr,
        LoopConfig(total_steps=args.steps, checkpoint_every=args.ckpt_every,
                   log_every=max(1, args.steps // 20)),
        init_key=jax.random.PRNGKey(args.seed),
    )
    print(json.dumps({
        "arch": cfg.name,
        "steps": res.steps_run,
        "first_loss": res.losses[0] if res.losses else None,
        "final_loss": res.losses[-1] if res.losses else None,
        "resumed_from": res.resumed_from,
        "straggler_events": res.straggler_events,
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
