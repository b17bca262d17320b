"""Fault-tolerant training loop.

Responsibilities beyond calling step_fn in a loop:
  * auto-resume from the latest valid checkpoint (params+optimizer+data state);
  * periodic async checkpointing with atomic publish;
  * preemption handling (SIGTERM -> synchronous final save);
  * straggler/hang mitigation: a watchdog flags steps exceeding
    ``deadline_factor`` x the trailing-median step time (on real fleets this
    triggers re-slicing; here it logs and records, keeping the control path
    exercised and testable);
  * NaN-loss circuit breaker with skip-and-log (bad batch resilience).

Observability (repro.obs): every ``[loop]`` line goes through a
``StructuredLogger`` — the human-readable output is unchanged, and each line
is also a machine-parseable JSONL record. Passing ``telemetry=`` turns on
the runtime measurement layer: a ``train.step`` span per step, step-time
histogram, loss / device-memory-watermark gauges, straggler/nan counters,
and (with ``drift=``) the online measured-vs-modeled ``DriftMonitor``. All
instrumentation is host-side — the jitted step program is untouched whether
telemetry is on or off (HLO-identity pinned by tests/test_obs.py), and the
enabled-path overhead is bounded (<5% of a toy step, also pinned by test).
"""
from __future__ import annotations

import dataclasses
import signal
import statistics
import time
from typing import Any, Callable

import jax
import numpy as np

from repro import obs
from repro.ckpt.checkpoint import CheckpointManager
from repro.data.pipeline import PipelineState, SyntheticTokenPipeline
from repro.dist import collectives as COLL


@dataclasses.dataclass
class LoopConfig:
    total_steps: int = 100
    checkpoint_every: int = 50
    log_every: int = 10
    deadline_factor: float = 3.0  # straggler threshold vs median step time
    max_nan_skips: int = 3


@dataclasses.dataclass
class LoopResult:
    steps_run: int
    final_step: int
    losses: list[float]
    step_times: list[float]  # seconds per finite-loss step, state ready
    resumed_from: int | None
    straggler_events: int
    nan_skips: int
    state: Any = None  # the train state after the last step


def train_loop(
    step_artifacts,
    pipeline: SyntheticTokenPipeline,
    ckpt: CheckpointManager | None,
    loop_cfg: LoopConfig,
    *,
    init_key=None,
    log: Callable[[str], None] | obs.StructuredLogger = print,
    telemetry: obs.Telemetry | None = None,
    drift: obs.DriftMonitor | None = None,
) -> LoopResult:
    logger = obs.as_logger(log, name="loop")
    tel = telemetry if telemetry is not None else obs.NULL_TELEMETRY
    reg, tracer = tel.registry, tel.tracer
    step_time_h = reg.histogram("train.step_time_s")
    loss_g = reg.gauge("train.loss")
    mem_g = reg.gauge("train.device_mem_watermark_bytes")
    steps_c = reg.counter("train.steps")
    nan_c = reg.counter("train.nan_skips")
    straggler_c = reg.counter("train.straggler_events")

    jfn = step_artifacts.jit()
    plan = getattr(step_artifacts, "plan", None)
    grad_compress = getattr(plan, "grad_compress", "none") if plan is not None else "none"
    if grad_compress != "none":
        suffix = " (error feedback in state)" if grad_compress == "int8_ef" else ""
        sync_mode = getattr(plan, "sync_mode", "xla")
        wire = "compressed payload on the wire" if sync_mode == "manual" else "wire numerics only"
        logger.info(
            "sync_config",
            f"[loop] gradient sync: {sync_mode} ({wire}), "
            f"compression: {grad_compress}{suffix}",
            sync_mode=sync_mode, grad_compress=grad_compress)

    # --- resume or init ------------------------------------------------------
    resumed_from = None
    start_step = 0
    state = None
    if ckpt is not None:
        specs = step_artifacts.state_specs
        try:
            got = ckpt.restore_latest(specs)
        except FileNotFoundError:
            if "ef" not in specs:
                raise
            # checkpoint predates grad compression: restore without the EF
            # residuals and cold-start them at their correct value, zero
            got = ckpt.restore_latest({k: v for k, v in specs.items() if k != "ef"})
            if got is not None:
                s0, st, extra = got
                st["ef"] = jax.tree.map(
                    lambda z, s: jax.device_put(z, s.sharding),
                    COLL.init_error_feedback(specs["ef"]), specs["ef"],
                )
                got = (s0, st, extra)
                logger.warning(
                    "ef_cold_start",
                    "[loop] checkpoint has no EF residuals; starting them at zero")
        if got is not None:
            start_step, state, extra = got
            pipeline.step = int(extra.get("data_step", start_step))
            resumed_from = start_step
            logger.info("resume",
                        f"[loop] resumed from checkpoint step {start_step}",
                        step=start_step)
    if state is None:
        key = init_key if init_key is not None else jax.random.PRNGKey(0)
        state = step_artifacts.init(key)

    # --- preemption handler ---------------------------------------------------
    preempted = {"flag": False}

    def on_term(sig, frame):
        preempted["flag"] = True

    old_handler = signal.signal(signal.SIGTERM, on_term)

    losses: list[float] = []
    step_times: list[float] = []
    straggler_events = 0
    nan_skips = 0
    step = start_step
    try:
        while step < loop_cfg.total_steps:
            batch = jax.device_put(pipeline.next_sync(), step_artifacts.batch_shardings)
            t0 = time.perf_counter()
            with tracer.span("train.step", step=step):
                new_state, metrics = jfn(state, batch)
                jax.block_until_ready(new_state)  # the update too, not just the loss
                loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            step_time_h.observe(dt)
            steps_c.inc()
            if tel.enabled:
                mem_bytes, mem_src = obs.device_memory_watermark()
                mem_g.set_max(mem_bytes)
            else:
                mem_bytes, mem_src = None, "none"
            if drift is not None:
                drift.observe_step(dt, mem_bytes, mem_source=mem_src)

            if not np.isfinite(loss):
                nan_skips += 1
                nan_c.inc()
                logger.warning(
                    "nan_skip",
                    f"[loop] step {step}: non-finite loss ({loss}); skipping batch",
                    step=step, loss=loss)
                if nan_skips > loop_cfg.max_nan_skips:
                    raise FloatingPointError("too many non-finite losses")
                # state was donated; fall back to last checkpoint or abort
                state = new_state  # donated buffers: keep going with updated state
                step += 1
                continue

            state = new_state
            losses.append(loss)
            loss_g.set(loss)
            step_times.append(dt)
            if len(step_times) >= 5:
                med = statistics.median(step_times[-50:])
                if dt > loop_cfg.deadline_factor * med:
                    straggler_events += 1
                    straggler_c.inc()
                    logger.warning(
                        "straggler",
                        f"[loop] step {step}: straggler ({dt:.3f}s vs median {med:.3f}s)",
                        step=step, dt_s=dt, median_s=med)

            if loop_cfg.log_every and step % loop_cfg.log_every == 0:
                ef = metrics.get("ef_norm")
                ef_s = f" ef_norm={float(ef):.3g}" if ef is not None else ""
                fields: dict[str, Any] = {"step": step, "loss": loss,
                                          "dt_s": dt}
                if ef is not None:
                    fields["ef_norm"] = float(ef)
                logger.info(
                    "step",
                    f"[loop] step {step} loss={loss:.4f} ({dt*1e3:.0f} ms){ef_s}",
                    **fields)
            step += 1

            if ckpt is not None and step % loop_cfg.checkpoint_every == 0:
                with tracer.span("train.checkpoint", step=step):
                    ckpt.save(step, state, extra={"data_step": pipeline.step})
            if preempted["flag"]:
                logger.warning(
                    "preempt",
                    "[loop] preemption signal received: final checkpoint + exit",
                    step=step)
                if ckpt is not None:
                    ckpt.save(step, state, extra={"data_step": pipeline.step}, sync=True)
                break
    finally:
        signal.signal(signal.SIGTERM, old_handler)
        if ckpt is not None:
            if not preempted["flag"]:
                ckpt.save(step, state, extra={"data_step": pipeline.step}, sync=True)
            ckpt.wait()

    return LoopResult(
        steps_run=step - start_step,
        final_step=step,
        losses=losses,
        step_times=step_times,
        resumed_from=resumed_from,
        straggler_events=straggler_events,
        nan_skips=nan_skips,
        state=state,
    )
