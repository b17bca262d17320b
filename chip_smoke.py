#!/usr/bin/env python3
"""Chip smoke: train the paper's GPT-2 1B on a TPU through the normal trainer.

    python chip_smoke.py               # one chip: searched plan, 5 steps
    python chip_smoke.py --four-chips  # ZeRO-3 manual int8_ef vs xla sync

One chip: ``gpt2-1b`` (configs/paper_models.py, 1.01 B parameters, the
paper's Table 1/4 model) at full width and seq 1024 goes through the same
functions ``python -m repro.launch.train`` uses on a chip —
``launch.train.fit_plan`` (search, build_train_step, compile) then
``train.loop.train_loop`` — planned against the spec of the chip
``device_kind`` names, realised as searched with its host chunks, for 5
steps of ``SyntheticTokenPipeline`` data from ``--seed``. It also checks
the two repaired Pallas kernels against their oracles on the chip.

Four chips: the same model on an explicit ``(4, 1)`` ``("data", "model")``
mesh under a ZeRO-3 manual-sync plan with int8+EF gradients on the wire
(the fused quantize kernel), against the same plan with XLA's sync, 5 steps
each; losses must agree within ``LOSS_RTOL``.

Exits non-zero, printing no result, when JAX finds no TPU or when any check
fails. The last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

ARCH = "gpt2-1b"
SEQ = 1024
BATCHES = (8, 4, 2, 1)  # largest first: the plan's batch is the largest that fits
STEPS = 5
TIMED = slice(2, 5)  # steps 3-5: compile and first transfers are behind them
# manual int8+EF vs XLA-sync losses (tests/test_manual_sync.py's bound)
LOSS_RTOL = 2e-2


def fail(msg: str) -> None:
    print(f"[smoke] FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def host_state_bytes(specs) -> int:
    import jax

    return sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(specs)
               if getattr(s.sharding, "memory_kind", None) == "pinned_host")


def check_kernels(seed: int) -> None:
    """The repaired kernels, compiled, against their jnp oracles."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import fused_quantize_ef, ref
    from repro.kernels.ops import rmsnorm

    key = jax.random.PRNGKey(seed)
    # a ragged wire chunk (rows % tile != 0) and activation rows
    for shape, me in (((4, 1100, 256), 2), ((300, 2048), 0)):
        x = jax.random.normal(key, shape, jnp.float32) * 3.0
        q, s, e = fused_quantize_ef(x, jnp.int32(me))
        qr, sr, er = jax.jit(ref.fused_quantize_ef_ref)(x, jnp.int32(me))
        dq = int(jnp.max(jnp.abs(q.astype(jnp.int32) - qr.astype(jnp.int32))))
        ds = float(jnp.max(jnp.abs(s - sr) / sr))
        de = float(jnp.max(jnp.abs(e - er)))
        say(f"kernel fused_quantize_ef {shape}: max |dq|={dq} max rel dscale={ds:.2e} "
            f"max |derr|={de:.2e}")
        # a quotient on a rounding tie may land one step apart
        if dq > 1 or ds > 1e-6 or de > float(jnp.max(sr)) * 1.01:
            fail(f"fused_quantize_ef {shape} disagrees with its oracle on the chip")
    x = jax.random.normal(key, (4096, 2048), jnp.bfloat16)
    sc = jax.random.normal(jax.random.fold_in(key, 1), (2048,), jnp.float32) + 1.0
    got = np.asarray(rmsnorm(x, sc), np.float32)
    want = np.asarray(ref.rmsnorm_ref(x, sc).astype(jnp.bfloat16), np.float32)
    err = float(np.max(np.abs(got - want) / (np.abs(want) + 1e-2)))
    say(f"kernel rmsnorm bf16 (4096, 2048): max rel err {err:.2e}")
    if err > 2e-2:
        fail("rmsnorm disagrees with its oracle on the chip")


def train(art, cfg, shape, seed: int):
    import jax

    from repro.data.pipeline import SyntheticTokenPipeline
    from repro.train.loop import LoopConfig, train_loop

    res = train_loop(art, SyntheticTokenPipeline(cfg, shape, seed=seed), None,
                     LoopConfig(total_steps=STEPS, checkpoint_every=10 ** 9,
                                log_every=1),
                     init_key=jax.random.PRNGKey(seed), log=say)
    if res.nan_skips or len(res.losses) != STEPS:
        fail(f"non-finite loss: {res.nan_skips} of {STEPS} steps skipped")
    if not all(math.isfinite(x) for x in res.losses):
        fail(f"non-finite loss in {res.losses}")
    return res


def report_steps(res, tokens: int) -> float:
    for i, (dt, loss) in enumerate(zip(res.step_times, res.losses), 1):
        say(f"step {i}: {dt:.4f} s, {tokens / dt:.1f} tokens/s, loss {loss:.6f}")
    t = sum(res.step_times[TIMED]) / len(res.step_times[TIMED])
    say(f"steps 3-5: {t:.4f} s/step, {tokens / t:.1f} tokens/s")
    return t


def one_chip(seed: int) -> None:
    import jax

    from repro import obs
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core.hardware import hardware_for_device
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import fit_plan
    from repro.optim.adam import AdamConfig, cosine_schedule
    from repro.train.step_builder import build_train_step

    check_kernels(seed)
    hw = hardware_for_device(jax.devices()[0])
    cfg = get_config(ARCH)
    mesh = make_local_mesh()
    say(f"config {cfg.name}: {cfg.param_count() / 1e9:.3f} B params, d_model "
        f"{cfg.d_model}, {cfg.num_layers} layers, {cfg.num_heads} heads, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, seq {SEQ}")
    say(f"planning against {hw.name}: HBM {hw.hbm_bytes / 1e9:.2f} GB, plannable "
        f"{hw.capacity_bytes() / 1e9:.2f} GB, host RAM {hw.host_mem_bytes / 1e9:.2f} GB")
    fit = shape = None
    for batch in BATCHES:
        shape = ShapeConfig("smoke", SEQ, batch, "train")
        lr = 3e-4

        def build(plan, _shape=shape):
            return build_train_step(cfg, plan, mesh, _shape, adam=AdamConfig(lr=lr),
                                    lr_schedule=cosine_schedule(lr, warmup=1, total=STEPS))

        try:
            fit = fit_plan(cfg, shape, mesh, hw, build, log=say)
            break
        except RuntimeError as e:
            say(f"batch {batch}: {e}")
    if fit is None:
        fail("no searched plan compiled within device memory at any batch")
    plan, art = fit.art.plan, fit.art
    for peak, over in fit.misses:
        say(f"cost-model miss: modeled peak {peak / 1e9:.3f} GB, compiler over "
            f"HBM by {over / 1e9:.3f} GB")
    say(f"plan (batch {shape.global_batch}, capacity {fit.capacity_bytes / 1e9:.3f} GB): "
        f"{plan.describe()}")
    say(f"compile: {fit.compile_s:.2f} s")
    ma = fit.compiled.memory_analysis()
    say(f"compiled memory_analysis: arguments {ma.argument_size_in_bytes / 1e9:.3f} GB, "
        f"temp {ma.temp_size_in_bytes / 1e9:.3f} GB, output "
        f"{ma.output_size_in_bytes / 1e9:.3f} GB, aliased {ma.alias_size_in_bytes / 1e9:.3f} GB")
    if plan.n_host < 1:
        fail(f"the searched plan has no host chunk: {plan.describe()}")
    host_b = host_state_bytes(art.state_specs)
    say(f"host RAM free (/proc/meminfo MemAvailable) {hw.host_mem_bytes / 1e9:.2f} GB; plan's "
        f"host-placed state {host_b / 1e9:.3f} GB")

    res = train(art, cfg, shape, seed)
    tokens = shape.global_batch * SEQ
    t = report_steps(res, tokens)
    say(f"losses: {res.losses}")
    say(f"first loss {res.losses[0]:.4f} vs ln(vocab) = ln({cfg.vocab_size}) = "
        f"{math.log(cfg.vocab_size):.4f}")
    leaf = next((x for x in jax.tree.leaves(res.state)
                 if getattr(x.sharding, "memory_kind", None) not in (None, "device")),
                None)
    kind = None if leaf is None else leaf.sharding.memory_kind
    say(f"host-placed state leaf {None if leaf is None else leaf.shape}: memory kind {kind}")
    if kind != "pinned_host":
        fail(f"host-placed state is in {kind!r}, not pinned_host")
    peak, src = obs.device_memory_watermark()
    if src != "memory_stats":
        fail(f"peak HBM came from {src}, not memory_stats")
    say(f"peak HBM {peak / 1e9:.3f} GB (source {src}) vs modeled peak "
        f"{fit.search.memory.peak / 1e9:.3f} GB")
    say(f"step time {t:.4f} s measured vs t_iter {fit.search.runtime.t_iteration:.4f} s "
        f"modeled")


def four_chips(seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core import build_workload, search
    from repro.core.hardware import MeshSpec, hardware_for_device
    from repro.optim.adam import AdamConfig, cosine_schedule
    from repro.train.step_builder import build_train_step

    devs = jax.devices()
    if len(devs) != 4:
        fail(f"--four-chips needs 4 devices, JAX sees {len(devs)}")
    hw = hardware_for_device(devs[0])
    cfg = get_config(ARCH)
    shape = ShapeConfig("smoke4", SEQ, 8, "train")
    mesh = Mesh(np.array(devs).reshape(4, 1), ("data", "model"))
    w = build_workload(cfg, shape, MeshSpec((4, 1), ("data", "model")), hw)
    found = search(w, sp="auto", sync="manual", compress="on")
    plan = dataclasses.replace(found.plan, zero_stage=3)
    if plan.manual_sync_kind(tp_degree=1) != "zero3" or plan.grad_compress != "int8_ef":
        fail(f"not a ZeRO-3 manual int8_ef plan: {plan.describe()}")
    say(f"config {cfg.name} on mesh (4, 1) ('data', 'model'), batch 8, seq {SEQ}")
    lr = 3e-4
    losses = {}
    for sync in ("manual", "xla"):
        p = dataclasses.replace(plan, sync_mode=sync)
        art = build_train_step(cfg, p, mesh, shape, adam=AdamConfig(lr=lr),
                               lr_schedule=cosine_schedule(lr, warmup=1, total=STEPS))
        t0 = time.perf_counter()
        compiled = art.lower().compile()
        say(f"[{sync}] plan {p.describe()}; compile {time.perf_counter() - t0:.2f} s")
        if sync == "manual" and "tpu_custom_call" not in compiled.as_text():
            fail("the manual int8_ef step has no Pallas quantize kernel")
        del compiled
        res = train(art, cfg, shape, seed)
        report_steps(res, shape.global_batch * SEQ)
        losses[sync] = res.losses
        say(f"[{sync}] losses: {res.losses}")
        zero = [x for x in jax.tree.leaves(res.state["params"])
                if x.addressable_shards[0].data.shape != x.shape]
        if not zero:
            fail(f"[{sync}] no parameter is sharded over the data axis")
        n_dev = {len({s.device for s in x.addressable_shards}) for x in zero}
        say(f"[{sync}] {len(zero)} ZeRO-sharded parameter leaves, shards on "
            f"{sorted(n_dev)} distinct devices")
        if n_dev != {4}:
            fail(f"[{sync}] parameter shards are not on 4 distinct devices")
        del res, art, zero  # free this run's state before the next one
    rel = max(abs(a - b) / abs(b) for a, b in zip(losses["manual"], losses["xla"]))
    say(f"manual int8_ef vs xla sync: max relative loss difference {rel:.3e} "
        f"(tolerance {LOSS_RTOL})")
    if rel > LOSS_RTOL:
        fail("manual ZeRO-3 int8_ef losses left the xla-sync tolerance")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip ZeRO-3 manual-vs-xla comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = Path(__file__).resolve().parent / "src"
    sys.path.insert(0, str(src))
    try:
        import repro
        from repro.compat import pallas_interpret_required
        from repro.launch.train import enable_compile_cache
    except ImportError as e:
        fail(f"the repro package is not beside this script: {e}")
    if src not in {Path(d).resolve().parent for d in repro.__path__}:
        fail(f"imported repro from {list(repro.__path__)}, not from {src}")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's devices are {dev.platform} ({jax.devices()})")
    say(f"platform {dev.platform}, device_kind {dev.device_kind}, "
        f"device count {len(jax.devices())}")
    if pallas_interpret_required():
        fail("Pallas kernels would run in interpret mode")
    say(f"compile cache: {enable_compile_cache()}")
    (four_chips if args.four_chips else one_chip)(args.seed)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
