#!/usr/bin/env python3
"""Readings that the correctness limits are set from: the control and the
planted fault, against the reference, at a cell's own size.

    python3 benchmarks/chip/calibrate.py --workload gpt2-1b.s1024-b8 \
        --seeds 11 12 13
    python3 benchmarks/chip/calibrate.py --config <config.json> \
        --traffic <traffic.json> --seeds 11 12

A cell is named by its workload, or, before it exists, by its
configuration and traffic files. For each seed it prints one JSON line
with the reference's losses and seconds, the device's peak memory once
the reference has run (``peak_bytes_in_use``, and with
``peak_bytes_reserved`` added; the process's peak so far) and, with their
seconds, the three numbers of ``check.py`` for

- ``control``: the reference with its optimizer state (master, m, v)
  rounded to bfloat16 after every update, the precision below the
  configuration's float32 optimizer state;
- ``half_batch``: the reference trained on the first half of each batch's
  rows (half of the batch left out, the mean taken over the rest).

A step that returns its state unchanged reads ``update_norm_gap`` 1 by
construction and needs no run. The program's own readings come from the
benchmark's runs (``run.py``). The benchmark's runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    cell = ap.add_mutually_exclusive_group(required=True)
    cell.add_argument("--workload")
    cell.add_argument("--config", type=Path, help="a configuration file, with --traffic")
    ap.add_argument("--traffic", type=Path, help="a traffic file, with --config")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    if (args.config is None) != (args.traffic is None):
        ap.error("--config and --traffic go together")

    import jax
    import jax.numpy as jnp

    import check
    from reference.train import Reference
    from run import CHECKED_STEPS, ROOT, load_cell, memory_peak
    from tokens import make_batch

    if args.workload:
        cell = load_cell(ROOT, args.workload)
        cfg, traffic, name = cell.cfg, cell.traffic, args.workload
    else:
        cfg = json.loads(args.config.read_text())
        traffic = json.loads(args.traffic.read_text())
        name = f"{args.config.stem}.{args.traffic.stem}"
    b, s = traffic["global_batch"], traffic["seq_len"]
    dev = jax.devices()[0]
    variants = {
        "control": Reference(cfg, traffic["optimizer"], opt_dtype=jnp.bfloat16, device=dev),
        "half_batch": Reference(cfg, traffic["optimizer"], rows=slice(0, b // 2), device=dev),
    }
    ref = Reference(cfg, traffic["optimizer"], device=dev)
    for seed in args.seeds:
        batches = [make_batch(seed, i, b, s, cfg["vocab_size"]) for i in range(CHECKED_STEPS)]
        t = time.perf_counter()
        want = ref.run(seed, batches)
        out = {"workload": name, "seed": seed, "device": dev.device_kind,
               "reference_s": time.perf_counter() - t, "losses": want.losses,
               "peak_bytes_in_use": (dev.memory_stats() or {}).get("peak_bytes_in_use"),
               "peak_bytes_with_reserved": memory_peak([dev])}
        for variant, r in variants.items():
            t = time.perf_counter()
            out[variant] = check.gaps(r.run(seed, batches), want)
            out[f"{variant}_s"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
