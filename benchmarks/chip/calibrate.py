#!/usr/bin/env python3
"""Readings that the correctness limits are set from: the control and the
planted fault, against the reference, at a cell's own size.

    python3 benchmarks/chip/calibrate.py --workload gpt2-1b.s1024-b8 \
        --seeds 11 12 13

For each seed it prints one JSON line with the three numbers of
``check.py`` for

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
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import check
    from reference.train import Reference
    from run import CHECKED_STEPS, ROOT, load_cell
    from tokens import make_batch

    cell = load_cell(ROOT, args.workload)
    cfg, traffic = cell.cfg, cell.traffic
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
        out = {"workload": args.workload, "seed": seed, "device": dev.device_kind,
               "reference_s": time.perf_counter() - t}
        for name, r in variants.items():
            out[name] = check.gaps(r.run(seed, batches), want)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
