#!/usr/bin/env python3
"""Record the TPU trace that ``test_param_fetch.py`` reads.

    python3 benchmarks/chip/tests/record_param_fetch_trace.py   # on a TPU host

Three steps of the program's own train step (``build_train_step``) for a
small decoder shaped like StableLM's blocks (``CONFIG``: partial rotary,
SwiGLU, untied head) under ``PLAN``: every chunk in host memory, bf16
parameters included, two microbatches, no buffered chunk, the first two
layers' activations swapped to host memory. So each microbatch fetches
every layer's weights from the host in its forward and again in its
backward, beside the swapped activations coming back and the streamed
Adam update's fp32 states, under the same ``bench.*`` host spans as
``run.py``. Writes ``param_fetch_step.xplane.pb`` into ``--out``
(``tests/data`` by default) and prints what ``param_fetch.py`` reads.
"""
from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]
# a configuration file's keys (program.model_config), at a small size
CONFIG = {"name": "fetch", "hidden_size": 512, "num_hidden_layers": 4,
          "num_attention_heads": 4, "num_key_value_heads": 4, "intermediate_size": 1024,
          "vocab_size": 2048, "tie_word_embeddings": False, "mlp": "swiglu",
          "norm": "layernorm", "norm_eps": 1e-05, "positions": "rope_partial",
          "rotary_fraction": 0.25, "rope_theta": 10000.0, "param_dtype": "bfloat16"}
SEQ, BATCH, STEPS = 256, 2, 3
PLAN = {"n_chunks": 6, "n_blocks": 4, "n_host": 6, "n_swap": 2, "microbatch": 2,
        "host_params": True}


def record(mesh, out_dir: Path) -> Path:
    """Trace ``STEPS`` steps on ``mesh``; write the trace into ``out_dir``;
    return its path."""
    import jax

    import program
    import trace_reduce
    from repro.configs.base import ShapeConfig
    from repro.core.plan import MemoryPlan
    from repro.train.step_builder import build_train_step
    from tokens import make_batch

    art = build_train_step(program.model_config(CONFIG), MemoryPlan(**PLAN), mesh,
                           ShapeConfig("fetch", SEQ, BATCH, "train"))
    step = art.jit()
    state = art.init(jax.random.PRNGKey(0))

    def batch(i):
        return jax.device_put(make_batch(0, i, BATCH, SEQ, CONFIG["vocab_size"]),
                              art.batch_shardings)

    state = jax.block_until_ready(step(state, batch(0))[0])
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(STEPS):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    state, _ = step(state, batch(1 + i))
                with jax.profiler.TraceAnnotation("bench.wait"):
                    jax.block_until_ready(state)
        jax.profiler.stop_trace()
        out_dir.mkdir(parents=True, exist_ok=True)
        out = out_dir / "param_fetch_step.xplane.pb"
        shutil.copy(trace_reduce.find_trace(tmp), out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=Path, default=HERE / "data")
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import param_fetch

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, JAX sees {dev}", file=sys.stderr)
        return 1
    out = record(Mesh(np.array([dev]).reshape(1, 1), ("data", "model")), args.out)
    print(param_fetch.reduce_file(str(out), CONFIG))
    return 0


if __name__ == "__main__":
    sys.exit(main())
