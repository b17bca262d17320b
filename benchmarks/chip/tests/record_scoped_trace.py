#!/usr/bin/env python3
"""Record the scoped TPU trace that ``test_trace_split.py`` reads.

    python3 benchmarks/chip/tests/record_scoped_trace.py   # on a TPU host

Three steps of a jitted training step shaped like the program's: a loss
under ``jax.named_scope("model")``, differentiated (forward ops then carry
``jvp(model)``, backward ops ``transpose(jvp(model))``), and an Adam-like
update under ``jax.named_scope("optimizer")`` whose fp32 master, m and v
live in host memory and make the round trip to the device and back, under
the same ``bench.*`` host spans as ``run.py``. Writes
``tests/data/scoped_step.xplane.pb``, the step's compiled HLO text beside it
(``scoped_step.hlo.txt``), and prints what ``trace_split.py`` reads.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
N = 4096  # 64 MiB per fp32 state leaf


def record(dev, out_dir: Path) -> Path:
    """Trace three steps on ``dev``; write the trace and HLO into
    ``out_dir``; return the trace's path."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import trace_reduce

    host = SingleDeviceSharding(dev, memory_kind="pinned_host")
    on_dev = SingleDeviceSharding(dev, memory_kind="device")

    def step(w, master, m, v, x):
        @jax.named_scope("model")
        def loss(w):
            h = jnp.tanh(x @ w)
            return jnp.mean(jnp.square(h.astype(jnp.float32)))

        value, g = jax.value_and_grad(loss)(w)
        with jax.named_scope("optimizer"):
            master, m, v = (jax.device_put(a, on_dev) for a in (master, m, v))
            gf = g.astype(jnp.float32)
            gf = gf * jnp.minimum(1.0, 1.0 / jnp.sqrt(jnp.sum(gf * gf)))  # clip to norm 1
            m = 0.9 * m + 0.1 * gf
            v = 0.95 * v + 0.05 * gf * gf
            master = master - 1e-3 * m / (jnp.sqrt(v) + 1e-8)
            w = master.astype(w.dtype)
            master, m, v = (jax.device_put(a, host) for a in (master, m, v))
        return w, master, m, v, value

    shardings = (on_dev, host, host, host, on_dev)
    jstep = jax.jit(step, in_shardings=shardings,
                    out_shardings=(*shardings[:4], None), donate_argnums=(0, 1, 2, 3))
    key = jax.random.PRNGKey(0)
    w = jax.device_put(jax.random.normal(key, (N, N), jnp.bfloat16) * 0.01, on_dev)
    master = jax.device_put(w.astype(jnp.float32), host)
    m = jax.device_put(jnp.zeros((N, N), jnp.float32), host)
    v = jax.device_put(jnp.zeros((N, N), jnp.float32), host)
    x = jax.device_put(jax.random.normal(key, (N, N), jnp.bfloat16), on_dev)
    compiled = jstep.lower(w, master, m, v, x).compile()
    state = jax.block_until_ready(jstep(w, master, m, v, x))[:4]
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    *state, value = jstep(*state, x)
                with jax.profiler.TraceAnnotation("bench.wait"):
                    jax.block_until_ready(state)
        jax.profiler.stop_trace()
        out_dir.mkdir(parents=True, exist_ok=True)
        out = out_dir / "scoped_step.xplane.pb"
        shutil.copy(trace_reduce.find_trace(tmp), out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    # source file names relative to the checkout
    (out_dir / "scoped_step.hlo.txt").write_text(
        compiled.as_text().replace(f"{HERE.parents[2]}/", ""))
    return out


def main() -> int:
    import jax

    import trace_reduce
    import trace_split

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, JAX sees {dev}", file=sys.stderr)
        return 1
    out = record(dev, HERE / "data")
    print(trace_reduce.reduce_file(str(out)))
    print(trace_split.split_file(str(out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
