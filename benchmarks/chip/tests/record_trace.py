#!/usr/bin/env python3
"""Record the small TPU trace that ``test_trace_reduce.py`` reads.

    python3 benchmarks/chip/tests/record_trace.py   # on a TPU host

Three steps of a jitted function that brings a host-placed array to the
device, multiplies it, and sends the result back to host memory, under
the same ``bench.*`` host spans as ``run.py``. Writes
``tests/data/offload_step.xplane.pb`` and prints what the reduction reads
from it.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import trace_reduce

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, JAX sees {dev}", file=sys.stderr)
        return 1
    host = SingleDeviceSharding(dev, memory_kind="pinned_host")
    on_dev = SingleDeviceSharding(dev, memory_kind="device")

    @jax.jit
    def step(x, w):
        y = jax.device_put(x, on_dev) @ w
        return jax.device_put(y, host)

    x = jax.device_put(jnp.ones((4096, 4096), jnp.float32), host)
    w = jnp.full((4096, 4096), 1e-3, jnp.float32)
    step(x, w).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    y = step(x, w)
                with jax.profiler.TraceAnnotation("bench.wait"):
                    y.block_until_ready()
        jax.profiler.stop_trace()
        out = HERE / "data" / "offload_step.xplane.pb"
        out.parent.mkdir(exist_ok=True)
        shutil.copy(trace_reduce.find_trace(tmp), out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(trace_reduce.reduce_file(str(out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
