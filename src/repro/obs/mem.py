"""Device-memory watermark: the allocator's peak on an accelerator, the
live-array sum on the CPU backend.

Accelerator backends expose allocator statistics through
``Device.memory_stats()`` (``peak_bytes_in_use`` is the HBM watermark the
cost model's ``estimate_memory`` predicts); an accelerator that reports no
peak is an error, never a quiet fallback. The forced-host CPU backend
returns nothing there, so on CPU the watermark is the committed bytes of
every live ``jax.Array`` — that misses XLA's transient temp buffers (they
live only inside a step's execution) but tracks the resident model/
optimizer/cache state, which is the dominant term the drift monitor
watches on CPU. The returned ``source`` string says which measurement you
got, so reports never conflate the two.
"""
from __future__ import annotations

import jax


def device_memory_watermark() -> tuple[int, str]:
    """(bytes, source): source is "memory_stats" (allocator watermark) or,
    on the CPU backend only, "live_arrays" (sum of live jax.Array bytes)."""
    dev = jax.local_devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    if peak:
        return int(peak), "memory_stats"
    if dev.platform != "cpu":
        raise RuntimeError(f"{dev} reports no peak_bytes_in_use in memory_stats()")
    return int(sum(x.nbytes for x in jax.live_arrays())), "live_arrays"
