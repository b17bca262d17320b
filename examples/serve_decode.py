"""Streaming serving demo: submit a prompt batch to the decode engine and
consume tokens as ``TokenEvent``s while requests are still in flight.

    PYTHONPATH=src python examples/serve_decode.py

Prompts enter the cache through the chunked-prefill program (one compiled
``lax.scan`` of decode steps per chunk — see docs/serving.md §5) interleaved
with decode ticks, so the first request starts streaming before the last
prompt has finished ingesting. Compare examples/serve_lm.py, which drives
``run()`` to completion and reports aggregate latency percentiles.
"""
import time

import jax

from repro.configs import get_config, reduced
from repro.configs.base import ShapeConfig
from repro.core.plan import MemoryPlan
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.serve import DecodeEngine, Request

B, PROMPT, GEN = 4, 32, 16

cfg = reduced(get_config("mixtral-8x22b"))
mesh = make_local_mesh()
shape = ShapeConfig("serve", PROMPT + GEN, B, "decode")
plan = MemoryPlan(n_chunks=4, n_blocks=2, n_persist=4)

params = M.init_params(cfg, jax.random.PRNGKey(0))
engine = DecodeEngine(cfg, plan, mesh, shape, params)

prompts = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 1, cfg.vocab_size)
engine.submit([Request(i, [int(t) for t in prompts[i]], GEN) for i in range(B)])

t0 = time.time()
streams: dict[int, list[int]] = {}
for ev in engine.stream():
    streams.setdefault(ev.rid, []).append(ev.token)
    if ev.finished:
        print(f"req {ev.rid} finished at +{time.time() - t0:.2f}s "
              f"({len(streams[ev.rid])} tokens)")

report = engine.report()
dt = max(report.wall_s, 1e-9)
print(f"decoded {report.generated_tokens} tokens x {B} seqs in {dt:.2f}s "
      f"({report.generated_tokens / dt:.1f} tok/s on CPU; "
      f"{report.prefill_ticks} prefill chunks of {report.prefill_chunk}, "
      f"{report.decode_ticks} decode ticks)")
print("sample token ids:", streams[0][:16])
