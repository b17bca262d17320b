"""The host->device fetches of host-resident layer weights, from the profiler trace.

A plan with host-resident parameters keeps a run's stacked bf16 layer
weights in host memory, and the layer scan fetches one layer at a time
(``models/model.gather_weights``, scope ``param_fetch``): in each
microbatch's forward, and again in the backward of a run that does not keep
them (the plan's ``n_buffer``). Compiled, such a fetch is the scan's slice
of the host buffer, which the TPU's host-offload pass turns into a
``dynamic-slice-start``/``dynamic-slice-done`` pair reading host memory
(``S(5)``). Its ``op_name`` is the scan's (``.../jvp(model)/while/body/
dynamic_slice``): the pass keeps none of ``param_fetch``. So a fetch is
known by

- its opcode, from the instruction's name (``dynamic-slice-start``,
  ``-done``) and its operand in host memory;
- its ``op_name``, under the ``model`` scope (forward or backward, as
  ``trace_split.phase`` reads it): the streamed Adam update slices host
  buffers too, under ``optimizer``;
- its shape: one layer of a weight matrix of the configuration
  (``weights.layer_leaves``) in the parameter dtype. The backward reads
  swapped activations back from host memory by the same kind of slice,
  inside the model scope too.

Per device, over the harness's window (the last ``bench.window`` host
span): the fetches' bytes (from the ``-done``'s result shape), the union of
their in-flight intervals (each ``-start``'s begin to its ``-done``'s end,
paired in order), and the self time of their ``-done`` ops on the
``XLA Ops`` line, the core's exposed wait; averaged over devices, as
``trace_reduce`` does. ``metrics/param_fetch.*.py`` read it.
"""
from __future__ import annotations

import functools
import re
import sys
from collections import defaultdict, deque
from pathlib import Path

import trace_reduce as tr
import trace_split as ts
from weights import layer_leaves

START, DONE = "dynamic-slice-start", "dynamic-slice-done"
_OPERAND = re.compile(r"%(" + START + r"[\w.\-]*)\)")
_RESULT = re.compile(r"=\s*([a-z]+\d*)\[([\d,]*)\]")
_DTYPES = {"bfloat16": "bf16", "float32": "f32"}


def weight_slices(cfg: dict) -> set[tuple[str, tuple[int, ...]]]:
    """(HLO dtype, shape) of one layer of each weight matrix of a layer of
    ``cfg``: the leaves a host run fetches a layer at a time (its ``[L, d]``
    norms stay in HBM, ``dist/sharding.host_layer_sliceable``)."""
    dtype = _DTYPES[cfg["param_dtype"]]
    return {(dtype, (1, *leaf.shape)) for leaf in layer_leaves(cfg) if len(leaf.shape) >= 2}


def _result(text: str):
    m = _RESULT.search(text)
    if m is None:
        return None
    return m.group(1), tuple(int(d) for d in m.group(2).split(",") if d)


def reduce_trace(pd, op_names: dict[str, dict[str, str]], cfg: dict) -> dict:
    """Weight fetches in the window of ``jax.profiler.ProfileData`` ``pd``,
    given ``trace_split.hlo_op_names`` of the same trace."""
    lo, hi = ts._window(pd)
    wanted = weight_slices(cfg)
    devices = [p for p in pd.planes if tr.DEVICE_PLANE.match(p.name)]
    nd = len(devices)
    out = {"window_s": (hi - lo) * 1e-9, "fetches": 0, "bytes": 0.0, "wait_s": 0.0,
           "inflight_s": 0.0, "by_phase": defaultdict(int)}
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        modules = sorted((ev.start_ns, ev.end_ns, ev.name)
                         for ev in getattr(lines.get(ts.MODULES_LINE), "events", ()))

        def module_of(t):
            inner = [m for m in modules if m[0] <= t < m[1]]
            return inner[-1][2] if inner else None

        ops_line = lines.get(tr.OPS_LINE)
        events = list(ts._clipped(ops_line, lo, hi)) if ops_line else []
        starts: dict[tuple, deque] = defaultdict(deque)
        intervals = []
        for begin, _end, text in sorted(events):
            name = ts.instr_name(text)
            if name.startswith(START):
                starts[(module_of(begin), name)].append(begin)
        ends = {(b, t): e for b, e, t in events}
        for begin, self_ns, text in tr.self_times(events):
            name = ts.instr_name(text)
            if not name.startswith(DONE) or tr.HOST_SPACE not in text:
                continue
            module = module_of(begin)
            phase = ts.phase(op_names.get(module, {}).get(name, ""))
            result = _result(text)
            m = _OPERAND.search(text)
            if phase not in ("forward", "backward") or result not in wanted or m is None:
                continue
            queue = starts[(module, m.group(1))]
            if not queue:
                continue  # its start fell before the window
            intervals.append((queue.popleft(), ends[(begin, text)]))
            out["fetches"] += 1 / nd
            out["by_phase"][phase] += 1 / nd
            out["bytes"] += ts.shape_bytes(text.split("=", 1)[1]) / nd
            out["wait_s"] += self_ns * 1e-9 / nd
        out["inflight_s"] += sum(e - s for s, e in tr.union(intervals)) * 1e-9 / nd
    out["by_phase"] = dict(out["by_phase"])
    return out


def reduce_file(path: str, cfg: dict) -> dict:
    from jax.profiler import ProfileData

    data = Path(path).read_bytes()
    return reduce_trace(ProfileData.from_serialized_xspace(data), ts.hlo_op_names(data), cfg)


@functools.lru_cache(maxsize=4)
def _reduce_and_report(path: str, cfg_key: tuple) -> dict:
    out = reduce_file(path, dict(cfg_key))
    print(f"[bench] param fetch: {out['fetches']:.0f} fetches {out['by_phase']}, "
          f"{out['bytes'] / 1e9:.4f} GB, in flight {out['inflight_s']:.4f} s, exposed wait "
          f"{out['wait_s']:.4f} s", file=sys.stderr)
    return out


def window_fetches(reader_file: str, ctx) -> dict | None:
    """The fetches of the trace the harness just wrote (the newest
    ``.xplane.pb`` under the checkout's ``.bench_trace``, three levels above
    ``metrics/<name>.py``), if its window is the harness's and it holds
    any; None otherwise."""
    root = Path(reader_file).resolve().parents[3]
    paths = sorted((root / ".bench_trace").rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        return None
    cfg_key = tuple(sorted((k, v) for k, v in ctx.cfg.items()
                           if isinstance(v, (str, int, float, bool))))
    out = _reduce_and_report(str(paths[-1]), cfg_key)
    if abs(out["window_s"] - ctx.trace["window_s"]) > 1e-9 or not out["fetches"]:
        return None
    return out
