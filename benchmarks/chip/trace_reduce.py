"""From a profiler trace (``.xplane.pb``) to device metrics.

The window is the host span ``bench.window`` that the harness puts around
the measured steps. On each TPU plane (``/device:TPU:<n>``) the ``XLA Ops``
line holds one event per executed HLO instruction, named by its HLO text
(``%copy-done.15 = f32[18,2048,2048]{...S(5)} copy-done(...)``); a
``while`` event spans the events of its body. So each event's *self* time
(its interval less its children's) is what that instruction itself held
the core for, and self times do not overlap. Instructions are classed by
opcode:

- ``host_transfer``: ``copy-start``/``copy-done`` with an operand or result
  in host memory (memory space ``S(5)``): the core waiting for offloaded
  state to cross the host link;
- ``collective``: all-gather, all-reduce, reduce-scatter, all-to-all,
  collective-permute, their ``-start``/``-done`` halves, and ``async-*``
  wrappers that call one;
- ``compute``: everything else.

Busy time is the union of all events in the window, idle the rest. A
class's exposed time is its self time: time in which the core ran that
class and nothing else. Both are averaged over the devices. Idle gaps are
named by the innermost ``bench.*`` host span open at the gap's midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
HOST_SPACE = "S(5)"
_OPCODE = re.compile(r"=\s.*?\s([a-z][a-z0-9\-]*)\(")
_INSTR = re.compile(r"^%?([A-Za-z_\-]+?)[.\d]*\s=")
_CALLS = re.compile(r"calls=%?([a-z\-]+)")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def opcode(text: str) -> str:
    m = _OPCODE.search(text)
    return m.group(1) if m else text.split(" ", 1)[0]


def op_class(text: str) -> str:
    op = opcode(text)
    if op.startswith("async-"):
        m = _CALLS.search(text)
        op = m.group(1) if m else op
    if op.startswith(_COLLECTIVES):
        return "collective"
    if op in ("copy-start", "copy-done") and HOST_SPACE in text:
        return "host_transfer"
    return "compute"


def short_name(text: str) -> str:
    """Instruction name without its number (``copy-done``, ``fusion``,
    ``convolution_fusion``), with `` host`` for host-memory copies."""
    m = _INSTR.match(text)
    name = m.group(1) if m else opcode(text)
    return name + (" host" if op_class(text) == "host_transfer" else "")


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of union ``a`` not covered by union ``b`` (both sorted)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events) -> list[tuple[float, float, str]]:
    """(start, self seconds-in-ns, text) per event of one ops line, where
    events nest (a parent spans its children)."""
    evs = sorted(events, key=lambda t: (t[0], -t[1]))
    child = [0.0] * len(evs)
    stack: list[int] = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += min(e, evs[stack[-1]][1]) - s
        stack.append(i)
    return [(s, (e - s) - c, t) for (s, e, t), c in zip(evs, child)]


def reduce_trace(pd) -> dict:
    """Metrics of the traced window from ``jax.profiler.ProfileData``."""
    host_spans: list[tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    host_spans.append((ev.start_ns, ev.end_ns, ev.name))
    windows = [(s, e) for s, e, n in host_spans if n == WINDOW_SPAN]
    if not windows or not devices:
        raise ValueError(f"trace has {len(windows)} window spans and {len(devices)} devices")
    lo, hi = windows[-1]
    nd = len(devices)
    busy = 0.0
    per_class: dict[str, float] = defaultdict(float)
    op_time: dict[str, float] = defaultdict(float)
    gaps: list[tuple[float, float]] = []
    for plane in devices:
        events = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
                    if e > s:
                        events.append((s, e, ev.name))
        ops = union((s, e) for s, e, _ in events)
        busy += sum(e - s for s, e in ops) / nd
        gaps += subtract([(lo, hi)], ops)
        for _, st, text in self_times(events):
            per_class[op_class(text)] += st / nd
            op_time[short_name(text)] += st / nd
    spans = sorted((s, e, n) for s, e, n in host_spans if n != WINDOW_SPAN)

    def activity(t):
        inner = [(s, n) for s, e, n in spans if s <= t < e]
        return max(inner)[1] if inner else "none"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    window_ns = hi - lo
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy * 1e-9,
        "idle_share": 1.0 - busy / window_ns,
        "class_s": {c: v * 1e-9 for c, v in per_class.items()},
        "breakdown": {
            "device_ops": [[n, t * 1e-9] for n, t in
                           sorted(op_time.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[activity((s + e) / 2), (e - s) * 1e-9] for s, e in longest],
        },
    }


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData

    return reduce_trace(ProfileData.from_file(path))
