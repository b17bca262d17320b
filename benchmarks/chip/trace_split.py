"""Where inside the step the device time goes, from the profiler trace alone.

``trace_reduce.py`` classes each executed instruction as compute, collective
or host transfer. This splits two of those classes further, over the same
window (the last ``bench.window`` host span) and the same self times:

- **compute by phase of the step.** The profiler stores each executed
  module's optimized HLO in its ``/host:metadata`` plane, with every
  instruction's ``op_name`` metadata. The program names the step's phases
  with ``jax.named_scope``: forward ops carry ``jvp(model)``, backward ops
  (recomputation included) ``transpose(jvp(model))``, the microbatch loop's
  own work (gradient buffers, each microbatch's fold into them)
  ``accumulate``, the optimizer update ``optimizer``; anything else, such
  as copies the compiler adds with no metadata, is ``unscoped``. An ``XLA Ops`` event is
  matched to its module by the ``XLA Modules`` event around it, and to its
  ``op_name`` by instruction name; a name missing from the module's HLO is
  *unresolved* (counted as unscoped and listed on stderr).
- **the host link by direction.** A ``copy-start``/``copy-done`` pair
  between host memory (memory space ``S(5)``) and the device is a
  *writeback* when its result (the destination, first in ``copy-start``'s
  tuple) is in ``S(5)``, else a *fetch*. The TPU's host-offload pass drops
  ``op_name`` from these copies, so the memory space is the only witness.
  On the ``Async XLA Ops`` line each copy's event spans its time in flight,
  ``copy-start`` to ``copy-done``; its bytes come from the destination's
  shape. Per direction: bytes moved, the union of in-flight intervals
  (bytes over it is the link's rate while that direction is busy), and the
  self time of its ``copy-done`` ops (the core's exposed wait); and the
  time both directions are in flight together.

The metric readers (``metrics/step.*_ms.py``, ``metrics/offload.*_gbps.py``)
read the newest trace under ``<checkout>/.bench_trace``, which the harness
has just written, and only when its window is the harness's own.
"""
from __future__ import annotations

import bisect
import functools
import re
import sys
from collections import defaultdict
from pathlib import Path

import trace_reduce as tr

PHASES = ("forward", "backward", "accumulate", "optimizer", "unscoped")
DIRECTIONS = ("fetch", "writeback")
METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
_NAME = re.compile(r"^%?([^\s=]+)\s=\s")
_SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
_DTYPE_BYTES = {"pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "bf16": 2, "f16": 2,
                "s16": 2, "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1}


# -- the HLO the profiler keeps: a minimal protobuf wire-format reader -------
def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited fields, raw bytes for fixed ones."""
    buf = memoryview(buf)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield field, value


def _text(v) -> str:
    return bytes(v).decode()


def _module_op_names(hlo_proto) -> dict[str, str]:
    """Instruction name -> op_name over every computation of an ``HloProto``
    (hlo_module 1 > computations 3 > instructions 2 > name 1, metadata 7 >
    op_name 2)."""
    out = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for mf, comp in _fields(module):
            if mf != 3:
                continue
            for cf, instr in _fields(comp):
                if cf != 2:
                    continue
                name, op_name = None, ""
                for inf, v in _fields(instr):
                    if inf == 1:
                        name = _text(v)
                    elif inf == 7:
                        op_name = next((_text(x) for k, x in _fields(v) if k == 2), "")
                out[name] = op_name
    return out


def hlo_op_names(xspace: bytes) -> dict[str, dict[str, str]]:
    """Module run name, as the ``XLA Modules`` line names it (``jit_step(<id>)``)
    -> instruction name -> ``op_name``, from the ``Hlo Proto`` stats of the
    metadata plane (XSpace planes 1 > XPlane name 2, event_metadata 4,
    stat_metadata 5; XEventMetadata name 2, stats 5; XStat metadata_id 1,
    bytes_value 6)."""
    out: dict[str, dict[str, str]] = {}
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        name, events, stat_names = None, [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = _text(v)
            elif pf == 4:
                events.append(v)
            elif pf == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        if name != METADATA_PLANE:
            continue
        for entry in events:
            meta = list(_fields(dict(_fields(entry)).get(2, b"")))
            ev_name = next((_text(v) for k, v in meta if k == 2), None)
            for k, stat in meta:
                if k != 5:
                    continue
                stat = dict(_fields(stat))
                if stat_names.get(stat.get(1)) == "Hlo Proto" and 6 in stat:
                    out[ev_name] = _module_op_names(stat[6])
    return out


# -- classification --------------------------------------------------------
def phase(op_name: str) -> str:
    parts = op_name.split("/")
    if "transpose(jvp(model))" in parts:
        return "backward"
    if "jvp(model)" in parts:
        return "forward"
    if "accumulate" in parts:
        return "accumulate"
    if "optimizer" in parts:
        return "optimizer"
    return "unscoped"


def shape_bytes(shape: str) -> int:
    """Bytes of an HLO array shape (``f32[18,2048,2048]{...}``)."""
    m = _SHAPE.search(shape)
    n = 1
    for d in filter(None, m.group(2).split(",")):
        n *= int(d)
    return int(n * _DTYPE_BYTES[m.group(1)])


def host_copy(text: str) -> tuple[str, int] | None:
    """(direction, bytes) of a ``copy-start``/``copy-done`` between host
    memory and the device, from its instruction text; None otherwise."""
    if tr.opcode(text) not in ("copy-start", "copy-done") or tr.HOST_SPACE not in text:
        return None
    result = text.split(" = ", 1)[1].lstrip("(")
    dest = result[:result.index("}") + 1]
    return ("writeback" if tr.HOST_SPACE in dest else "fetch"), shape_bytes(dest)


def instr_name(text: str) -> str:
    m = _NAME.match(text)
    return m.group(1) if m else text


# -- the reduction ---------------------------------------------------------
def _window(pd) -> tuple[float, float]:
    spans = [(ev.start_ns, ev.end_ns) for plane in pd.planes
             if not tr.DEVICE_PLANE.match(plane.name) for line in plane.lines
             for ev in line.events if ev.name == tr.WINDOW_SPAN]
    if not spans:
        raise ValueError("trace has no window span")
    return spans[-1]


def _clipped(line, lo, hi):
    for ev in line.events:
        s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
        if e > s:
            yield s, e, ev.name


def split_trace(pd, op_names: dict[str, dict[str, str]]) -> dict:
    """Phase and host-link split of the window of ``jax.profiler.ProfileData``
    ``pd``, given ``hlo_op_names`` of the same trace. Seconds and bytes are
    averaged over the devices, as in ``trace_reduce.reduce_trace``."""
    lo, hi = _window(pd)
    devices = [p for p in pd.planes if tr.DEVICE_PLANE.match(p.name)]
    nd = len(devices)
    phase_s: dict[str, float] = defaultdict(float)
    phase_ops: dict[str, set] = defaultdict(set)
    unresolved: set[str] = set()
    link = {d: {"bytes": 0.0, "copies": 0, "inflight_s": 0.0, "exposed_s": 0.0}
            for d in DIRECTIONS}
    both_ns = 0.0
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        modules = sorted((ev.start_ns, ev.end_ns, ev.name)
                         for ev in getattr(lines.get(MODULES_LINE), "events", ()))
        starts = [m[0] for m in modules]

        def module_of(t):
            k = bisect.bisect_right(starts, t) - 1
            return modules[k][2] if k >= 0 and t < modules[k][1] else None

        ops_line = lines.get(tr.OPS_LINE)
        events = list(_clipped(ops_line, lo, hi)) if ops_line else []
        for start, st, text in tr.self_times(events):
            cls = tr.op_class(text)
            if cls == "host_transfer":
                link[host_copy(text)[0]]["exposed_s"] += st * 1e-9 / nd
            if cls != "compute":
                continue
            name = instr_name(text)
            op_name = op_names.get(module_of(start), {}).get(name)
            if op_name is None:
                unresolved.add(name)
                op_name = ""
            ph = phase(op_name)
            phase_s[ph] += st * 1e-9 / nd
            phase_ops[ph].add(name)
        inflight = defaultdict(list)
        async_line = lines.get(ASYNC_LINE)
        for s, e, text in (_clipped(async_line, lo, hi) if async_line else ()):
            copy = host_copy(text)
            if copy is None:
                continue
            d, nbytes = copy
            inflight[d].append((s, e))
            link[d]["bytes"] += nbytes / nd
            link[d]["copies"] += 1
        unions = {d: tr.union(inflight[d]) for d in DIRECTIONS}
        for d in DIRECTIONS:
            link[d]["inflight_s"] += sum(e - s for s, e in unions[d]) * 1e-9 / nd
        either = tr.union(inflight["fetch"] + inflight["writeback"])
        both_ns += (sum(e - s for u in unions.values() for s, e in u)
                    - sum(e - s for s, e in either)) / nd
    return {
        "window_s": (hi - lo) * 1e-9,
        "phase_s": {p: phase_s.get(p, 0.0) for p in PHASES},
        "phase_ops": {p: len(phase_ops.get(p, ())) for p in PHASES},
        "unresolved": sorted(unresolved),
        "link": link,
        "both_inflight_s": both_ns * 1e-9,
    }


def split_file(path: str) -> dict:
    from jax.profiler import ProfileData

    data = Path(path).read_bytes()
    return split_trace(ProfileData.from_serialized_xspace(data), hlo_op_names(data))


@functools.lru_cache(maxsize=4)
def _split_and_report(path: str) -> dict:
    out = split_file(path)
    ph, link = out["phase_s"], out["link"]
    print("[bench] trace split: compute by phase " + ", ".join(
        f"{p} {ph[p]:.4f} s ({out['phase_ops'][p]} instructions)" for p in PHASES),
        file=sys.stderr)
    print(f"[bench] trace split: {len(out['unresolved'])} unresolved instructions "
          f"{out['unresolved'][:20]}", file=sys.stderr)
    for d in DIRECTIONS:
        x = link[d]
        print(f"[bench] trace split: host {d}: {x['copies']} copies, {x['bytes'] / 1e9:.4f} GB, "
              f"in flight {x['inflight_s']:.4f} s, exposed wait {x['exposed_s']:.4f} s",
              file=sys.stderr)
    print(f"[bench] trace split: both directions in flight {out['both_inflight_s']:.4f} s",
          file=sys.stderr)
    return out


def window_split(reader_file: str, ctx) -> dict | None:
    """The split of the trace the harness just wrote: the newest
    ``.xplane.pb`` under the checkout's ``.bench_trace`` (the checkout is
    three levels above ``metrics/<name>.py``), if its window is the one the
    harness reduced; None otherwise."""
    root = Path(reader_file).resolve().parents[3]
    paths = sorted((root / ".bench_trace").rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        return None
    out = _split_and_report(str(paths[-1]))
    if abs(out["window_s"] - ctx.trace["window_s"]) > 1e-9:
        return None
    return out


def phase_ms(reader_file: str, ctx, name: str) -> float | None:
    """Per step, ms of compute self time in phase ``name``; None where no
    instruction of the window carries that phase's scope."""
    out = window_split(reader_file, ctx)
    if out is None or not out["phase_ops"][name]:
        return None
    return 1e3 * out["phase_s"][name] / ctx.n_steps


def link_gbps(reader_file: str, ctx, direction: str) -> float | None:
    """GB/s of host copies in ``direction`` while that direction is in
    flight; None where the window holds no such copy."""
    out = window_split(reader_file, ctx)
    if out is None or not out["link"][direction]["copies"]:
        return None
    x = out["link"][direction]
    return x["bytes"] / x["inflight_s"] / 1e9
