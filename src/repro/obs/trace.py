"""Structured span tracer: nestable wall-clock spans on the profiler's clock.

``tracer.span("plan.compile")`` is a context manager; spans nest through a
per-thread stack so concurrent engine/loop threads interleave without
locking the hot path (only the shared event list append is locked). Each
retained event carries an ``id`` and the ``parent`` id of the span that
enclosed it on its thread (None at the top).

Every span also opens a ``jax.profiler.TraceAnnotation`` of its own name
(a ``StepTraceAnnotation`` when it has a ``step`` attribute), whether or not
the tracer retains events, so under a ``jax.profiler`` session the span
lands in the device trace beside the device's operations. With no
profiler session an annotation costs about a microsecond.

``write_chrome_trace(path)`` / ``to_chrome_trace()`` export the retained
spans as Chrome trace-event JSON (``{"traceEvents": [...]}``) that Perfetto
and ``chrome://tracing`` load directly: complete ("ph": "X") events with
microsecond ``ts``/``dur`` and process/thread-name metadata ("ph": "M").

Disabled tracers still *measure* (two ``perf_counter`` reads — the span
object's ``dur_s`` is always valid, which is what lets benchmark drivers use
one clock for their own reporting) but retain nothing.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time

from jax.profiler import StepTraceAnnotation, TraceAnnotation


class Span:
    """One timed region. ``dur_s`` is valid after the ``with`` block exits
    whether or not the tracer retains events."""

    __slots__ = ("name", "attrs", "t0_s", "dur_s", "id", "parent", "_tracer",
                 "_annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.t0_s = 0.0
        self.dur_s = 0.0
        self.id = next(tracer._ids)
        self.parent: int | None = None

    def __enter__(self) -> "Span":
        stack = self._tracer._stack_of(threading.get_ident())
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        step = self.attrs.get("step")
        self._annotation = (TraceAnnotation(self.name) if step is None
                            else StepTraceAnnotation(self.name, step_num=int(step)))
        self._annotation.__enter__()
        self.t0_s = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.dur_s = time.perf_counter() - self.t0_s
        self._annotation.__exit__(exc_type, exc, tb)
        stack = self._tracer._stack_of(threading.get_ident())
        if stack and stack[-1] is self:
            stack.pop()
        self._tracer._record(self)


class Tracer:
    """Span recorder. ``enabled=False`` keeps the timing contract (and the
    profiler annotations) but drops every event (the no-op used when
    telemetry is off)."""

    def __init__(self, enabled: bool = True, max_events: int = 1 << 18):
        self.enabled = enabled
        self.max_events = max_events
        self.events: list[dict] = []
        self._lock = threading.Lock()
        self._stacks: dict[int, list] = {}
        self._ids = itertools.count()
        self._epoch = time.perf_counter()

    def _stack_of(self, tid: int) -> list:
        got = self._stacks.get(tid)
        if got is None:
            got = self._stacks.setdefault(tid, [])
        return got

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def _record(self, span: Span) -> None:
        if not self.enabled:
            return
        ev = {"name": span.name, "ph": "X",
              "ts_s": span.t0_s - self._epoch, "dur_s": span.dur_s,
              "tid": threading.get_ident(), "id": span.id, "parent": span.parent,
              "args": span.attrs}
        with self._lock:
            if len(self.events) < self.max_events:
                self.events.append(ev)

    # -- export ---------------------------------------------------------------
    def to_chrome_trace(self, process_name: str = "repro") -> dict:
        """Chrome trace-event format: ``ts``/``dur`` in microseconds,
        complete events per span, thread-name metadata per seen thread."""
        with self._lock:
            events = list(self.events)
        tids = sorted({e["tid"] for e in events})
        tid_ix = {t: i for i, t in enumerate(tids)}
        out = [{"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
                "args": {"name": process_name}}]
        for t in tids:
            out.append({"name": "thread_name", "ph": "M", "pid": 0,
                        "tid": tid_ix[t], "args": {"name": f"thread-{tid_ix[t]}"}})
        for e in events:
            rec = {"name": e["name"], "ph": e["ph"], "pid": 0,
                   "tid": tid_ix[e["tid"]],
                   "ts": round(e["ts_s"] * 1e6, 3),
                   "dur": round(e["dur_s"] * 1e6, 3)}
            if e["args"]:
                rec["args"] = dict(e["args"])
            out.append(rec)
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path: str, process_name: str = "repro") -> str:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(process_name), f)
            f.write("\n")
        return path


NULL_TRACER = Tracer(enabled=False)
