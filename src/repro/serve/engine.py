"""Decode engine: continuous batching over a (resident or paged) decode step.

``DecodeEngine`` owns the compiled decode and prefill steps
(``step_builder.build_decode_step`` / ``build_prefill_step(chunk=C)``), a
``ContinuousScheduler``, and the live cache state. The public surface is the
request API: ``submit(requests)`` queues work, ``run(max_steps=...)`` drives
ticks until drained and returns an ``EngineReport``, ``stream()`` yields
``TokenEvent``s as slots produce tokens, and ``report()`` snapshots metrics
for callers that drive ``step_once()`` themselves (benchmarks/serve_load.py).

Each tick the engine

  1. admits queued requests into free batch slots (zeroing the slots' cache
     rows — mamba state is recurrent and MUST be reset; attention rows are
     reset for hygiene, masking already hides stale rows);
  2. decides prefill vs decode (``scheduler.should_prefill``): under chunked
     admission, prompts are ingested through the chunked-prefill program up
     to ``prefill_chunk`` tokens per slot per call, interleaved with decode
     ticks so at most ``chunk_budget`` consecutive prefill calls ever stall
     an in-flight stream; under ``"whole"`` admission the same program runs
     back-to-back until every prompt is resident (the stall-heavy baseline
     the load harness compares against); ``"replay"`` keeps the legacy
     teacher-forced path — prompt tokens fed one per tick through the decode
     step — as the fallback for attention-free configs;
  3. runs the compiled step (greedy sampling inside the program) and feeds
     the sampled tokens back to the scheduler, which finishes/evicts slots
     and allocates pages crossed into.

The engine is deliberately backend-agnostic: all placement decisions live in
the step artifacts (plan + paging spec), so the same loop drives a fully
HBM-resident cache or the host-paged one.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Iterable, Iterator

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.plan import MemoryPlan
from repro.obs.metrics import quantile as _quantile
from repro.serve.paging import PagingSpec, cache_partition_bytes
from repro.serve.scheduler import ContinuousScheduler, PagePool, Request


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One generated token, as yielded by ``DecodeEngine.stream``."""

    rid: int
    token: int
    index: int  # position in the request's generated sequence
    finished: bool  # True on the request's final token


@dataclasses.dataclass
class EngineReport:
    steps: int
    generated_tokens: int
    finished: dict[int, list[int]]
    rejected: dict[int, list[int]]
    evictions: int
    wall_s: float
    hbm_cache_bytes: int  # device-resident cache bytes (global)
    host_cache_bytes: int  # host-resident cold pages (global)
    resident_cache_bytes: int  # what the fully-resident layout would hold
    drained: bool = True  # False: max_steps hit with requests in flight
    pending: tuple[int, ...] = ()  # rids still queued/running at stop
    truncated: tuple[int, ...] = ()  # rids finished by cache exhaustion
    # -- per-request timing (wall-clock; inherently nondeterministic) --------
    ttft_s: dict[int, float] = dataclasses.field(default_factory=dict)
    request_latency_s: dict[int, float] = dataclasses.field(default_factory=dict)
    itl_s: tuple[float, ...] = ()  # inter-token gaps across all streams
    prefill_ticks: int = 0
    decode_ticks: int = 0
    admission: str = "replay"
    prefill_chunk: int = 0

    @property
    def hbm_reduction(self) -> float:
        """Resident-over-paged device cache footprint (>1 means paging
        freed HBM)."""
        return self.resident_cache_bytes / max(self.hbm_cache_bytes, 1)

    @property
    def p50_latency_s(self) -> float:
        return _quantile(list(self.request_latency_s.values()), 0.50)

    @property
    def p99_latency_s(self) -> float:
        return _quantile(list(self.request_latency_s.values()), 0.99)

    @property
    def p50_ttft_s(self) -> float:
        return _quantile(list(self.ttft_s.values()), 0.50)

    @property
    def p99_ttft_s(self) -> float:
        return _quantile(list(self.ttft_s.values()), 0.99)

    @property
    def p99_itl_s(self) -> float:
        """p99 in-flight decode latency: the tail of the wall-clock gaps
        between consecutive tokens of the same stream — what whole-prompt
        admission inflates and chunked prefill bounds."""
        return _quantile(list(self.itl_s), 0.99)

    def to_dict(self) -> dict:
        """The flat JSON form load harnesses record per mode — field for
        field (and rounding for rounding) what benchmarks/serve_load.py
        writes into BENCH_serve.json, so callers stop re-deriving the
        percentile math (the harness adds only the token checksum)."""
        return {
            "admission": self.admission,
            "prefill_chunk": self.prefill_chunk,
            "drained": self.drained,
            "steps": self.steps,
            "prefill_ticks": self.prefill_ticks,
            "decode_ticks": self.decode_ticks,
            "generated_tokens": self.generated_tokens,
            "finished_requests": len(self.finished),
            "evictions": self.evictions,
            "truncated": len(self.truncated),
            "rejected": len(self.rejected),
            # wall-clock measurements (jitter run to run)
            "wall_s": round(self.wall_s, 6),
            "tokens_per_s": round(
                self.generated_tokens / max(self.wall_s, 1e-9), 3),
            "p50_latency_s": round(self.p50_latency_s, 6),
            "p99_latency_s": round(self.p99_latency_s, 6),
            "p50_ttft_s": round(self.p50_ttft_s, 6),
            "p99_ttft_s": round(self.p99_ttft_s, 6),
            "p99_itl_s": round(self.p99_itl_s, 6),
        }


_HOST_KINDS = ("pinned_host", "unpinned_host")


def _zero_slots(dev, host, mask: jax.Array, *, host_sh):
    """Zero the rows of slots where ``mask`` is True in every cache leaf.

    All decode-cache leaves carry the batch dim at axis 1 — (R, B, ...) —
    for both resident and paged layouts. ``dev`` are the device-resident
    leaves, zeroed by one select each. ``host`` are host-resident cold
    pages: a select there would need all of it in device memory (jax
    rejects mixing memory spaces in one op, and the cold store may exceed
    HBM), so each masked slot gets a device zero block written into the
    host buffer and the result is pinned back to ``host_sh``.
    """

    def zero_dev(x):
        m = mask.reshape((1, -1) + (1,) * (x.ndim - 2))
        return jnp.where(m, jnp.zeros((), x.dtype), x)

    def zero_host(x, sh):
        # the store and one slot's zero block, both in host memory
        x = jax.device_put(x, sh)
        spec = tuple(sh.spec) + (None,) * (x.ndim - len(sh.spec))
        z = jax.device_put(
            jnp.zeros(x.shape[:1] + (1,) + x.shape[2:], x.dtype),
            NamedSharding(sh.mesh, P(spec[0], None, *spec[2:]),
                          memory_kind=sh.memory_kind))
        for b in range(x.shape[1]):
            x = jax.lax.cond(
                mask[b],
                lambda x, b=b: jax.lax.dynamic_update_slice_in_dim(x, z, b, axis=1),
                lambda x: x, x)
        return x

    return [zero_dev(x) for x in dev], [zero_host(x, s) for x, s in zip(host, host_sh)]


class DecodeEngine:
    """``admission`` selects how prompts enter the cache: ``"chunked"``
    (default for attentive configs) interleaves cost-model-sized prefill
    chunks with decode ticks; ``"whole"`` runs the same chunk program to
    completion before decode resumes (the fair stall-heavy baseline);
    ``"replay"`` (default for attention-free configs) teacher-forces the
    prompt through the decode step one token per tick. ``prefill_chunk``
    overrides the cost-model chunk size; ``chunk_budget`` caps consecutive
    prefill ticks while decode-ready streams wait (None = unbounded)."""

    def __init__(
        self,
        cfg: ModelConfig,
        plan: MemoryPlan,
        mesh,
        shape: ShapeConfig,
        params: Any,
        *,
        paging: PagingSpec | None = None,
        own_params: bool = False,
        admission: str | None = None,
        prefill_chunk: int | None = None,
        chunk_budget: int | None = 1,
        hw=None,
        telemetry: obs.Telemetry | None = None,
    ):
        from repro.models import kvcache as KVC
        from repro.train import step_builder as SB

        self.cfg, self.shape, self.paging = cfg, shape, paging
        # the engine's bookkeeping (tick counts, request counters, ITL) IS
        # its metrics registry — EngineReport reads back out of it — so an
        # engine without caller-provided telemetry still runs a real
        # registry (cheap host-side dict), just with span retention off
        tel = telemetry if telemetry is not None else obs.current_telemetry()
        if not tel.enabled:
            tel = obs.Telemetry(trace=False)
        self.tel = tel
        if admission is None:
            admission = "replay" if cfg.attention_free else "chunked"
        assert admission in ("replay", "chunked", "whole"), admission
        self.admission = admission
        self.chunk_budget = None if admission == "whole" else chunk_budget

        self.art = SB.build_decode_step(cfg, plan, mesh, shape,
                                        paging=paging, per_slot_pos=True)
        # the step donates its state (the paged cold store must not double
        # per step), so the engine owns the param buffers: place them per the
        # plan and detach from the caller's copies unless ownership was
        # explicitly handed over (own_params=True, the production path)
        params = jax.tree.map(jax.device_put, params,
                              self.art.state_shardings["params"])
        if not own_params:
            params = jax.tree.map(lambda x: x.copy(), params)
        cache_sh = self.art.state_shardings["cache"]
        if paging is None:
            cache = KVC.init_cache(cfg, shape.global_batch, shape.seq_len)
            cache = jax.tree.map(jax.device_put, cache, cache_sh)
        else:
            from repro.serve.paging import init_paged_cache

            cache = init_paged_cache(cfg, shape.global_batch, shape.seq_len,
                                     paging, shardings=cache_sh)
        self.state = {"params": params, "cache": cache}
        self._step = jax.jit(self.art.fn, donate_argnums=(0,))
        # the reset donates the device leaves only: the CPU backend cannot
        # alias a donated host buffer into the re-pinned output
        sh_flat, self._cache_def = jax.tree.flatten(cache_sh)
        self._host_idx = [i for i, s in enumerate(sh_flat)
                          if s.memory_kind in _HOST_KINDS]
        self._zero = jax.jit(
            functools.partial(_zero_slots,
                              host_sh=[sh_flat[i] for i in self._host_idx]),
            donate_argnums=(0,))

        cache_len = KVC.cache_len(cfg, shape.seq_len)
        if admission != "replay":
            if prefill_chunk is None:
                from repro.core.cost_model import choose_prefill_chunk
                from repro.core.hardware import LOCAL_CPU_HW, MeshSpec

                mspec = MeshSpec(tuple(mesh.devices.shape),
                                 tuple(mesh.axis_names))
                prefill_chunk = choose_prefill_chunk(
                    cfg, shape, mspec, hw or LOCAL_CPU_HW, spec=paging,
                    max_chunk=paging.page_size if paging else cache_len)
            self.prefill_chunk = max(1, min(int(prefill_chunk), cache_len))
            prefill_art = SB.build_prefill_step(
                cfg, plan, mesh, shape, chunk=self.prefill_chunk, paging=paging)
            self._prefill = jax.jit(prefill_art.fn, donate_argnums=(0,))
        else:
            self.prefill_chunk = 0
            self._prefill = None

        page_size = paging.page_size if paging else cache_len
        n_pages_per_slot = -(-cache_len // page_size)
        self.scheduler = ContinuousScheduler(
            n_slots=shape.global_batch,
            pool=PagePool(n_pages_per_slot * shape.global_batch),
            page_size=page_size,
            cache_len=cache_len,
            # ring caches (SWA) and O(1)-state models decode past the cache
            # length by slot reuse; full attention runs out of slots there
            allow_wrap=bool(cfg.sliding_window) or cfg.attention_free,
            registry=tel.registry,
        )
        # tick accounting lives in the registry (serve.ticks total plus the
        # phase-labeled split); `ticks`/`prefill_ticks`/`decode_ticks` below
        # are read-back properties over these counters
        reg = tel.registry
        self._c_ticks = reg.counter("serve.ticks")
        self._c_prefill_ticks = reg.counter("serve.ticks", phase="prefill")
        self._c_decode_ticks = reg.counter("serve.ticks", phase="decode")
        self._c_gen = reg.counter("serve.generated_tokens")
        self._h_itl = reg.histogram("serve.itl_s")
        self._c_fetch = reg.counter("serve.page_fetches")
        self._c_h2d = reg.counter("serve.h2d_bytes")
        # paged decode moves cold pages over the host link *inside* the
        # jitted step, so the traffic is priced statically (the same
        # inventory the cost model's t_page_fetch uses) and accounted per
        # decode tick
        if paging is not None:
            from repro.core.cost_model import (
                _attn_layer_count, page_fetch_bytes_per_step)
            from repro.core.hardware import MeshSpec

            mspec = MeshSpec(tuple(mesh.devices.shape),
                             tuple(mesh.axis_names))
            self._h2d_per_tick = int(
                page_fetch_bytes_per_step(cfg, shape, mspec, paging))
            self._fetches_per_tick = paging.n_cold * _attn_layer_count(cfg)
        else:
            self._h2d_per_tick = 0
            self._fetches_per_tick = 0
        # request-level timing (wall clock)
        self._consec_prefill = 0
        self._t0: float | None = None
        self._t_submit: dict[int, float] = {}
        self._t_first: dict[int, float] = {}
        self._t_finish: dict[int, float] = {}
        self._t_last_tok: dict[int, float] = {}
        self._gen_count: dict[int, int] = {}
        self._itl: list[float] = []

    # -- registry-backed tick accounting --------------------------------------
    # (writable only through the counters; the report is a view over them)
    @property
    def ticks(self) -> int:
        return int(self._c_ticks.value)

    @property
    def prefill_ticks(self) -> int:
        return int(self._c_prefill_ticks.value)

    @property
    def decode_ticks(self) -> int:
        return int(self._c_decode_ticks.value)

    # -- request API ---------------------------------------------------------
    def warmup(self) -> None:
        """Compile the decode (and prefill) programs ahead of traffic by
        running each once with an all-inactive batch — the active mask
        suppresses every cache write, so live state is untouched. Load
        harnesses call this so first-request latency measures the step,
        not the XLA compile."""
        bsz = self.shape.global_batch
        z = jnp.zeros((bsz,), jnp.int32)
        batch = {"tokens": z[:, None], "pos": z,
                 "active": jnp.zeros((bsz,), bool)}
        self.state, _ = self._step(self.state, batch)
        if self._prefill is not None:
            pb = {"tokens": jnp.zeros((bsz, self.prefill_chunk), jnp.int32),
                  "pos": z, "n_tok": z}
            self.state, _ = self._prefill(self.state, pb)
        self.state["cache"] = self._reset(self.state["cache"],
                                          jnp.zeros((bsz,), bool))

    def _reset(self, cache, mask: jax.Array):
        """Zero the cache rows of the slots in ``mask`` (see _zero_slots)."""
        leaves = self._cache_def.flatten_up_to(cache)
        host = set(self._host_idx)
        dev, hst = self._zero([x for i, x in enumerate(leaves) if i not in host],
                              [leaves[i] for i in self._host_idx], mask)
        dev, hst = iter(dev), iter(hst)
        return self._cache_def.unflatten(
            [next(hst) if i in host else next(dev) for i in range(len(leaves))])

    def submit(self, requests: Iterable[Request]) -> None:
        """Queue requests; admission happens on subsequent ticks."""
        now = time.time()
        if self._t0 is None:
            self._t0 = now
        reqs = list(requests)
        self.scheduler.submit(reqs)
        for r in reqs:
            self._t_submit.setdefault(r.rid, now)

    def step_once(self) -> None:
        """One engine tick: admit, then one prefill chunk or one decode step
        (``scheduler.should_prefill`` arbitrates under chunked admission)."""
        sched = self.scheduler
        admitted = sched.admit()
        if admitted:
            mask = jnp.zeros((self.shape.global_batch,), bool)
            mask = mask.at[jnp.asarray(admitted)].set(True)
            self.state["cache"] = self._reset(self.state["cache"], mask)
        if (self._prefill is not None
                and sched.should_prefill(self._consec_prefill, self.chunk_budget)):
            with self.tel.tracer.span("serve.prefill_tick"):
                self._prefill_tick()
            self._consec_prefill += 1
        else:
            with self.tel.tracer.span("serve.decode_tick"):
                self._decode_tick()
            self._consec_prefill = 0
        self._c_ticks.inc()
        self._note_progress()

    # retained alias: one tick of the pre-redesign surface
    tick = step_once

    def run(self, requests: Iterable[Request] | None = None,
            max_steps: int = 10_000) -> EngineReport:
        """Drive ticks until drained (or ``max_steps``); returns the report."""
        if requests is not None:
            self.submit(requests)
        sched = self.scheduler
        steps = 0
        while not sched.idle and steps < max_steps:
            self.step_once()
            steps += 1
        return self.report(steps=steps)

    def stream(self, requests: Iterable[Request] | None = None,
               max_steps: int = 10_000) -> Iterator[TokenEvent]:
        """Tick the engine, yielding each generated token as a TokenEvent.

        Tokens are emitted in tick order, interleaved across requests
        (continuous batching). An evicted request's replayed tokens are not
        re-emitted — greedy decode regenerates them identically."""
        if requests is not None:
            self.submit(requests)
        sched = self.scheduler
        emitted: dict[int, int] = {}

        def drain() -> Iterator[TokenEvent]:
            live = {s.rid: (s.generated, False)
                    for s in sched.slots if s is not None}
            done = {rid: (toks, True) for rid, toks in sched.finished.items()}
            for rid, (toks, fin) in {**live, **done}.items():
                start = emitted.get(rid, 0)
                for i in range(start, len(toks)):
                    yield TokenEvent(rid, int(toks[i]), i,
                                     fin and i == len(toks) - 1)
                emitted[rid] = max(start, len(toks))

        steps = 0
        while not sched.idle and steps < max_steps:
            self.step_once()
            steps += 1
            yield from drain()

    # -- internal ticks -------------------------------------------------------
    def _decode_tick(self) -> None:
        sched = self.scheduler
        toks, poss, active = sched.step_inputs(
            replay_prefill=self.admission == "replay")
        if not any(active):
            return  # every occupied slot is mid-prefill: nothing to decode
        batch = {
            "tokens": jnp.asarray(toks, jnp.int32)[:, None],
            "pos": jnp.asarray(poss, jnp.int32),
            "active": jnp.asarray(active),
        }
        self.state, nxt = self._step(self.state, batch)
        sched.advance([int(t) for t in jax.device_get(nxt)], active)
        self._c_decode_ticks.inc()
        if self._fetches_per_tick:
            self._c_fetch.inc(self._fetches_per_tick)
            self._c_h2d.inc(self._h2d_per_tick)

    def _prefill_tick(self) -> None:
        sched = self.scheduler
        chunk = self.prefill_chunk
        bsz = self.shape.global_batch
        # page up BEFORE any cache write, so pool-pressure evictions and
        # rejections land before the chunk runs (an evicted slot restarts
        # from its prompt; its partial rows are zeroed on re-admission)
        for b in list(sched.prefill_slots()):
            s = sched.slots[b]
            if s is None:
                continue
            sched.ensure_pages(b, s.length + min(chunk, sched.prefill_budget(b)))
        # assemble AFTER all ensures: an ensure may have evicted another
        # prefill candidate, and a half-assembled batch would feed its rows
        toks = [[0] * chunk for _ in range(bsz)]
        pos = [0] * bsz
        n_tok = [0] * bsz
        for b in sched.prefill_slots():
            s = sched.slots[b]
            n_b = min(chunk, sched.prefill_budget(b))
            if n_b <= 0:
                continue
            toks[b][:n_b] = s.prompt[s.length:s.length + n_b]
            pos[b] = s.length
            n_tok[b] = n_b
        if not any(n_tok):
            return
        batch = {
            "tokens": jnp.asarray(toks, jnp.int32),
            "pos": jnp.asarray(pos, jnp.int32),
            "n_tok": jnp.asarray(n_tok, jnp.int32),
        }
        self.state, nxt = self._prefill(self.state, batch)
        sched.advance_prefill(n_tok, [int(t) for t in jax.device_get(nxt)])
        self._c_prefill_ticks.inc()

    # -- timing ---------------------------------------------------------------
    def _note_progress(self) -> None:
        now = time.time()
        sched = self.scheduler
        counts = {rid: len(toks) for rid, toks in sched.finished.items()}
        counts.update({s.rid: len(s.generated)
                       for s in sched.slots if s is not None})
        for rid, n in counts.items():
            seen = self._gen_count.get(rid, 0)
            if n > seen:
                self._c_gen.inc(n - seen)
                if rid not in self._t_first and rid in self._t_submit:
                    self._t_first[rid] = now
                if rid in self._t_last_tok:
                    # a gap per tick that produced tokens for this stream —
                    # the in-flight latency chunked prefill exists to bound
                    gap = now - self._t_last_tok[rid]
                    self._itl.append(gap)
                    self._h_itl.observe(gap)
                self._t_last_tok[rid] = now
                self._gen_count[rid] = n
            elif n < seen:
                self._gen_count[rid] = n  # evicted: replaying from scratch
        for rid in sched.finished:
            self._t_finish.setdefault(rid, now)
        for rid in sched.rejected:
            self._t_finish.setdefault(rid, now)

    # -- reporting -------------------------------------------------------------
    def report(self, steps: int | None = None) -> EngineReport:
        """Metrics snapshot — callable mid-flight by harnesses that drive
        ``step_once`` themselves."""
        sched = self.scheduler
        parts = cache_partition_bytes(
            self.cfg, self.shape.global_batch, self.shape.seq_len, self.paging)
        resident = cache_partition_bytes(
            self.cfg, self.shape.global_batch, self.shape.seq_len, None)
        pending = tuple(sorted(
            {r.rid for r in sched.queue}
            | {s.rid for s in sched.slots if s is not None}))
        t0 = self._t0 if self._t0 is not None else time.time()
        latency = {rid: self._t_finish[rid] - self._t_submit[rid]
                   for rid in self._t_finish if rid in self._t_submit}
        ttft = {rid: self._t_first[rid] - self._t_submit[rid]
                for rid in self._t_first if rid in self._t_submit}
        return EngineReport(
            drained=sched.idle,
            pending=pending,
            truncated=tuple(sorted(sched.truncated)),
            steps=self.ticks if steps is None else steps,
            generated_tokens=sum(len(v) for v in sched.finished.values()),
            finished=dict(sched.finished),
            rejected=dict(sched.rejected),
            evictions=sched.evictions,
            wall_s=time.time() - t0,
            hbm_cache_bytes=parts["hbm"] + parts["transient"],
            host_cache_bytes=parts["host"],
            resident_cache_bytes=resident["hbm"],
            ttft_s=ttft,
            request_latency_s=latency,
            itl_s=tuple(self._itl),
            prefill_ticks=self.prefill_ticks,
            decode_ticks=self.decode_ticks,
            admission=self.admission,
            prefill_chunk=self.prefill_chunk,
        )
