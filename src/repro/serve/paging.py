"""Page-table KV cache: hot window in HBM, cold pages in host memory.

Each attention position's (B, S, n_kv, hd) decode cache is split along the
sequence dimension into fixed-size pages. Two physical stores back it:

  * ``k_hot``/``v_hot`` — an HBM ring of the last ``hot_window`` slots
    (``n_hot`` pages). Every decoded token is written here at
    ``slot % hot_window``, so the most recent pages are always servable
    without touching the host link.
  * ``k_cold``/``v_cold`` — the canonical full cache in host memory
    (``compat.host_memory_kind``), written through every step (a one-token
    update). Cold is always correct, which is what makes eviction implicit:
    a hot ring row may be overwritten ``hot_window`` steps later without any
    flush, because the canonical value already lives in cold.

At attention time the per-layer full cache is reconstructed page by page
inside the decode repeat scan (the serving twin of ``Run.lazy_gather``'s
per-chunk weight gathers): pages inside the hot window are static slices of
the HBM ring; pages outside it are fetched h2d with ``jax.device_put`` under
``lax.cond``, double-buffered — each fetch is ordered after the page-before-
last via ``optimization_barrier`` so at most two transfers are in flight and
XLA cannot hoist the fetch pipeline out of the scan (the same anti-hoist
rationale as ``models.model.gather_weights``).

Exactness: the gathered cache equals the resident cache *elementwise on every
attended slot*. Hot-ring rows belonging to masked slots may hold stale tokens
(ring reuse), but the decode mask is additive ``NEG_INF`` — their softmax
weight underflows to exactly 0.0 in fp32, so paged logits are bit-identical
to resident logits (tests/test_serve_paging.py asserts zero difference).

Mamba positions carry O(1) recurrent state and stay fully HBM-resident, as
does encoder-decoder cross-attention K/V (prefill-computed, read-only).

The ring-correctness invariant requires ``n_pages % n_hot == 0`` for
sliding-window (ring) caches — a page and the hot slot it maps to must agree
on which logical page is the most recently written one; ``choose_paging``
enforces the divisibility.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import kvcache as KV


@dataclasses.dataclass(frozen=True)
class PagingSpec:
    """Page geometry for one serve configuration.

    ``n_hot`` counts hot (HBM-resident) pages; the remaining
    ``n_pages - n_hot`` cold pages are what ``MemoryPlan.n_host`` records for
    serve plans (core/serve_plan.py).
    """

    page_size: int  # tokens per page (P)
    n_pages: int  # pages spanning the cache length
    n_hot: int  # pages of the hot window (>= 1, divides n_pages)

    def __post_init__(self):
        assert self.page_size >= 1 and self.n_pages >= 1
        assert 1 <= self.n_hot <= self.n_pages
        assert self.n_pages % self.n_hot == 0, (
            "hot window must tile the page ring (SWA ring-slot correctness)")

    @property
    def cache_len(self) -> int:
        return self.page_size * self.n_pages

    @property
    def hot_window(self) -> int:
        return self.page_size * self.n_hot

    @property
    def n_cold(self) -> int:
        return self.n_pages - self.n_hot


def choose_paging(cache_len: int, page_size: int, n_hot: int) -> PagingSpec:
    """Clamp (page_size, n_hot) to a valid spec for ``cache_len``.

    page_size is reduced to the largest divisor of ``cache_len`` not
    exceeding the request; n_hot to the largest divisor of the resulting
    page count. Keeps planner searches total — every request maps to some
    legal geometry.
    """
    page_size = max(1, min(page_size, cache_len))
    while cache_len % page_size:
        page_size -= 1
    n_pages = cache_len // page_size
    n_hot = max(1, min(n_hot, n_pages))
    while n_pages % n_hot:
        n_hot -= 1
    return PagingSpec(page_size=page_size, n_pages=n_pages, n_hot=n_hot)


# ---------------------------------------------------------------------------
# Paged cache pytrees
# ---------------------------------------------------------------------------
def paged_cache_specs(cfg: ModelConfig, batch: int, seq_len: int,
                      spec: PagingSpec) -> dict:
    """ShapeDtypeStruct pytree for the paged decode cache.

    Attention positions split into hot ring + cold store; mamba (and encdec
    cross-attention) entries are identical to the resident layout.
    """
    base = KV.cache_specs(cfg, batch, seq_len)
    assert spec.cache_len == KV.cache_len(cfg, seq_len), (
        f"paging spec covers {spec.cache_len} slots, cache has "
        f"{KV.cache_len(cfg, seq_len)}")
    out: dict[str, Any] = {}
    for pos, entry in base.items():
        if "k" not in entry:
            out[pos] = dict(entry)
            continue
        kv = entry["k"]  # (R, B, S, n_kv, hd)
        r, b, _, n_kv, hd = kv.shape
        hot = jax.ShapeDtypeStruct((r, b, spec.hot_window, n_kv, hd), kv.dtype)
        new = {"k_hot": hot, "v_hot": hot, "k_cold": kv, "v_cold": kv}
        for extra in ("xk", "xv"):  # encdec cross-attention stays resident
            if extra in entry:
                new[extra] = entry[extra]
        out[pos] = new
    return out


def init_paged_cache(cfg: ModelConfig, batch: int, seq_len: int,
                     spec: PagingSpec, shardings=None):
    """Zeros matching ``paged_cache_specs``; ``shardings`` (same pytree of
    NamedSharding) places cold leaves in host memory."""
    specs = paged_cache_specs(cfg, batch, seq_len, spec)
    zeros = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    if shardings is None:
        return zeros
    return jax.tree.map(jax.device_put, zeros, shardings)


def paged_to_resident(cache: dict) -> dict:
    """Resident-layout view of a paged cache.

    Under write-through (``PagedKV(flush=False)``) cold is canonical at every
    step. Under page-boundary flush (the default) cold is canonical for every
    *completed* page; each slot's current write page is only in the hot ring
    until the slot crosses the next page boundary.
    """
    out = {}
    for pos, entry in cache.items():
        if "k_cold" not in entry:
            out[pos] = dict(entry)
            continue
        new = {"k": entry["k_cold"], "v": entry["v_cold"]}
        for extra in ("xk", "xv"):
            if extra in entry:
                new[extra] = entry[extra]
        out[pos] = new
    return out


# ---------------------------------------------------------------------------
# Decode-time cache I/O (the kv_io hook of models.kvcache.decode_step)
# ---------------------------------------------------------------------------
class PagedKV:
    """Paged cache I/O for one decode step.

    ``fetch_sharding`` (optional NamedSharding of one fetched page,
    device-memory) makes the h2d fetch an explicit op inside the scan; when
    None the transfer is left to XLA's memory-space propagation (tests that
    construct PagedKV without a mesh).

    ``flush`` selects the cold-store write policy. ``True`` (default) is the
    page-boundary flush of docs/serving.md §5: the hot ring is the only
    per-token write target, and a completed page is copied hot→cold once per
    ``page_size`` steps — one d2h burst per page instead of a one-token d2h
    every step. ``False`` keeps the original write-through (cold updated
    every token), retained as the reference policy the flush equivalence
    test compares against.

    ``use_kernel`` selects the attention path the ``attend`` hook takes
    (docs/kernels.md). ``None`` (default) auto-resolves: the fused Pallas
    paged-attention kernel when the package dispatches to Pallas *and* the
    stores are device-visible (``fetch_sharding is None``); the lax
    gather-then-attend rebuild otherwise. The explicit-sharding exclusion is
    deliberate: the step-builder path pins cold leaves in host memory and
    shards the cache under GSPMD, and a ``pallas_call`` neither partitions
    under GSPMD nor reads a host memory space — there the double-buffered
    per-page fetch pipeline *is* the right engine (and the h2d calibration
    census depends on its lowered form). ``True``/``False`` force the path
    (differential tests drive both sides of the parity contract).
    """

    entry_keys = ("k_hot", "v_hot", "k_cold", "v_cold")

    def __init__(self, spec: PagingSpec, fetch_sharding=None,
                 flush: bool = True, use_kernel: bool | None = None):
        self.spec = spec
        self.fetch_sharding = fetch_sharding
        self.flush = flush
        if use_kernel is None:
            use_kernel = fetch_sharding is None
        self.use_kernel = use_kernel

    # -- page residency -----------------------------------------------------
    def _hot_mask(self, wp: jax.Array, p: int, sliding: bool) -> jax.Array:
        """Is logical page ``p`` fully servable from the hot ring for a slot
        at write page ``wp``? Shape follows ``wp`` (scalar, or (B,) per-slot).

        Full attention: the last ``n_hot`` pages including the current write
        page (its unwritten rows are masked, so stale ring content there is
        invisible). Sliding-window ring caches differ in steady state: every
        cache slot is *valid*, and the current write page's not-yet-rewritten
        slots hold values from one ring cycle ago — older than the hot
        window — so only the ``n_hot - 1`` most recent *fully written* pages
        are servable; the write page itself needs cold rows (all of them
        under write-through; the not-yet-rewritten tail under flush).
        """
        s = self.spec
        if sliding:
            d = (wp - p) % s.n_pages
            return (d >= 1) & (d < s.n_hot)
        return (wp >= p) & (wp - p < s.n_hot)

    def _page_is_hot(self, wp: jax.Array, p: int, sliding: bool) -> jax.Array:
        """Scalar ALL-reduction of ``_hot_mask`` (a page is fetched unless
        hot for every batch row)."""
        return jnp.all(self._hot_mask(wp, p, sliding))

    def _take_hot_rows(self, wp: jax.Array, slot: jax.Array, p: int,
                       sliding: bool) -> jax.Array:
        """Flush-mode row-level residency of page ``p``: True where the hot
        ring holds the canonical value, False where cold does.

        Full attention: the write page has no canonical cold copy (it is
        flushed only on completion), so the whole hot window — write page
        included — serves from the ring; unwritten rows are masked. Sliding
        rings additionally split the write page by row: rows the current
        cycle already rewrote (``row <= slot % P``) live in the ring, the
        remaining rows still hold *last* cycle's values, flushed to cold when
        that cycle completed the page.

        Returns a rank-2 mask broadcastable against the page's (B, P) leading
        axes: (B-or-1, 1) for full attention, (B-or-1, P) for sliding rings.
        """
        s = self.spec
        if not sliding:
            mask = self._hot_mask(wp, p, sliding)  # write page included
            return mask.reshape((-1, 1))  # (B, 1) or (1, 1)
        d = jnp.asarray((wp - p) % s.n_pages).reshape((-1,))  # (B,) or (1,)
        full = (d >= 1) & (d < s.n_hot)
        rows = jnp.arange(s.page_size)
        written = rows[None, :] <= jnp.asarray(slot % s.page_size).reshape((-1, 1))
        return full[:, None] | ((d == 0)[:, None] & written)

    def _gather(self, hot: jax.Array, cold: jax.Array, wp: jax.Array,
                slot: jax.Array, sliding: bool) -> jax.Array:
        """Reconstruct the full (B, S, n_kv, hd) cache from hot ring + cold
        pages, double-buffered prefetch ordering on the cold fetches.

        Write-through keeps the per-page all-or-nothing ``lax.cond`` (cold is
        always canonical, so any page may be fetched whole). Flush mode keeps
        the all-hot fast path as a ``lax.cond`` but resolves mixed pages with
        a per-slot (sliding: per-row) select between ring and fetched cold."""
        s = self.spec
        P = s.page_size
        pages: list[jax.Array] = []
        for p in range(s.n_pages):
            row0 = (p % s.n_hot) * P
            hot_rows = jax.lax.slice_in_dim(hot, row0, row0 + P, axis=1)
            cold_rows = jax.lax.slice_in_dim(cold, p * P, (p + 1) * P, axis=1)
            if len(pages) >= 2:
                # double buffer: this fetch may start only once the
                # page-before-last materialized (≤ 2 transfers in flight),
                # and the barrier pins the pipeline inside the repeat scan
                cold_rows, _ = jax.lax.optimization_barrier((cold_rows, pages[-2]))
            fetch = self.fetch_sharding

            def from_cold(h, c, _sh=fetch):
                return c if _sh is None else jax.device_put(c, _sh)

            if not self.flush:
                pages.append(jax.lax.cond(
                    self._page_is_hot(wp, p, sliding),
                    lambda h, c: h, from_cold, hot_rows, cold_rows))
                continue

            take_hot = self._take_hot_rows(wp, slot, p, sliding)  # (B?, P?)
            sel = take_hot[..., None, None]  # broadcast over (B, P, kv, hd)

            def mixed(h, c, _sh=fetch, _sel=sel):
                c = c if _sh is None else jax.device_put(c, _sh)
                return jnp.where(_sel, h, c)

            pages.append(jax.lax.cond(
                jnp.all(take_hot), lambda h, c: h, mixed, hot_rows, cold_rows))
        return jnp.concatenate(pages, axis=1)

    # -- page-boundary flush --------------------------------------------------
    def _flush_cold(self, cold: jax.Array, hot: jax.Array, slot: jax.Array,
                    active: jax.Array | None) -> jax.Array:
        """Copy each slot's just-completed page hot→cold when the slot sits
        on a page boundary (``(slot + 1) % page_size == 0``); no cold write
        otherwise. The ring row of cache row ``r`` is exactly
        ``r % hot_window`` (``hot_window`` divides the ring), which keeps the
        per-slot source lookup a plain modular gather."""
        s = self.spec
        P, W = s.page_size, s.hot_window
        if jnp.ndim(slot) == 0:
            wp = slot // P

            def do_flush(c, h):
                page = jax.lax.dynamic_slice_in_dim(h, (wp % s.n_hot) * P, P, axis=1)
                return jax.lax.dynamic_update_slice_in_dim(c, page, wp * P, axis=1)

            return jax.lax.cond((slot + 1) % P == 0, do_flush,
                                lambda c, h: c, cold, hot)

        boundary = (slot + 1) % P == 0
        if active is not None:
            boundary = boundary & active
        wp = slot // P
        rows = jnp.arange(cold.shape[1])

        def do_flush(c, h):
            src = jnp.take(h, rows % W, axis=1)  # (B, S, ...) ring view
            sel = boundary[:, None] & (rows[None, :] // P == wp[:, None])
            return jnp.where(sel.reshape(sel.shape + (1,) * (c.ndim - 2)), src, c)

        return jax.lax.cond(jnp.any(boundary), do_flush,
                            lambda c, h: c, cold, hot)

    # -- the kv_io hook -------------------------------------------------------
    def _write(self, entry: dict, k: jax.Array, v: jax.Array,
               pos: jax.Array, cfg: ModelConfig,
               active: jax.Array | None):
        """The per-token cache write shared by both attention paths:
        hot-ring write plus flush/write-through cold update. Returns
        ``(hot_k, hot_v, cold_k, cold_v, slot, wp, sliding)``."""
        s = self.spec
        s_kv = entry["k_cold"].shape[1]
        assert s_kv == s.cache_len, (s_kv, s.cache_len)
        sliding = bool(cfg.sliding_window)
        slot = pos % s_kv if sliding else pos
        # hot ring at slot % W is the per-token write target
        hot_k = KV.write_slot(entry["k_hot"], k, slot % s.hot_window, mask=active)
        hot_v = KV.write_slot(entry["v_hot"], v, slot % s.hot_window, mask=active)
        if self.flush:
            # cold receives a completed page once per page_size steps
            cold_k = self._flush_cold(entry["k_cold"], hot_k, slot, active)
            cold_v = self._flush_cold(entry["v_cold"], hot_v, slot, active)
        else:
            # write-through: canonical cold updated every token
            cold_k = KV.write_slot(entry["k_cold"], k, slot, mask=active)
            cold_v = KV.write_slot(entry["v_cold"], v, slot, mask=active)
        return hot_k, hot_v, cold_k, cold_v, slot, slot // s.page_size, sliding

    def update_and_fetch(self, entry: dict, k: jax.Array, v: jax.Array,
                         pos: jax.Array, cfg: ModelConfig,
                         active: jax.Array | None = None):
        hot_k, hot_v, cold_k, cold_v, slot, wp, sliding = self._write(
            entry, k, v, pos, cfg, active)
        full_k = self._gather(hot_k, cold_k, wp, slot, sliding)
        full_v = self._gather(hot_v, cold_v, wp, slot, sliding)
        mask = KV.decode_mask(pos, self.spec.cache_len, sliding)
        new_entry = {"k_hot": hot_k, "v_hot": hot_v,
                     "k_cold": cold_k, "v_cold": cold_v}
        return full_k, full_v, mask, new_entry

    def _row_residency(self, wp: jax.Array, slot: jax.Array, sliding: bool,
                       batch: int) -> jax.Array:
        """(B, S) row-level residency the kernel's in-pass select consumes:
        True where the hot ring holds the row the lax ``_gather`` would take.

        Flush mode concatenates ``_take_hot_rows`` per page; write-through
        broadcasts the all-or-nothing ``_page_is_hot`` scalar (``_gather``'s
        ``lax.cond`` at row granularity — identical elementwise, and on
        masked stale rows any choice is absorbed by the NEG_INF mask).
        """
        s = self.spec
        cols = []
        for p in range(s.n_pages):
            if self.flush:
                take = self._take_hot_rows(wp, slot, p, sliding)
            else:
                take = self._page_is_hot(wp, p, sliding).reshape((1, 1))
            cols.append(jnp.broadcast_to(take, (batch, s.page_size)))
        return jnp.concatenate(cols, axis=1)

    def attend(self, entry: dict, q: jax.Array, k: jax.Array, v: jax.Array,
               pos: jax.Array, cfg: ModelConfig,
               active: jax.Array | None = None):
        """Fused write+attend hook (models.kvcache._decode_attention).

        With ``use_kernel`` the Pallas paged-attention kernel streams
        hot-ring slices and cold-page tiles straight into the attention
        pass — the gathered full cache never materializes. Without it,
        defers to ``update_and_fetch`` + ``_masked_decode_attn`` (the lax
        rebuild, which the parity tests hold the kernel bitwise against).
        Returns ``(out (B, 1, Hq, hd), new_entry)``.
        """
        if not self.use_kernel:
            full_k, full_v, mask, new_entry = self.update_and_fetch(
                entry, k, v, pos, cfg, active=active)
            return KV._masked_decode_attn(q, full_k, full_v, mask), new_entry
        from repro.kernels import decode_paged_attention

        hot_k, hot_v, cold_k, cold_v, slot, wp, sliding = self._write(
            entry, k, v, pos, cfg, active)
        b = q.shape[0]
        sel = self._row_residency(wp, slot, sliding, b)
        mask = KV.decode_mask(pos, self.spec.cache_len, sliding)
        mask = jnp.broadcast_to(mask.astype(jnp.float32),
                                (b, self.spec.cache_len))
        out = decode_paged_attention(q, hot_k, hot_v, cold_k, cold_v,
                                     sel, mask, n_hot=self.spec.n_hot)
        new_entry = {"k_hot": hot_k, "v_hot": hot_v,
                     "k_cold": cold_k, "v_cold": cold_v}
        return out, new_entry


# ---------------------------------------------------------------------------
# Accounting (serve_plan / examples / fidelity rows)
# ---------------------------------------------------------------------------
def cache_partition_bytes(cfg: ModelConfig, batch: int, seq_len: int,
                          spec: PagingSpec | None) -> dict[str, int]:
    """Global bytes of the decode cache by residence tier.

    Keys: ``hbm`` (hot rings + mamba/cross-attn state), ``host`` (cold
    pages), ``transient`` (one attention position's gathered full cache —
    the largest per-layer reconstruction live during its attention). A
    ``spec`` of None prices the resident layout (everything hbm, no
    transient).
    """
    base = KV.cache_specs(cfg, batch, seq_len)
    hbm = host = transient = 0
    for entry in base.values():
        for name, sd in entry.items():
            nbytes = 1
            for d in sd.shape:
                nbytes *= d
            nbytes *= sd.dtype.itemsize
            if spec is None or name not in ("k", "v"):
                hbm += nbytes
                continue
            hbm += nbytes * spec.n_hot // spec.n_pages  # hot ring
            host += nbytes  # canonical cold store
            # per-repeat gathered reconstruction: (B, S, kv, hd) x {k, v}
            transient = max(transient, 2 * nbytes // sd.shape[0])
    return {"hbm": hbm, "host": host, "transient": transient if spec else 0}
