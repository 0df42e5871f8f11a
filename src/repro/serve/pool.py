"""Paged KV-cache pool: block storage for the quantized wire format.

The pool owns every layer's K/V storage as shared page arrays —
``(n_super, n_pages, page_size, KV, ...)`` for scan-stacked superblock
positions, ``(n_pages, page_size, KV, ...)`` for the unscanned tail — in
the LQ wire format when ``kv_bits`` is set (core/kvwire.py) or fp
otherwise.  Requests own ordered page lists (page tables); the device-side
gather/scatter lives in core/kvwire.py and models/attention.py; this class
is the host-side allocator: alloc/free/defrag plus accounting.

Full and sliding-window (``attn_local``) layers share one page geometry:
a windowed layer's pages hold its whole context, though its attention
reads only the last ``window`` positions (a known cost: ring or
per-layer page counts would free the rest).

Page 0 is reserved as a scratch page.  Padded page-table entries and
inactive decode slots read and write it; decode masking guarantees its
garbage never reaches a real output.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kvwire
from repro.models.config import ModelConfig
from repro.obs import NOOP


def _check_paged_support(cfg: ModelConfig):
    for mixer, _ in cfg.pattern:
        if mixer not in ("attn", "attn_local"):
            raise ValueError(
                f"paged KV pool supports full and sliding-window attention "
                f"decoders only; mixer {mixer!r} needs the contiguous "
                f"Engine path")
    if cfg.n_enc_layers:
        raise ValueError("paged serving does not support encoder-decoder")
    if cfg.frontend != "none":
        raise ValueError("paged serving does not support modality frontends")
    if cfg.pos_embed == "learned":
        raise ValueError("paged serving needs rope (per-slot positions)")


def make_pool_pages(cfg: ModelConfig, *, n_pages: int, page_size: int,
                    kv_bits=None, kv_group: int = 64, dtype=None):
    """Build the zero-initialized page pytree of a :class:`PagedKVPool`.

    ``kv_bits`` is ``None`` (fp), one int (every layer shares the wire
    format), or a per-layer sequence of ``bits | None`` — the
    heterogeneous page geometry of a mixed-KV :class:`~repro.plan.QuantPlan`.
    Homogeneous pools stack superblock leaves under ``"super"`` as before;
    a genuinely mixed map stores one stacked leaf per run of superblocks
    sharing a wire shape under ``"super_segments"`` (packed widths differ,
    so heterogeneous layers cannot share an array), mirroring
    ``transformer.init_cache``.  Page ids stay *global*: page ``p`` of
    every layer's array belongs to the same request, whatever that
    layer's bitwidth — only the bytes behind a page differ per layer.

    Module-level so callers can price a pool without materializing it:
    ``jax.eval_shape(lambda: make_pool_pages(...))`` yields the structure
    abstractly (see :func:`pool_nbytes`, used by the fleet registry's
    host-budget accounting).
    """
    from repro.models.transformer import normalize_kv_quant

    _check_paged_support(cfg)
    if n_pages < 2:
        raise ValueError("need at least one allocatable page + scratch")
    kvq = normalize_kv_quant(cfg, (kv_bits, kv_group))
    per_layer = kvq is not None and isinstance(kvq[0], tuple)
    if kvq is not None and cfg.head_dim % kv_group:
        raise ValueError(f"head_dim={cfg.head_dim} not divisible by "
                         f"kv_group={kv_group}")
    dtype = dtype or cfg.activation_dtype

    def leaf(stack: int | None, bits):
        one = kvwire.make_paged_kv(n_pages, page_size, cfg.n_kv_heads,
                                   cfg.head_dim, bits, kv_group, dtype)
        if stack is None:
            return one
        return jax.tree.map(
            lambda a: jnp.zeros((stack,) + a.shape, a.dtype), one)

    p_len = len(cfg.pattern)
    if per_layer:
        bits_list = kvq[0]
        runs = kvwire.segment_runs(list(bits_list), p_len, cfg.n_super)
        sup = [tuple({"self": {"k": leaf(size, key[j]),
                               "v": leaf(size, key[j])}}
                     for j in range(p_len))
               for _, size, key in runs]
        tail = [{"self": {"k": leaf(None, bits_list[cfg.n_super * p_len + t]),
                          "v": leaf(None, bits_list[cfg.n_super * p_len + t])}}
                for t in range(cfg.n_tail)]
        return {"super_segments": sup, "tail": tail}

    bits = None if kvq is None else kvq[0]
    sup = tuple({"self": {"k": leaf(cfg.n_super, bits),
                          "v": leaf(cfg.n_super, bits)}}
                for _ in cfg.pattern)
    tail = [{"self": {"k": leaf(None, bits), "v": leaf(None, bits)}}
            for _ in range(cfg.n_tail)]
    return {"super": sup, "tail": tail}


def pool_nbytes(cfg: ModelConfig, *, n_pages: int, page_size: int,
                kv_bits=None, kv_group: int = 64, dtype=None) -> int:
    """Resident bytes of a pool with this geometry, without building it.

    Exact by construction (``eval_shape`` over the real pytree), including
    per-layer heterogeneous ``kv_bits`` maps — the fleet registry prices
    mixed-KV tenants with these bytes, not a uniform over-approximation.
    """
    pages = jax.eval_shape(lambda: make_pool_pages(
        cfg, n_pages=n_pages, page_size=page_size, kv_bits=kv_bits,
        kv_group=kv_group, dtype=dtype))
    return kvwire.cache_nbytes(pages)


class PagedKVPool:
    """Block/paged KV storage + host-side page allocator.

    n_pages counts physical pages including the reserved scratch page 0, so
    ``n_pages - 1`` pages are allocatable.  ``kv_bits`` in {8, 4, 2, 1} —
    one int, or a per-layer map (heterogeneous page geometry) — stores
    pages in the packed wire format; packing is along head_dim, so
    page_size is independent of kv_bits (see serve/README.md).  The
    allocator below is bitwidth-blind: a page id spans every layer's
    array, so alloc/free/defrag never need to know the geometry.
    """

    def __init__(self, cfg: ModelConfig, *, n_pages: int, page_size: int,
                 kv_bits=None, kv_group: int = 64, dtype=None, obs=None):
        self.cfg = cfg
        self.n_pages, self.page_size = n_pages, page_size
        self.kv_bits, self.kv_group = kv_bits, kv_group
        self.obs = obs or NOOP     # allocator events + occupancy gauge
        self.pages = make_pool_pages(cfg, n_pages=n_pages,
                                     page_size=page_size, kv_bits=kv_bits,
                                     kv_group=kv_group, dtype=dtype)
        sup_key = ("super_segments" if "super_segments" in self.pages
                   else "super")
        self._permute = jax.jit(lambda pages, perm: {
            sup_key: kvwire.permute_pages(pages[sup_key], perm,
                                          stacked=True),
            "tail": kvwire.permute_pages(pages["tail"], perm)})
        self._reset_table = jax.jit(lambda pages, table, keep: {
            sup_key: kvwire.reset_table_rows(pages[sup_key], table, keep,
                                             stacked=True),
            "tail": kvwire.reset_table_rows(pages["tail"], table, keep)})

        self._free = list(range(n_pages - 1, 0, -1))   # LIFO free list
        self.page_tables: dict[int, list[int]] = {}    # rid -> ordered pages

    # ---------------------------------------------------------- allocator
    @property
    def n_allocatable(self) -> int:
        return self.n_pages - 1

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_allocated(self) -> int:
        return self.n_allocatable - self.n_free

    def occupancy(self) -> float:
        return self.n_allocated / self.n_allocatable

    def alloc(self, rid: int, n: int = 1) -> bool:
        """Append n pages to rid's table; all-or-nothing on exhaustion."""
        if n > len(self._free):
            if self.obs.enabled:
                # flight-recorder anomaly trigger (obs/flight.py)
                self.obs.event("alloc_fail", rid=int(rid), n_pages=n,
                               free=len(self._free))
                self.obs.metrics.counter("pool_alloc_fail_total").inc()
            return False
        got = [self._free.pop() for _ in range(n)]
        self.page_tables.setdefault(rid, []).extend(got)
        if self.obs.enabled:
            self.obs.event("alloc", rid=int(rid), n_pages=n)
            self.obs.metrics.counter("pool_alloc_total").inc(n)
            self.obs.metrics.gauge("pool_occupancy").set(self.occupancy())
        return True

    def free(self, rid: int) -> int:
        """Release every page owned by rid; returns how many."""
        pages = self.page_tables.pop(rid, [])
        self._free.extend(reversed(pages))
        if pages and self.obs.enabled:
            self.obs.event("free", rid=int(rid), n_pages=len(pages))
            self.obs.metrics.gauge("pool_occupancy").set(self.occupancy())
        return len(pages)

    def pages_of(self, rid: int) -> list[int]:
        return list(self.page_tables.get(rid, []))

    # ------------------------------------------------------------- rewind
    def truncate(self, rid: int, keep_tokens: int) -> int:
        """Un-write rid's cache past ``keep_tokens`` tokens (speculative
        rollback): trailing rows of the partially-kept page and every
        wholly-unused trailing page are reset to the zero-initialized wire
        state (across every layer, at that layer's own format), and the
        trailing pages return to the free list.  No realloc — the kept
        prefix stays in place, so after a rewind the pool is
        byte-indistinguishable from one that never speculated.  Returns
        the number of pages released.
        """
        if keep_tokens < 0:
            raise ValueError(f"keep_tokens must be >= 0, got {keep_tokens}")
        tbl = self.page_tables.get(rid, [])
        keep_pages = -(-keep_tokens // self.page_size)
        if keep_pages > len(tbl):
            raise ValueError(
                f"truncate({rid}, {keep_tokens}) needs {keep_pages} pages "
                f"but the request owns {len(tbl)}")
        drop = tbl[keep_pages:]
        if keep_tokens < len(tbl) * self.page_size and tbl:
            # one fused dispatch resets the partial page's tail AND every
            # dropped page (fixed-length scratch-padded table -> one trace)
            padded = np.zeros((self.n_pages,), np.int32)
            padded[:len(tbl)] = tbl
            self.pages = self._reset_table(
                self.pages, jnp.asarray(padded),
                jnp.asarray(keep_tokens, jnp.int32))
        if drop:
            del self.page_tables[rid][keep_pages:]
            self._free.extend(reversed(drop))
        if self.obs.enabled:
            self.obs.event("rewind", rid=int(rid),
                           keep_tokens=int(keep_tokens),
                           released_pages=len(drop))
            self.obs.metrics.counter("pool_rewind_total").inc()
            self.obs.metrics.gauge("pool_occupancy").set(self.occupancy())
        return len(drop)

    def table_array(self, rid: int, max_pages: int) -> np.ndarray:
        """rid's page table as (max_pages,) int32, scratch-padded."""
        tbl = self.page_tables.get(rid, [])
        out = np.zeros((max_pages,), np.int32)
        out[:len(tbl)] = tbl
        return out

    # ------------------------------------------------------------- defrag
    def defrag(self) -> dict[int, int]:
        """Compact allocated pages into [1, n_allocated], preserving each
        request's page order.  Rewrites page tables and physically permutes
        the pool (jitted gather).  Returns the old->new page mapping."""
        perm = np.empty((self.n_pages,), np.int32)
        perm[0] = 0
        mapping: dict[int, int] = {}
        nxt = 1
        for rid, tbl in self.page_tables.items():
            for old in tbl:
                mapping[old] = nxt
                perm[nxt] = old
                nxt += 1
        leftovers = [p for p in range(1, self.n_pages) if p not in mapping]
        perm[nxt:] = leftovers
        self.pages = self._permute(self.pages, jnp.asarray(perm))
        self.page_tables = {rid: [mapping[p] for p in tbl]
                            for rid, tbl in self.page_tables.items()}
        self._free = list(range(self.n_pages - 1, nxt - 1, -1))
        if self.obs.enabled:
            self.obs.event("defrag", moved=sum(
                1 for old, new in mapping.items() if old != new))
            self.obs.metrics.counter("pool_defrag_total").inc()
        return mapping

    # --------------------------------------------------------- accounting
    def nbytes(self) -> int:
        return kvwire.cache_nbytes(self.pages)

    def page_nbytes(self) -> int:
        """Bytes of one page across all layers."""
        return self.nbytes() // self.n_pages
