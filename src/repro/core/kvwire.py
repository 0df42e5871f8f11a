"""LQ-quantized KV-cache (+ SSM-state) wire format (core format layer).

The paper quantizes layer *inputs* at runtime (section V.B: "the inputs
have to be converted into fixed point in runtime").  The serving-era
analogue is the KV cache: decode is memory-bound on cache reads, so
storing K/V in the local-quantization-region format cuts HBM traffic by
16/bits x — the same roofline win as packed weights (DESIGN.md §5.1).

Wire format per cached tensor (quantized along the head/feature dim):

    {"packed": uint8 (..., D/cpb), "scale": f32 (..., G), "zmin": f32 (..., G)}

``bits`` is *inferred from shapes* (cpb = D // packed_D in {1,2,4,8} ->
bits in {8,4,2,1}), so the cache stays a plain pytree — it flows through
scan / pjit / donation with no static metadata.  6/5/3-bit KV is therefore
not expressible here (weights support it; the cache keeps the power-of-two
set — noted in DESIGN.md).

Supported leaves: attention K/V (B, S, KV, D) and mamba2 SSM state
(B, H, P, N) — the attention-free arch's "cache" (DESIGN.md §4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import packing


def _infer(packed_d: int, d: int, scale_g: int):
    cpb = d // packed_d
    bits = {1: 8, 2: 4, 4: 2, 8: 1}[cpb]
    group_size = d // scale_g
    return bits, group_size


def is_quant_kv(leaf) -> bool:
    return isinstance(leaf, dict) and "packed" in leaf


is_quant_state = is_quant_kv


def kv_bits_of(q: dict, d: int) -> int:
    return _infer(q["packed"].shape[-1], d, q["scale"].shape[-1])[0]


def quantize_kv(x: jnp.ndarray, bits: int, group_size: int) -> dict:
    """x (..., D) -> wire dict, regions along the last dim."""
    d = x.shape[-1]
    if d % group_size:
        raise ValueError(f"D={d} not divisible by group_size={group_size}")
    g = d // group_size
    xg = x.astype(jnp.float32).reshape(*x.shape[:-1], g, group_size)
    xmin = xg.min(-1)
    xmax = xg.max(-1)
    levels = (1 << bits) - 1
    rng = xmax - xmin
    scale = jnp.where(rng > 0, rng / levels, jnp.ones_like(rng))
    codes = jnp.clip(jnp.round((xg - xmin[..., None]) / scale[..., None]),
                     0, levels).astype(jnp.uint8)
    return {"packed": packing.pack(codes.reshape(*x.shape), bits),
            "scale": scale, "zmin": xmin}


def dequantize_kv(q: dict, d: int, dtype=jnp.float32) -> jnp.ndarray:
    bits, group_size = _infer(q["packed"].shape[-1], d, q["scale"].shape[-1])
    codes = packing.unpack(q["packed"], bits, d).astype(jnp.float32)
    g = d // group_size
    cg = codes.reshape(*codes.shape[:-1], g, group_size)
    x = cg * q["scale"][..., None] + q["zmin"][..., None]
    return x.reshape(*codes.shape).astype(dtype)


def make_quant_kv(shape: tuple, bits: int, group_size: int) -> dict:
    """Zero-initialized wire cache for a (..., D) tensor.

    ALL leaves init to zero — including ``scale``, so an unwritten row
    dequantizes to 0 (code 0 * scale 0 + zmin 0) and the zero wire state
    is one uniform fill.  Scan-stacked cache/pool layouts build their
    leaves with ``jnp.zeros`` over this structure
    (``transformer.init_cache``, ``serve/pool.py``), and cache rewind
    (:func:`reset_table_rows`) restores exactly this state.
    """
    *lead, d = shape
    cpb = packing.codes_per_byte(bits)
    g = d // group_size
    return {"packed": jnp.zeros((*lead, d // cpb), jnp.uint8),
            "scale": jnp.zeros((*lead, g), jnp.float32),
            "zmin": jnp.zeros((*lead, g), jnp.float32)}


def update_quant_kv(q: dict, new: jnp.ndarray, slot, *, axis: int,
                    bits: int, group_size: int) -> dict:
    """Quantize ``new`` and write it at ``slot`` along ``axis``.

    ``new`` has the same rank as the cache's logical tensor; its extent
    along ``axis`` may exceed 1 (bulk prefill write).
    """
    wire = quantize_kv(new, bits, group_size)
    return {k: jax.lax.dynamic_update_slice_in_dim(
        q[k], wire[k].astype(q[k].dtype), slot, axis=axis) for k in q}


# ---------------------------------------------------------------------------
# paged layout: pool pages of the same wire format
# ---------------------------------------------------------------------------
#
# A paged pool stores its tokens in pages instead of one contiguous
# (B, T, KV, D) buffer.  A request owns an ordered list of pages (its page
# table); page p of the table holds absolute token positions
# [p*page_size, (p+1)*page_size).  Page 0 is reserved as a scratch page:
# padded table entries and inactive batch slots read/write it, and the
# masking in decode_attention guarantees scratch garbage never reaches a
# real output.  Packing is along the head dim, so page_size is independent
# of kv_bits; every page is page_size * KV * (D*bits/8 + 8*D/group) bytes.
#
# Logically a page leaf is (n_pages, page_size, KV, X): X = D/cpb packed
# codes, G regions of scale/zmin, or D fp features.  It is STORED feature-
# major within a page: the page's R = page_size*KV (token, head) rows lie
# on the minor dim, lane ``t*KV + h`` for token row t of kv head h, and
# its features on the dim before.  Where R is under the chip's 128 lanes,
# the features fold into them: with fold f, feature ``j*(X/f) + i`` of
# row r sits at ``[i, j*R + r]``, so a stored page is (X/f, f*R).  f is the
# largest power of two with f*R <= 128 that divides X, chosen per leaf
# from its shape (1 where R already fills the lanes).  The two minor dims
# are then what the fused kernel reads for one page, dense in the chip's
# (sublane, 128-lane) tiles, and a scan-stacked leaf (n_super, n_pages,
# X/f, f*R) is written in place and read as stored, whole, by every layer
# of the stack.  Same elements, same bytes: a permutation only.
# :func:`logical_pages` gives the logical view, :func:`stored_pages` the
# stored one.

LANES = 128        # the chip's vector lanes, which a stored page fills


def page_fold(width: int, rows: int) -> int:
    """How many feature blocks of a page share the lanes: the largest
    power of two f with ``f * rows <= LANES`` that divides ``width``."""
    f = 1
    while 2 * f * rows <= LANES and width % (2 * f) == 0:
        f *= 2
    return f


def fold_pages(a, rows: int):
    """(..., X, R) feature-major pages -> the stored (..., X/f, f*R)."""
    *lead, w, _ = a.shape
    f = page_fold(w, rows)
    if f == 1:
        return a
    a = a.reshape(*lead, f, w // f, rows)
    return jnp.swapaxes(a, -2, -3).reshape(*lead, w // f, f * rows)


def unfold_pages(a, rows: int):
    """The stored (..., X/f, f*R) -> (..., X, R) feature-major pages."""
    *lead, wf, lanes = a.shape
    f = lanes // rows
    if f == 1:
        return a
    a = a.reshape(*lead, wf, f, rows)
    return jnp.swapaxes(a, -2, -3).reshape(*lead, f * wf, rows)


def lane_rows(width: int, rows: int, kv_heads: int) -> jnp.ndarray:
    """(f*R,) in-page token row of each lane of a stored page of logical
    width ``width`` and R = ``rows``."""
    t = jnp.broadcast_to(jnp.arange(rows) // kv_heads, (width, rows))
    return fold_pages(t, rows)[0]


def stored_pages(x):
    """Logical (..., n_pages, page_size, KV, X) pages -> the stored layout;
    leaves of a wire dict alike."""
    def f(a):
        *lead, ps, kvh, w = a.shape
        a = jnp.swapaxes(a.reshape(*lead, ps * kvh, w), -1, -2)
        return fold_pages(a, ps * kvh)
    return jax.tree.map(f, x)


def logical_pages(x, page_size: int, kv_heads: int):
    """Stored pages -> the logical (..., n_pages, page_size, KV, X) view;
    leaves of a wire dict alike."""
    def f(a):
        a = unfold_pages(a, page_size * kv_heads)
        *lead, w, _ = a.shape
        return jnp.swapaxes(a, -1, -2).reshape(*lead, page_size, kv_heads, w)
    return jax.tree.map(f, x)


def page_width(a, rows: int) -> int:
    """Logical feature width X of a stored page array."""
    return a.shape[-2] * a.shape[-1] // rows


def page_spec(leaf, head_dim: int, rows: int):
    """(bits, group) of a stored wire leaf with R = ``rows``, else None."""
    if not is_quant_kv(leaf):
        return None
    return _infer(page_width(leaf["packed"], rows), head_dim,
                  page_width(leaf["scale"], rows))


def make_paged_kv(n_pages: int, page_size: int, kv_heads: int, head_dim: int,
                  bits: int | None = None, group_size: int = 64,
                  dtype=jnp.float32):
    """One zero pool leaf in the stored layout: fp array or wire dict."""
    shape = (n_pages, page_size, kv_heads, head_dim)
    one = jax.eval_shape(
        lambda: stored_pages(jnp.zeros(shape, dtype) if bits is None
                             else make_quant_kv(shape, bits, group_size)))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), one)


def gather_pages(leaf, page_table: jnp.ndarray, page_size: int,
                 kv_heads: int, layer=None):
    """Gather a (B, P) page table into logical (B, P*page_size, KV, X)
    views of a stored leaf (layer ``layer`` of a stacked one).

    Works on fp leaves and wire dicts alike.  Row order in the gathered
    view is the page-table order, so with in-order tables position t of
    request b lives at gathered index t.
    """
    def g(a):
        out = a[page_table] if layer is None else a[layer, page_table]
        out = logical_pages(out, page_size, kv_heads)   # (B, P, ps, KV, X)
        return out.reshape(page_table.shape[0], -1, *out.shape[3:])
    return jax.tree.map(g, leaf)


def scatter_tokens(leaf, new: jnp.ndarray, page_idx, row, *, page_size: int,
                   layer=None, bits: int | None = None,
                   group_size: int | None = None):
    """Write a length-L run of tokens per batch row into its pages, in
    place in the stored layout (layer ``layer`` of a stacked leaf).

    ``new`` is fp (B, L, KV, D); ``page_idx``/``row`` are (B, L) physical
    page ids and in-page rows — the speculative verify path writes all L
    candidate positions of every slot in one scatter.  Each token
    rewrites its slot's whole stored page with its own rows set, so the
    scatter's window is the page's two minor dims and the pool keeps its
    layout.  Rows of inactive (or overflowing) slots should point at the
    scratch page; duplicate scratch writes are unordered, which is fine —
    the scratch page is never read unmasked.  (Live slots never share a
    page, so no other duplicates arise.)
    """
    kvh, l = new.shape[2], new.shape[1]
    rows = page_size * kvh
    wire = quantize_kv(new, bits, group_size) if is_quant_kv(leaf) else new

    def put(a, w):                                          # w (B, L, KV, X)
        token_row = lane_rows(w.shape[-1], rows, kvh)
        for i in range(l):
            at = ((page_idx[:, i],) if layer is None
                  else (layer, page_idx[:, i]))
            old = a[at]                                     # (B, X/f, f*R)
            tok = jnp.swapaxes(w[:, i], 1, 2).astype(a.dtype)   # (B, X, KV)
            tok = fold_pages(jnp.tile(tok, (1, 1, page_size)), rows)
            hit = token_row == row[:, i, None, None]        # (B, 1, f*R)
            a = a.at[at].set(jnp.where(hit, tok, old))
        return a
    return jax.tree.map(put, leaf, wire)


def scatter_token(leaf, new: jnp.ndarray, page_idx, row, *, page_size: int,
                  layer=None, bits: int | None = None,
                  group_size: int | None = None):
    """:func:`scatter_tokens` for one token per batch row: ``new`` is fp
    (B, 1, KV, D), ``page_idx``/``row`` (B,)."""
    return scatter_tokens(leaf, new, page_idx[:, None], row[:, None],
                          page_size=page_size, layer=layer, bits=bits,
                          group_size=group_size)


def reset_table_rows(tree, table, keep_tokens, *, page_size: int,
                     kv_heads: int, stacked: bool = False):
    """Un-write every row past ``keep_tokens`` tokens of one request's
    page table, in ONE fused update per leaf.

    ``table`` is the request's (scratch-padded, fixed-length) ordered
    page-id vector; entry i of the table covers token positions
    ``[i * page_size, (i+1) * page_size)``.  Rows at positions
    ``>= keep_tokens`` on the table's real (non-scratch) pages are reset
    to the zero-initialized wire state (all leaves -> 0, matching
    :func:`make_quant_kv`); scratch-padded entries are left untouched.

    This is the device half of cache rewind: a speculative verify writes
    L candidate rows, the accept decision keeps a prefix, and the pool
    un-writes the rejected suffix so its bytes are indistinguishable from
    a pool that never speculated (``serve/pool.py::PagedKVPool.truncate``)
    — one dispatch per rewind, however many pages it spans.
    """
    n_tbl = table.shape[0]
    rows = page_size * kv_heads

    def reset(a):
        pages = a[:, table] if stacked else a[table]   # (.., n_tbl, X/f, L)
        t = lane_rows(page_width(pages, rows), rows, kv_heads)
        pos = jnp.arange(n_tbl)[:, None] * page_size + t[None]
        mask = (pos >= keep_tokens) & (table > 0)[:, None]
        new = jnp.where(mask[:, None], jnp.zeros((), a.dtype), pages)
        # duplicate scratch entries all scatter their own UNCHANGED rows
        # (mask is False there), so the unordered dupes are harmless
        return (a.at[:, table].set(new) if stacked
                else a.at[table].set(new))

    return jax.tree.map(reset, tree)


def scatter_prefill(leaf, contig, page_ids: jnp.ndarray, *,
                    stacked: bool = False):
    """Copy a B=1 contiguous prefill cache into pool pages.

    ``contig`` is the (S, 1, T, KV, X) (stacked=True) or (1, T, KV, X) leaf
    from a contiguous prefill; T must equal len(page_ids) * page_size.
    Pages the request does not own map to the scratch page in
    ``page_ids``.  The small bucket is put in the stored layout before
    the set, so the pool is written in place as it is stored.
    """
    def s(pl, cl):
        n = page_ids.shape[0]
        lead = cl.shape[:1] if stacked else ()
        c = stored_pages(cl.reshape(*lead, n, -1, *cl.shape[-2:]))
        if stacked:
            return pl.at[:, page_ids].set(c.astype(pl.dtype))
        return pl.at[page_ids].set(c.astype(pl.dtype))
    return jax.tree.map(s, leaf, contig)


def permute_pages(leaf, perm: jnp.ndarray, *, stacked: bool = False):
    """Reorder pages (defrag): new page i takes old page perm[i]."""
    return jax.tree.map(lambda a: a[:, perm] if stacked else a[perm], leaf)


# ---------------------------------------------------------------------------
# SSM state (mamba2): same format, quantized along the state dim N
# ---------------------------------------------------------------------------

def quantize_state(h: jnp.ndarray, bits: int = 8,
                   group_size: int = 64) -> dict:
    gs = min(group_size, h.shape[-1])
    return quantize_kv(h, bits, gs)


def dequantize_state(q: dict, n: int) -> jnp.ndarray:
    return dequantize_kv(q, n, jnp.float32)


# ---------------------------------------------------------------------------
# per-layer (heterogeneous) layout helpers
# ---------------------------------------------------------------------------

KV_BITS = (8, 4, 2, 1)     # the wire format's expressible widths (cpb 2^k)


def check_kv_bits(bits) -> None:
    """The wire format infers bits from shapes, so only power-of-two
    widths round-trip (6/5/3-bit would alias another cpb)."""
    if bits is not None and bits not in KV_BITS:
        raise ValueError(f"kv_bits must be one of {KV_BITS} or None (fp), "
                         f"got {bits!r}")


def segment_runs(values, p_len: int, n_super: int) -> list:
    """Group consecutive superblocks whose per-position values match.

    ``values`` is a per-layer list (length >= n_super * p_len); the key of
    superblock ``s`` is ``tuple(values[s*p_len + j] for j in range(p_len))``.
    Returns ``[(start_super, size, key), ...]`` — the maximal runs one
    stacked cache array (or scan body) can cover.  This is the shared
    grouping rule behind ``transformer.plan_segments`` and the
    heterogeneous pool layout in ``serve/pool.py``: per-layer kv bitwidths
    change packed leaf *shapes*, so each run gets its own stacked array.
    """
    segs = []
    s = 0
    while s < n_super:
        key = tuple(values[s * p_len + j] for j in range(p_len))
        e = s + 1
        while e < n_super and key == tuple(values[e * p_len + j]
                                           for j in range(p_len)):
            e += 1
        segs.append((s, e - s, key))
        s = e
    return segs


# ---------------------------------------------------------------------------
# accounting
# ---------------------------------------------------------------------------

def cache_nbytes(cache) -> int:
    """Total bytes of a (possibly mixed fp/quantized) cache pytree."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


def kv_token_nbytes(kv_heads: int, head_dim: int, bits: int | None,
                    group_size: int = 64, fp_itemsize: int = 4) -> float:
    """Exact wire bytes one cached token costs for one K+V pair.

    Matches the paged-pool leaf byte-for-byte: packed codes are
    ``head_dim * bits / 8`` per head plus an f32 (scale, zmin) pair per
    local region; fp caches pay ``fp_itemsize`` per element.  Used by
    ``plan/costmodel.py`` to price per-layer cache budgets and by the
    pool-geometry property tests.
    """
    if bits is None:
        per_head = head_dim * fp_itemsize
    else:
        check_kv_bits(bits)
        per_head = head_dim * bits / 8 + 2 * 4 * (head_dim // group_size)
    return 2.0 * kv_heads * per_head
