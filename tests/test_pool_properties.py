"""Property tests for the heterogeneous paged KV pool.

Random alloc/free/defrag sequences against a pool with per-layer page
geometry must preserve the allocator invariants the decode path relies
on: the scratch page 0 is never handed out, no physical page is ever
owned by two requests (page ids are global across layers, so per-slot
disjointness IS cross-layer disjointness), the free list and the page
tables partition the allocatable pages, and defrag compacts to
``[1, n_allocated]`` while preserving each request's page order.  Pool bytes
are checked against the exact per-layer wire arithmetic
(``kvwire.kv_token_nbytes``), not just monotonicity.

Hypothesis is optional extra coverage (same guard as tests/test_packing.py);
the exact-bytes and example-sequence tests always run.
"""
import jax
import numpy as np
import pytest

try:        # property tests are extra coverage; the container may lack it
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.core import kvwire
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.serve import PagedKVPool, pool_nbytes

TINY = ModelConfig(name="tiny", family="dense", n_layers=3, d_model=64,
                   vocab_size=256, n_heads=4, n_kv_heads=2, head_dim=16,
                   d_ff=128, dtype="float32", remat="none")

KV_MAPS = [(8, None, 2), (2, 2, 8), (None, 1, 4), (8, 8, 8), (None,) * 3]
N_PAGES, PAGE_SIZE, KV_GROUP = 8, 4, 16


def _expected_nbytes(cfg, kv_map, n_pages, page_size, kv_group):
    """Sum of exact per-layer page bytes, from the wire format arithmetic."""
    per_token = sum(
        kvwire.kv_token_nbytes(cfg.n_kv_heads, cfg.head_dim, b, kv_group,
                               fp_itemsize=cfg.activation_dtype.itemsize)
        for b in kv_map)
    return int(per_token * page_size * n_pages)


@pytest.mark.parametrize("kv_map", KV_MAPS)
def test_pool_nbytes_is_sum_of_per_layer_page_bytes(kv_map):
    got = pool_nbytes(TINY, n_pages=N_PAGES, page_size=PAGE_SIZE,
                      kv_bits=kv_map, kv_group=KV_GROUP)
    assert got == _expected_nbytes(TINY, kv_map, N_PAGES, PAGE_SIZE,
                                   KV_GROUP)


def _check_invariants(pool):
    tables = {rid: list(t) for rid, t in pool.page_tables.items()}
    owned = [p for t in tables.values() for p in t]
    # scratch page 0 stays reserved
    assert 0 not in owned and 0 not in pool._free
    # no page aliased across requests (page ids are global across layers)
    assert len(owned) == len(set(owned))
    # free list and tables partition the allocatable pages
    assert not set(owned) & set(pool._free)
    assert sorted(owned + list(pool._free)) == list(range(1, pool.n_pages))
    assert pool.n_allocated == len(owned)
    assert pool.n_free == pool.n_allocatable - len(owned)


def _run_ops(pool, ops):
    """Drive the allocator; returns {rid: pages} shadow bookkeeping."""
    shadow = {}
    for kind, rid, n in ops:
        if kind == 0:                       # alloc
            before = pool.pages_of(rid)
            ok = pool.alloc(rid, n)
            after = pool.pages_of(rid)
            if ok:
                assert after[:len(before)] == before    # append-only
                assert len(after) == len(before) + n
                shadow[rid] = after
            else:                           # all-or-nothing on exhaustion
                assert after == before
                assert n > pool.n_free
        elif kind == 1:                     # free
            freed = pool.free(rid)
            assert freed == len(shadow.pop(rid, []))
        elif kind == 2:                     # defrag
            mapping = pool.defrag()
            assert set(mapping) == {p for t in shadow.values() for p in t}
            shadow = {rid: [mapping[p] for p in t]
                      for rid, t in shadow.items()}
            # compact: allocated pages are exactly [1, n_allocated],
            # preserving each request's page order
            owned = sorted(p for t in shadow.values() for p in t)
            assert owned == list(range(1, pool.n_allocated + 1))
        else:                               # truncate (speculative rewind)
            owned = shadow.get(rid, [])
            keep = min(n, len(owned) * pool.page_size)
            keep_pages = -(-keep // pool.page_size)
            freed = pool.truncate(rid, keep)
            assert freed == len(owned) - keep_pages
            if owned:
                shadow[rid] = owned[:keep_pages]
        for rid2, t in shadow.items():
            assert pool.pages_of(rid2) == t
        _check_invariants(pool)
    return shadow


def test_example_sequence_all_maps():
    """Deterministic walk of every kv map (always runs, no hypothesis)."""
    ops = [(0, 1, 2), (0, 2, 3), (3, 2, 7), (1, 1, 0), (2, 0, 0),
           (0, 3, 4), (3, 3, 9), (3, 3, 2), (0, 4, 9), (1, 2, 0),
           (2, 0, 0), (0, 5, 1), (3, 5, 0), (1, 3, 0), (2, 0, 0)]
    for kv_map in KV_MAPS:
        pool = PagedKVPool(TINY, n_pages=N_PAGES, page_size=PAGE_SIZE,
                           kv_bits=kv_map, kv_group=KV_GROUP)
        _run_ops(pool, ops)
        assert pool.nbytes() == _expected_nbytes(
            TINY, kv_map, N_PAGES, PAGE_SIZE, KV_GROUP)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(
        kv_map=st.sampled_from(KV_MAPS),
        ops=st.lists(
            st.tuples(st.integers(0, 3),    # alloc/free/defrag/truncate
                      st.integers(1, 5),    # rid
                      st.integers(0, 12)),  # pages requested / keep tokens
            min_size=1, max_size=24),
    )
    def test_random_alloc_free_defrag_never_aliases(kv_map, ops):
        pool = PagedKVPool(TINY, n_pages=N_PAGES, page_size=PAGE_SIZE,
                           kv_bits=kv_map, kv_group=KV_GROUP)
        _run_ops(pool, ops)
        assert pool.nbytes() == _expected_nbytes(
            TINY, kv_map, N_PAGES, PAGE_SIZE, KV_GROUP)


def _segment_bits(kv_map):
    """The kv bits of each run of the pool (``transformer.stack_runs``):
    runs of equal consecutive layers share one stacked array, so run
    ``s`` is not layer ``s`` once a bit width repeats."""
    return [key[0] for _, _, key in
            kvwire.segment_runs(list(kv_map), 1, TINY.n_layers)]


def _runs(pool, kv_map):
    """The pool's runs, one per run of equal consecutive kv bits."""
    runs = transformer.stack_runs(pool.pages)
    assert len(runs) == len(_segment_bits(kv_map))
    return runs


def _defrag_data_check(kv_map, sizes, victim):
    """Write a sentinel token row into every allocated page of every layer
    (at that layer's own wire format), shuffle the pool with frees +
    defrag, and check each surviving request still reads its own rows —
    i.e. pages never alias across slots or layers under compaction."""
    pool = PagedKVPool(TINY, n_pages=N_PAGES, page_size=PAGE_SIZE,
                       kv_bits=kv_map, kv_group=KV_GROUP)
    rids = [1, 2, 3]
    for r, n in zip(rids, sizes):
        assert pool.alloc(r, n)

    # one token row per rid, scattered into page row 0 of its first page
    # at that layer's own wire format (every run has stack size 1 here)
    import jax.numpy as jnp
    toks = {r: jax.random.normal(jax.random.key(r),
                                 (1, 1, TINY.n_kv_heads, TINY.head_dim))
            for r in rids}
    for bits, seg in zip(_segment_bits(kv_map), _runs(pool, kv_map)):
        kw = {} if bits is None else dict(bits=bits, group_size=KV_GROUP)
        leaf = jax.tree.map(lambda a: a[0], seg[0]["self"]["k"])
        for r in rids:
            page = jnp.asarray([pool.pages_of(r)[0]])
            row = jnp.asarray([0])
            leaf = kvwire.scatter_token(leaf, toks[r], page, row,
                                        page_size=PAGE_SIZE, **kw)
        seg[0]["self"]["k"] = jax.tree.map(lambda a: a[None], leaf)

    def slot_views():
        """{(seg, rid): full gathered wire view of rid's pages}."""
        out = {}
        for s, seg in enumerate(_runs(pool, kv_map)):
            leaf = jax.tree.map(lambda a: a[0], seg[0]["self"]["k"])
            for r in rids:
                if r == victim and victim_freed[0]:
                    continue
                tbl = jnp.asarray([pool.pages_of(r)], jnp.int32)
                out[(s, r)] = kvwire.gather_pages(leaf, tbl, PAGE_SIZE,
                                                  TINY.n_kv_heads)
        return out

    victim_freed = [False]
    before = slot_views()
    victim_freed[0] = True
    pool.free(victim)
    pool.defrag()
    after = slot_views()
    # a defrag is a pure page permutation: every surviving request reads
    # back byte-identical wire data at every layer's own format
    for key, want in before.items():
        if key[1] == victim:
            continue
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), want, after[key])


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(kv_map=st.sampled_from([(8, None, 2), (2, 1, 8)]),
           sizes=st.tuples(st.integers(1, 2), st.integers(1, 2),
                           st.integers(1, 2)),
           victim=st.sampled_from([1, 2, 3]))
    def test_defrag_preserves_slot_data_across_geometries(kv_map, sizes,
                                                          victim):
        _defrag_data_check(kv_map, sizes, victim)
else:
    def test_defrag_preserves_slot_data_example():
        """Hypothesis-free fallback: fixed draws of the same property."""
        _defrag_data_check((8, None, 2), (2, 1, 2), 2)
        _defrag_data_check((2, 1, 8), (1, 2, 1), 1)


def _truncate_data_check(kv_map, keep_tokens):
    """Speculative-rewind property on mixed geometry: truncating one rid
    (1) leaves every other rid's wire data byte-identical at every
    layer's own format, (2) leaves the kept prefix rows intact, and
    (3) resets the dropped rows to the exact zero wire state — the byte
    sums of a rewound pool match a pool that never wrote them."""
    import jax.numpy as jnp
    pool = PagedKVPool(TINY, n_pages=N_PAGES, page_size=PAGE_SIZE,
                       kv_bits=kv_map, kv_group=KV_GROUP)
    fresh = PagedKVPool(TINY, n_pages=N_PAGES, page_size=PAGE_SIZE,
                        kv_bits=kv_map, kv_group=KV_GROUP)
    rids, n_pages_each = [1, 2], 3
    for r in rids:
        assert pool.alloc(r, n_pages_each)
    total = n_pages_each * PAGE_SIZE
    x = jax.random.normal(jax.random.key(7),
                          (1, total, TINY.n_kv_heads, TINY.head_dim))
    for bits, seg in zip(_segment_bits(kv_map), _runs(pool, kv_map)):
        kw = {} if bits is None else dict(bits=bits, group_size=KV_GROUP)
        leaf = jax.tree.map(lambda a: a[0], seg[0]["self"]["k"])
        for r in rids:
            ids = pool.pages_of(r)
            page_idx = jnp.asarray([[ids[t // PAGE_SIZE]
                                     for t in range(total)]])
            row = jnp.asarray([[t % PAGE_SIZE for t in range(total)]])
            leaf = kvwire.scatter_tokens(leaf, x, page_idx, row,
                                         page_size=PAGE_SIZE, **kw)
        seg[0]["self"]["k"] = jax.tree.map(lambda a: a[None], leaf)

    def rows_of(r):
        tbl = jnp.asarray([pool.pages_of(r)], jnp.int32)
        return [jax.tree.map(
            lambda a: np.asarray(kvwire.gather_pages(
                a[0], tbl, PAGE_SIZE, TINY.n_kv_heads)),
            seg[0]["self"]["k"])
            for seg in _runs(pool, kv_map)]

    before = {r: rows_of(r) for r in rids}
    old_pages_1 = pool.pages_of(1)
    freed = pool.truncate(1, keep_tokens)
    assert freed == n_pages_each - -(-keep_tokens // PAGE_SIZE)
    _check_invariants(pool)
    # (1) the untouched rid reads back byte-identical wire data
    for want, got in zip(before[2], rows_of(2)):
        jax.tree.map(np.testing.assert_array_equal, want, got)
    kept_pages = pool.pages_of(1)
    assert kept_pages == old_pages_1[:len(kept_pages)]   # no realloc
    dropped = [p for p in old_pages_1 if p not in kept_pages]
    def logical(a):                  # (n_pages, page_size, KV, X)
        return np.asarray(kvwire.logical_pages(a[0], PAGE_SIZE,
                                               TINY.n_kv_heads))

    for s, seg in enumerate(_runs(pool, kv_map)):
        leaf = jax.tree.map(logical, seg[0]["self"]["k"])
        fresh_leaf = jax.tree.map(
            logical, _runs(fresh, kv_map)[s][0]["self"]["k"])
        view = before[1][s]          # gathered (1, total, ...) pre-rewind
        for t in range(len(kept_pages) * PAGE_SIZE):
            got = jax.tree.map(
                lambda a: a[kept_pages[t // PAGE_SIZE], t % PAGE_SIZE],
                leaf)
            if t < keep_tokens:      # (2) kept prefix intact
                jax.tree.map(
                    lambda a, w: np.testing.assert_array_equal(a, w[0, t]),
                    got, view)
            else:                    # (3) rewound rows: zero wire state
                jax.tree.map(
                    lambda a, f: np.testing.assert_array_equal(
                        a, f[0, t % PAGE_SIZE]),
                    got, fresh_leaf)
        # (3) released pages read as never-written pool bytes
        for p in dropped:
            jax.tree.map(
                lambda a, b: np.testing.assert_array_equal(a[p], b[p]),
                leaf, fresh_leaf)


@pytest.mark.parametrize("keep_tokens", [0, 3, 4, 7, 12])
def test_truncate_preserves_other_slots_and_zeroes_suffix(keep_tokens):
    _truncate_data_check((8, None, 2), keep_tokens)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(kv_map=st.sampled_from([(8, None, 2), (2, 2, 8), (2, 1, 8)]),
           keep_tokens=st.integers(0, 12))
    def test_truncate_property_mixed_geometry(kv_map, keep_tokens):
        _truncate_data_check(kv_map, keep_tokens)


SCRATCH_SENTINEL = 1e33


def _poison_scratch(pool):
    """Fill page 0 with unmistakable garbage at every leaf — the state a
    decode step leaves behind after scatter-writing inactive slots (whose
    padded table entries all point at scratch)."""
    import jax.numpy as jnp

    def poison(a):
        bad = 255 if a.dtype == jnp.uint8 else SCRATCH_SENTINEL
        # page axis: 1 on stacked super leaves (S, n_pages, ·, ·), 0 on
        # tail leaves (n_pages, ·, ·)
        return a.at[:, 0].set(bad) if a.ndim == 4 else a.at[0].set(bad)

    pool.pages = jax.tree.map(poison, pool.pages)


def _iter_page_leaves(pool):
    """Every (n_pages, ...) stored page array of the pool, destacked."""
    pages = pool.pages
    blocks = [(blk, True) for run in transformer.stack_runs(pages)
              for blk in run]
    for blk in pages.get("tail", ()):
        blocks.append((blk, False))
    for blk, stacked in blocks:
        for leaf in blk.get("self", {}).values():
            for a in jax.tree.leaves(leaf):
                if stacked:
                    for i in range(a.shape[0]):
                        yield a[i]
                else:
                    yield a


def _assert_live_rows_clean(pool, rid):
    """The hygiene property: the first ``live`` rows of rid's gathered
    view (the only rows the position mask ever exposes) contain no trace
    of scratch.  With no real writes in these sequences, clean == the
    exact zero wire state."""
    n = len(pool.pages_of(rid))
    if not n:
        return
    live = n * pool.page_size
    tbl = np.asarray(pool.table_array(rid, pool.n_pages))
    assert (tbl[:n] != 0).all()          # live prefix never maps to scratch
    for a in _iter_page_leaves(pool):
        view = kvwire.logical_pages(np.asarray(a)[tbl], pool.page_size,
                                    TINY.n_kv_heads)
        view = np.asarray(view).reshape(-1, *view.shape[2:])[:live]
        assert not view.any(), \
            f"scratch bytes leaked into rid {rid}'s live rows"


def _scratch_hygiene_check(kv_map, ops):
    pool = PagedKVPool(TINY, n_pages=N_PAGES, page_size=PAGE_SIZE,
                       kv_bits=kv_map, kv_group=KV_GROUP)
    _poison_scratch(pool)
    shadow = _run_ops(pool, ops)
    for rid in shadow:
        _assert_live_rows_clean(pool, rid)
    # scratch is STILL garbage: hygiene is an allocator + position-mask
    # guarantee (page 0 is never handed out; padded table entries sit past
    # the live prefix), not a zeroing pass — nothing needs to scrub it
    dirty = any(bool(np.asarray(a)[0].all())
                for a in _iter_page_leaves(pool))
    assert dirty, "scratch was scrubbed: the test lost its teeth"


def test_scratch_garbage_never_reaches_live_rows():
    """Overflow + free + defrag + truncate + realloc with a poisoned
    scratch page: no sequence can surface scratch bytes inside any
    slot's position-visible rows (the fused kernel and the XLA gather
    both read exactly these rows)."""
    ops = [(0, 1, 3), (0, 2, 3), (0, 3, 9), (1, 1, 0), (2, 0, 0),
           (0, 3, 4), (3, 3, 5), (0, 1, 2), (1, 2, 0), (0, 4, 9),
           (2, 0, 0), (0, 4, 2), (3, 4, 0), (0, 2, 1)]
    for kv_map in [(8, None, 2), (8, 8, 8), (None,) * 3]:
        _scratch_hygiene_check(kv_map, ops)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(kv_map=st.sampled_from(KV_MAPS),
           ops=st.lists(
               st.tuples(st.integers(0, 3), st.integers(1, 5),
                         st.integers(0, 12)),
               min_size=1, max_size=24))
    def test_scratch_hygiene_property(kv_map, ops):
        _scratch_hygiene_check(kv_map, ops)


def test_random_write_rewind_defrag_sequences():
    """Interleaved write/rewind/defrag on mixed geometry: rewinds never
    alias pages (invariants hold at every step) and the allocator's view
    stays consistent with the shadow bookkeeping."""
    rng = np.random.default_rng(11)
    for kv_map in KV_MAPS[:3]:
        pool = PagedKVPool(TINY, n_pages=N_PAGES, page_size=PAGE_SIZE,
                           kv_bits=kv_map, kv_group=KV_GROUP)
        ops = [(int(rng.integers(0, 4)), int(rng.integers(1, 5)),
                int(rng.integers(0, 12))) for _ in range(40)]
        _run_ops(pool, ops)
        assert pool.nbytes() == _expected_nbytes(
            TINY, kv_map, N_PAGES, PAGE_SIZE, KV_GROUP)
