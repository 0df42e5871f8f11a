"""Continuous-batching serve layer: pool invariants, scheduler fairness /
preemption, and end-to-end parity with the solo engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kvwire
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.serve import (Engine, EngineConfig, PagedConfig, PagedKVPool,
                         RequestParams, Server)

TINY = ModelConfig(name="tiny", family="dense", n_layers=3, d_model=64,
                   vocab_size=256, n_heads=4, n_kv_heads=2, head_dim=16,
                   d_ff=128, dtype="float32", remat="none")


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(TINY, jax.random.key(0))


def _prompts(seed=1, lens=(7, 12, 5)):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(0, 256, size=n))) for n in lens]


def _solo(params, prompt, n_tokens, **ecfg_kw):
    eng = Engine(TINY, params, EngineConfig(max_len=32, **ecfg_kw))
    out, _ = eng.generate({"tokens": jnp.asarray([prompt], jnp.int32)},
                          steps=n_tokens - 1)
    return np.asarray(out)[0].tolist()


# ---------------------------------------------------------------------------
# pool: alloc / free / defrag invariants
# ---------------------------------------------------------------------------

def test_pool_alloc_free_invariants():
    pool = PagedKVPool(TINY, n_pages=8, page_size=4)
    assert pool.n_allocatable == 7 and pool.n_free == 7
    assert pool.alloc(1, 3) and pool.alloc(2, 2)
    assert pool.n_free == 2 and pool.n_allocated == 5
    handed = pool.pages_of(1) + pool.pages_of(2)
    assert 0 not in handed                     # scratch page never allocated
    assert len(set(handed)) == 5               # no double allocation
    assert not pool.alloc(3, 3)                # all-or-nothing exhaustion
    assert pool.n_free == 2                    # failed alloc takes nothing
    assert pool.free(1) == 3
    assert pool.n_free == 5
    assert pool.alloc(3, 5)                    # freed pages are reusable
    assert pool.free(99) == 0                  # unknown rid is a no-op


def test_pool_table_array_padding():
    pool = PagedKVPool(TINY, n_pages=8, page_size=4)
    pool.alloc(7, 2)
    tbl = pool.table_array(7, 5)
    assert tbl.shape == (5,) and tbl.dtype == np.int32
    assert list(tbl[:2]) == pool.pages_of(7)
    assert (tbl[2:] == 0).all()                # scratch-padded tail


@pytest.mark.parametrize("kv_bits", [None, 8, 2])
def test_pool_defrag_preserves_contents(kv_bits):
    pool = PagedKVPool(TINY, n_pages=10, page_size=4, kv_bits=kv_bits,
                       kv_group=16)
    pool.alloc(1, 2), pool.alloc(2, 3), pool.alloc(3, 1)
    # write recognizable data into request 2's pages (layer pattern pos 0)
    runs = transformer.stack_runs(pool.pages)
    assert len(runs) == 1                      # a uniform map is one run
    leaf = runs[0][0]["self"]["k"]
    x = jax.random.normal(jax.random.key(0),
                          (TINY.n_super, 3 * 4, TINY.n_kv_heads,
                           TINY.head_dim))
    contig = (x[:, None] if kv_bits is None
              else kvwire.quantize_kv(x[:, None], kv_bits, 16))
    ids = jnp.asarray(pool.pages_of(2), jnp.int32)
    written = kvwire.scatter_prefill(leaf, contig, ids, stacked=True)
    runs[0][0]["self"]["k"] = written
    tbl_before = jnp.asarray([pool.table_array(2, 3)])
    before = jax.tree.map(lambda a: kvwire.gather_pages(   # superblock 0's
        a[0], tbl_before, 4, TINY.n_kv_heads), written)     # page view

    pool.free(1)                               # leave a hole, then compact
    mapping = pool.defrag()
    assert sorted(p for t in pool.page_tables.values() for p in t) == \
        list(range(1, pool.n_allocated + 1))   # compact, scratch untouched
    assert len(mapping) == 4                   # covers every allocated page
    tbl_after = jnp.asarray([pool.table_array(2, 3)])
    after = jax.tree.map(
        lambda a: kvwire.gather_pages(a[0], tbl_after, 4, TINY.n_kv_heads),
        transformer.stack_runs(pool.pages)[0][0]["self"]["k"])
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), before, after)
    assert pool.n_free == pool.n_allocatable - pool.n_allocated


def test_pool_rejects_unsupported_archs():
    ssm = ModelConfig(name="tssm", family="ssm", n_layers=2, d_model=64,
                      vocab_size=256, d_ff=0, rope=False,
                      pattern=(("mamba2", "none"),), ssm_state=16,
                      ssm_head_dim=16, dtype="float32")
    with pytest.raises(ValueError):
        PagedKVPool(ssm, n_pages=8, page_size=4)


def test_pool_bytes_shrink_with_kv_bits():
    sizes = [PagedKVPool(TINY, n_pages=16, page_size=8, kv_bits=b,
                         kv_group=16).nbytes() for b in (None, 8, 4, 2)]
    assert sizes == sorted(sizes, reverse=True)


# ---------------------------------------------------------------------------
# wire-level paged helpers
# ---------------------------------------------------------------------------

def test_gather_scatter_token_roundtrip():
    leaf = kvwire.make_paged_kv(6, 4, 2, 32, bits=8, group_size=16)
    new = jax.random.normal(jax.random.key(3), (2, 1, 2, 32))
    # slot 0 -> page 2 row 1 (pos 9, table [1,2]); slot 1 -> page 4 row 0
    leaf = kvwire.scatter_token(leaf, new, jnp.asarray([2, 4]),
                                jnp.asarray([1, 0]), page_size=4, bits=8,
                                group_size=16)
    table = jnp.asarray([[1, 2], [4, 3]], jnp.int32)
    view = kvwire.dequantize_kv(kvwire.gather_pages(leaf, table, 4, 2), 32)
    np.testing.assert_allclose(np.asarray(view[0, 5]),
                               np.asarray(new[0, 0]), rtol=0.05, atol=0.05)
    np.testing.assert_allclose(np.asarray(view[1, 0]),
                               np.asarray(new[1, 0]), rtol=0.05, atol=0.05)
    assert float(jnp.abs(view[0, 0]).max()) == 0      # untouched rows


# (page_size, kv heads, head_dim, bits): the chat cells' 128-row pages,
# 64-row pages (two feature blocks share the lanes), small pages folding
# further, and fp pages
STORED = {"r128": (16, 8, 64, 4), "r64": (16, 4, 128, 4),
          "r8": (4, 2, 32, 8), "fp": (4, 2, 32, None)}


@pytest.mark.parametrize("geom", STORED)
def test_stored_layout_round_trip(geom):
    """Every page op on the stored layout, read back through the logical
    view, equals a straightforward write of the same tokens into logical
    (n_pages, page_size, KV, X) pages: token scatter at one layer of a
    stack, the prefill bucket's set, a rewind, a defrag permutation."""
    ps, kvh, d, bits = STORED[geom]
    n, gs = 9, 16
    key = jax.random.key(5)
    wire = ((lambda x: x) if bits is None
            else (lambda x: kvwire.quantize_kv(x, bits, gs)))
    kw = {} if bits is None else dict(bits=bits, group_size=gs)
    logical = jax.tree.map(
        lambda a: jnp.stack([a, a]),
        jax.tree.map(jnp.zeros_like, wire(jnp.zeros((n, ps, kvh, d)))))
    stored = jax.tree.map(lambda a: jnp.stack([a, a]),
                          kvwire.make_paged_kv(n, ps, kvh, d, bits, gs))

    def view(t):
        return jax.tree.map(np.asarray, kvwire.logical_pages(t, ps, kvh))

    def check():
        jax.tree.map(np.testing.assert_array_equal, view(stored),
                     jax.tree.map(np.asarray, logical))
        jax.tree.map(np.testing.assert_array_equal,
                     kvwire.stored_pages(kvwire.logical_pages(
                         stored, ps, kvh)), stored)

    check()
    # a run of 3 tokens for 2 slots into layer 1; slot 1 crosses a page
    new = jax.random.normal(key, (2, 3, kvh, d))
    page = jnp.asarray([[2, 2, 2], [5, 5, 7]])
    row = jnp.asarray([[0, 1, 2], [ps - 2, ps - 1, 0]])
    stored = kvwire.scatter_tokens(stored, new, page, row, page_size=ps,
                                   layer=jnp.asarray(1), **kw)
    logical = jax.tree.map(lambda a, w: a.at[1, page, row].set(w),
                           logical, wire(new))
    check()
    # the prefill bucket: 2 pages of one request into both layers
    ids = jnp.asarray([3, 4])
    bucket = jax.random.normal(jax.random.fold_in(key, 1),
                               (2, 1, 2 * ps, kvh, d))
    stored = kvwire.scatter_prefill(stored, wire(bucket), ids, stacked=True)
    logical = jax.tree.map(
        lambda a, c: a.at[:, ids].set(c.reshape(2, 2, ps, kvh, -1)),
        logical, wire(bucket))
    check()
    # rewind a table [2, 3, 4, 0, ...] past its 5th token
    table = jnp.asarray([2, 3, 4] + [0] * (n - 3), jnp.int32)
    stored = kvwire.reset_table_rows(stored, table, jnp.asarray(5),
                                     page_size=ps, kv_heads=kvh,
                                     stacked=True)
    t = np.arange(3 * ps)
    for i in np.nonzero(t >= 5)[0]:
        p = int(table[i // ps])
        logical = jax.tree.map(
            lambda a, p=p, r=i % ps: a.at[:, p, r].set(0), logical)
    check()
    # defrag: new page i takes old page perm[i]
    perm = jnp.asarray([0, 4, 3, 2, 1, 8, 7, 6, 5])
    stored = kvwire.permute_pages(stored, perm, stacked=True)
    logical = jax.tree.map(lambda a: a[:, perm], logical)
    check()


# ---------------------------------------------------------------------------
# scheduler: fairness, priority lanes, preemption
# ---------------------------------------------------------------------------

def test_fcfs_completion_order(params):
    srv = Server(TINY, params, EngineConfig(max_len=32),
                 PagedConfig(max_slots=1, page_size=4, n_pages=20,
                             max_context=32))
    done = []
    srv.scheduler.on_complete = lambda c: done.append(c.rid)
    rids = [srv.submit(p, RequestParams(max_new_tokens=4))
            for p in _prompts()]
    srv.drain()
    assert done == rids                        # FCFS with one slot


def test_priority_lane_admitted_first(params):
    srv = Server(TINY, params, EngineConfig(max_len=32),
                 PagedConfig(max_slots=1, page_size=4, n_pages=20,
                             max_context=32))
    done = []
    srv.scheduler.on_complete = lambda c: done.append(c.rid)
    p = _prompts()
    running = srv.submit(p[0], RequestParams(max_new_tokens=4))
    srv.step()                                 # p[0] takes the only slot
    low = srv.submit(p[1], RequestParams(max_new_tokens=4, priority=0))
    high = srv.submit(p[2], RequestParams(max_new_tokens=4, priority=5))
    srv.drain()
    # admission is non-preemptive (the running request finishes), then the
    # high lane wins the freed slot over the earlier-submitted low request
    assert done == [running, high, low]


def test_preemption_recovers_and_is_exact_fp(params):
    prompts = _prompts()[:2]
    ref = [_solo(params, p, 16) for p in prompts]
    srv = Server(TINY, params, EngineConfig(max_len=32),
                 PagedConfig(max_slots=2, page_size=4, n_pages=10,
                             max_context=32))
    rids = [srv.submit(p, RequestParams(max_new_tokens=16)) for p in prompts]
    outs = srv.drain(max_steps=500)
    assert sum(srv.scheduler.request(r).n_preemptions for r in rids) >= 1
    for r, want in zip(rids, ref):
        assert outs[r] == want                 # recompute resume is exact fp
    assert srv.pool.n_allocated == 0           # everything released


def test_priority_request_survives_preemption_and_matches_solo(params):
    """Priority lanes under pool pressure: when pages run out, the
    low-priority request is the preemption victim; the high-priority
    request is never preempted and reproduces its solo-engine greedy
    tokens exactly (and the fp victim recovers exactly too)."""
    low_p, high_p = _prompts()[:2]
    ref_high = _solo(params, high_p, 16)
    ref_low = _solo(params, low_p, 16)
    srv = Server(TINY, params, EngineConfig(max_len=32),
                 PagedConfig(max_slots=2, page_size=4, n_pages=10,
                             max_context=32))
    low = srv.submit(low_p, RequestParams(max_new_tokens=16, priority=0))
    srv.step()                                 # low takes a slot first
    high = srv.submit(high_p, RequestParams(max_new_tokens=16, priority=5))
    outs = srv.drain(max_steps=500)
    assert srv.scheduler.request(low).n_preemptions >= 1
    assert srv.scheduler.request(high).n_preemptions == 0
    assert outs[high] == ref_high              # uninterrupted, solo-exact
    assert outs[low] == ref_low                # fp recompute resume is exact
    assert srv.scheduler.stats()["preemptions"] >= 1


def test_pool_too_small_for_single_request_rejected(params):
    srv = Server(TINY, params, EngineConfig(max_len=32),
                 PagedConfig(max_slots=2, page_size=4, n_pages=3,
                             max_context=32))
    with pytest.raises(ValueError):            # can never fit: reject upfront
        srv.submit(_prompts()[0], RequestParams(max_new_tokens=16))


def test_submit_validation(params):
    srv = Server(TINY, params, EngineConfig(max_len=32),
                 PagedConfig(max_slots=1, page_size=4, n_pages=20,
                             max_context=32))
    with pytest.raises(ValueError):
        srv.submit([], RequestParams())
    with pytest.raises(ValueError):
        srv.submit(list(range(30)), RequestParams(max_new_tokens=8))
    with pytest.raises(ValueError):            # non-positive token budget
        srv.submit([1, 2, 3], RequestParams(max_new_tokens=0))
    with pytest.raises(ValueError):
        srv.submit([1, 2, 3], RequestParams(max_new_tokens=-4))


def test_submit_rejects_request_pool_can_never_hold(params):
    """A request whose full length exceeds the pool's allocatable pages is
    rejected at submit with a clear error instead of live-locking the
    admit loop (pool: 4 pages x 4 = 16 token-slots < 18 needed)."""
    srv = Server(TINY, params, EngineConfig(max_len=32),
                 PagedConfig(max_slots=1, page_size=4, n_pages=5,
                             max_context=32))
    with pytest.raises(ValueError, match="never be admitted"):
        srv.submit(list(range(10)), RequestParams(max_new_tokens=8))
    srv.submit(list(range(10)), RequestParams(max_new_tokens=6))  # 16 fits
    srv.drain(max_steps=200)


def test_completion_carries_tenant_tag(params):
    srv = Server(TINY, params, EngineConfig(max_len=32),
                 PagedConfig(max_slots=1, page_size=4, n_pages=20,
                             max_context=32))
    done = []
    srv.scheduler.on_complete = done.append
    srv.submit(_prompts()[0], RequestParams(max_new_tokens=2,
                                            tenant="gold"))
    srv.submit(_prompts()[1], RequestParams(max_new_tokens=2))
    srv.drain(max_steps=200)
    assert [c.tenant for c in done] == ["gold", None]


# ---------------------------------------------------------------------------
# end-to-end: continuous batching == solo engine, token for token
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_bits", [None, 8, 2])
def test_staggered_arrivals_match_solo_greedy(params, kv_bits):
    """The acceptance bar: staggered admissions, shared pool, one jit —
    every request reproduces its solo greedy sequence exactly."""
    kw = dict(kv_bits=kv_bits, kv_group=16) if kv_bits else {}
    prompts = _prompts()
    max_new = [10, 6, 8]
    ref = [_solo(params, p, n, **kw) for p, n in zip(prompts, max_new)]

    streamed = {}
    srv = Server(TINY, params, EngineConfig(max_len=32, **kw),
                 PagedConfig(max_slots=2, page_size=4, n_pages=40,
                             max_context=32),
                 on_token=lambda rid, t: streamed.setdefault(rid,
                                                             []).append(t))
    r0 = srv.submit(prompts[0], RequestParams(max_new_tokens=max_new[0]))
    srv.step(); srv.step()
    r1 = srv.submit(prompts[1], RequestParams(max_new_tokens=max_new[1]))
    srv.step()
    r2 = srv.submit(prompts[2], RequestParams(max_new_tokens=max_new[2]))
    outs = srv.drain(max_steps=200)

    for rid, want in zip((r0, r1, r2), ref):
        assert outs[rid] == want
        assert streamed[rid] == want           # streaming saw every token
    assert srv.engine.decode_compilations == 1  # no per-step retrace


# ---------------------------------------------------------------------------
# per-layer kv plans: heterogeneous page geometry, golden-token parity
# ---------------------------------------------------------------------------

def _kv_plan(kv_map, default=None):
    from repro.plan import QuantPlan
    return QuantPlan.uniform("fp32").with_kv(kv_map, default=default,
                                             kv_group=16)


def test_hetero_pool_layout_and_bytes():
    """A mixed kv map stores one stacked leaf per run of same-format
    superblocks; a uniform map collapses to one run."""
    from repro.serve import cache_nbytes, make_pool_pages, pool_nbytes
    mixed = PagedKVPool(TINY, n_pages=8, page_size=4, kv_bits=(8, None, 2),
                        kv_group=16)
    assert len(transformer.stack_runs(mixed.pages)) == 3
    assert pool_nbytes(TINY, n_pages=8, page_size=4, kv_bits=(8, None, 2),
                       kv_group=16) == mixed.nbytes()
    uni = make_pool_pages(TINY, n_pages=8, page_size=4, kv_bits=(2, 2, 2),
                          kv_group=16)
    ref = make_pool_pages(TINY, n_pages=8, page_size=4, kv_bits=2,
                          kv_group=16)
    assert jax.tree.structure(uni) == jax.tree.structure(ref)
    assert len(transformer.stack_runs(ref)) == 1
    assert cache_nbytes(uni) == cache_nbytes(ref)
    # mixed sits strictly between its narrowest and widest uniform pools
    lo = pool_nbytes(TINY, n_pages=8, page_size=4, kv_bits=2, kv_group=16)
    hi = pool_nbytes(TINY, n_pages=8, page_size=4, kv_bits=None)
    assert lo < mixed.nbytes() < hi


def test_hetero_pool_defrag_preserves_contents():
    """Defrag permutes every segment's pages coherently: data written to a
    request's pages at different per-layer bitwidths survives compaction."""
    pool = PagedKVPool(TINY, n_pages=10, page_size=4, kv_bits=(8, None, 2),
                       kv_group=16)
    pool.alloc(1, 2), pool.alloc(2, 3), pool.alloc(3, 1)
    x = jax.random.normal(jax.random.key(0),
                          (1, 3 * 4, TINY.n_kv_heads, TINY.head_dim))
    ids = jnp.asarray(pool.pages_of(2), jnp.int32)
    segs = transformer.stack_runs(pool.pages)
    assert len(segs) == 3
    written = []
    for s, seg in enumerate(segs):
        leaf = seg[0]["self"]["k"]
        bits = (8, None, 2)[s]
        contig = (x[:, None] if bits is None
                  else kvwire.quantize_kv(x[:, None], bits, 16))
        w = kvwire.scatter_prefill(leaf, contig, ids, stacked=True)
        seg[0]["self"]["k"] = w
        written.append(w)
    tbl = jnp.asarray([pool.table_array(2, 3)])
    before = [jax.tree.map(lambda a: kvwire.gather_pages(
                               a[0], tbl, 4, TINY.n_kv_heads), w)
              for w in written]

    pool.free(1)
    mapping = pool.defrag()
    assert len(mapping) == 4
    tbl2 = jnp.asarray([pool.table_array(2, 3)])
    for s, want in enumerate(before):
        got = jax.tree.map(
            lambda a: kvwire.gather_pages(a[0], tbl2, 4, TINY.n_kv_heads),
            transformer.stack_runs(pool.pages)[s][0]["self"]["k"])
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), want, got)


@pytest.mark.parametrize("kv_bits", [None, 8, 2])
def test_uniform_kv_plan_matches_uniform_kv_engine(params, kv_bits):
    """Golden-token parity, degenerate case: a PagedEngine under a plan
    whose kv map is uniform reproduces the plain uniform-kv engine
    token-for-token (and the solo reference), with one compiled step."""
    kw = dict(kv_bits=kv_bits, kv_group=16) if kv_bits else {}
    prompts = _prompts()
    max_new = [8, 6, 7]
    ref = [_solo(params, p, n, **kw) for p, n in zip(prompts, max_new)]

    plan = _kv_plan({}, default=kv_bits)
    srv_plan = Server(TINY, params,
                      EngineConfig(max_len=32, plan=plan, backend="ref"),
                      PagedConfig(max_slots=2, page_size=4, n_pages=40,
                                  max_context=32))
    srv_uni = Server(TINY, params, EngineConfig(max_len=32, **kw),
                     PagedConfig(max_slots=2, page_size=4, n_pages=40,
                                 max_context=32))
    outs = []
    for srv in (srv_plan, srv_uni):
        rids = [srv.submit(p, RequestParams(max_new_tokens=n))
                for p, n in zip(prompts, max_new)]
        done = srv.drain(max_steps=200)
        outs.append([done[r] for r in rids])
        assert srv.engine.decode_compilations == 1
    assert outs[0] == outs[1] == ref
    # and the plan's pool collapsed to one run
    assert len(transformer.stack_runs(srv_plan.engine.new_pool().pages)) == 1


def test_mixed_kv_paged_matches_solo_reference(params):
    """The acceptance bar: a genuinely mixed per-layer kv plan served
    through the heterogeneous paged pool reproduces the solo (non-paged)
    mixed-kv ``engine.generate`` reference token-for-token, decode
    compiled once."""
    plan = _kv_plan({"layer.0": 8, "layer.2": 2}, default=None)
    prompts = _prompts()
    max_new = [10, 6, 8]
    solo = []
    for p, n in zip(prompts, max_new):
        eng = Engine(TINY, params, EngineConfig(max_len=32, plan=plan,
                                                backend="ref"))
        out, _ = eng.generate({"tokens": jnp.asarray([p], jnp.int32)},
                              steps=n - 1)
        solo.append(np.asarray(out)[0].tolist())

    srv = Server(TINY, params,
                 EngineConfig(max_len=32, plan=plan, backend="ref"),
                 PagedConfig(max_slots=2, page_size=4, n_pages=40,
                             max_context=32))
    r0 = srv.submit(prompts[0], RequestParams(max_new_tokens=max_new[0]))
    srv.step(); srv.step()
    r1 = srv.submit(prompts[1], RequestParams(max_new_tokens=max_new[1]))
    srv.step()
    r2 = srv.submit(prompts[2], RequestParams(max_new_tokens=max_new[2]))
    outs = srv.drain(max_steps=200)
    for rid, want in zip((r0, r1, r2), solo):
        assert outs[rid] == want
    assert srv.engine.decode_compilations == 1
    assert len(transformer.stack_runs(srv.pool.pages)) > 1  # heterogeneous


def test_mixed_weights_and_kv_paged_matches_solo(params):
    """Mixed weights AND mixed cache in one plan through the paged path."""
    from repro.plan import QuantPlan
    from repro.plan.plan import candidates_for
    cands = candidates_for(TINY, ["lq8w", "lq2w"])
    plan = QuantPlan.from_assignment(
        {"layer.0": cands["lq8w"]}, default=cands["lq2w"],
        kv_bits={"layer.0": 8}, kv_default=2, kv_group=16)
    prompt = _prompts()[0]
    eng = Engine(TINY, params, EngineConfig(max_len=32, plan=plan,
                                            backend="ref"))
    out, _ = eng.generate({"tokens": jnp.asarray([prompt], jnp.int32)},
                          steps=9)
    solo = np.asarray(out)[0].tolist()
    srv = Server(TINY, params,
                 EngineConfig(max_len=32, plan=plan, backend="ref"),
                 PagedConfig(max_slots=2, page_size=4, n_pages=40,
                             max_context=32))
    rid = srv.submit(prompt, RequestParams(max_new_tokens=10))
    outs = srv.drain(max_steps=200)
    assert outs[rid] == solo
    assert srv.engine.decode_compilations == 1


def test_completions_and_stats(params):
    srv = Server(TINY, params, EngineConfig(max_len=32),
                 PagedConfig(max_slots=2, page_size=4, n_pages=20,
                             max_context=32))
    rid = srv.submit(_prompts()[0], RequestParams(max_new_tokens=1))
    events = srv.step()                        # completes at admission
    assert [c.rid for c in events] == [rid]
    assert len(events[0].tokens) == 1
    s = srv.stats()
    assert s["active"] == 0 and s["queued"] == 0
    assert s["pool_bytes"] > 0
