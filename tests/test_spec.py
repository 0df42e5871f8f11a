"""Self-speculative decoding: acceptance logic, multi-token paged decode,
pool rewind, draft/verifier weight sharing, and the token-exactness bar —
speculative greedy output == verifier-only engine, with one compiled
batched verify step."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kvwire
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.plan import QuantPlan
from repro.plan.plan import candidates_for
from repro.serve import (EngineConfig, PagedConfig, PagedEngine,
                         PagedKVPool, RequestParams, Server)
from repro.spec import (PairedKVPool, SpeculativeEngine, accept_lengths,
                        emitted_tokens, shared_segment_keys)

TINY = ModelConfig(name="tiny", family="dense", n_layers=3, d_model=64,
                   vocab_size=256, n_heads=4, n_kv_heads=2, head_dim=16,
                   d_ff=128, dtype="float32", remat="none")

CANDS = candidates_for(TINY, ["lq8w", "lq4w", "lq2w"])
PCFG = PagedConfig(max_slots=2, page_size=4, n_pages=40, max_context=32)


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(TINY, jax.random.key(0))


def _prompts(seed=1, lens=(7, 12, 5)):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(0, 256, size=n))) for n in lens]


def _plan(scheme, kv=None, kv_default=None):
    p = QuantPlan(default=CANDS[scheme]) if scheme != "fp32" else \
        QuantPlan.uniform("fp32")
    if kv is not None or kv_default is not None:
        p = p.with_kv(kv or {}, default=kv_default, kv_group=16)
    return p


# ---------------------------------------------------------------------------
# acceptance logic (pure)
# ---------------------------------------------------------------------------

def test_accept_lengths_longest_prefix():
    props = np.array([[1, 2, 3], [1, 9, 3], [9, 2, 3], [1, 2, 9]])
    greedy = np.array([[1, 2, 3, 4], [1, 2, 3, 4], [1, 2, 3, 4],
                       [1, 2, 3, 4]])
    assert accept_lengths(props, greedy).tolist() == [3, 1, 0, 2]


def test_emitted_tokens_rules():
    props = np.array([[1, 2, 3], [1, 9, 3], [9, 2, 3]])
    greedy = np.array([[1, 2, 3, 4], [1, 2, 3, 4], [1, 2, 3, 4]])
    m = accept_lengths(props, greedy)
    out = emitted_tokens(props, greedy, m)
    # full acceptance: the k proposals, NO bonus token (g_3 == 4 dropped)
    assert out[0] == [1, 2, 3]
    # partial: accepted prefix + the verifier's correction g_m
    assert out[1] == [1, 2]
    # immediate mismatch: just the correction g_0 — a plain decode step
    assert out[2] == [1]
    # every emitted token is a verifier greedy token
    for toks in out:
        assert all(t in greedy for t in toks)


# ---------------------------------------------------------------------------
# multi-token paged decode == k sequential steps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_bits", [None, 8, 2])
def test_decode_multi_matches_sequential_steps(params, kv_bits):
    """The length-k verify forward writes the same cache bytes and scores
    the same greedy tokens as k single-token steps."""
    kw = dict(kv_bits=kv_bits, kv_group=16) if kv_bits else {}
    eng = PagedEngine(TINY, params, EngineConfig(max_len=32, **kw), PCFG)
    prompt = _prompts()[0]

    def prefilled():
        pool = eng.new_pool()
        assert pool.alloc(0, 4)
        first = eng.prefill_request(pool, prompt, pool.pages_of(0),
                                    jax.random.key(0), 1)
        return pool, first

    pool_a, first = prefilled()
    table = np.stack([pool_a.table_array(0, PCFG.pages_per_slot),
                      np.zeros(PCFG.pages_per_slot, np.int32)])
    pos0 = np.array([len(prompt), 0], np.int32)
    run = np.array([[first, 11, 22], [0, 0, 0]], np.int32)

    greedy_multi = eng.decode_multi_batch(pool_a, run, table, pos0)

    pool_b, _ = prefilled()
    seq = []
    for i in range(run.shape[1]):
        toks = eng.decode_step_batch(pool_b, run[:, i], table, pos0 + i,
                                     jax.random.key(1), i)
        seq.append(toks)
    seq = np.stack(seq, axis=1)
    np.testing.assert_array_equal(greedy_multi[0], seq[0])
    # and the pool bytes agree leaf-for-leaf (same rows written)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), pool_a.pages, pool_b.pages)


# ---------------------------------------------------------------------------
# pool rewind: truncate un-writes without realloc
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_map", [(8, 8, 8), (8, None, 2), None])
def test_truncate_restores_never_speculated_bytes(kv_map):
    """After writing 10 rows and truncating to 5, the pool is
    byte-identical to one where only 5 rows were ever written — across
    homogeneous, heterogeneous, and fp geometries."""
    def build_and_write(n_rows):
        kw = {} if kv_map is None else dict(kv_bits=kv_map, kv_group=16)
        pool = PagedKVPool(TINY, n_pages=8, page_size=4, **kw)
        assert pool.alloc(1, 3)                 # rows 0..11 available
        x = jax.random.normal(jax.random.key(0),
                              (1, 12, TINY.n_kv_heads, TINY.head_dim))
        ids = pool.pages_of(1)
        wpos = np.arange(n_rows)
        page_idx = jnp.asarray([[ids[p // 4] for p in wpos]])
        row = jnp.asarray([wpos % 4])
        runs = transformer.stack_runs(pool.pages)
        assert len(runs) == (3 if kv_map == (8, None, 2) else 1)
        for seg in runs:
            for blk in seg:
                leaf = blk["self"]["k"]
                sample = jax.tree.leaves(leaf)[0]
                stack = sample.shape[0]
                one = jax.tree.map(lambda a: a[0], leaf)
                spec = kvwire.page_spec(one, TINY.head_dim,
                                        4 * TINY.n_kv_heads)
                kw2 = ({} if spec is None
                       else dict(bits=spec[0], group_size=16))
                one = kvwire.scatter_tokens(one, x[:, :n_rows], page_idx,
                                            row, page_size=4, **kw2)
                blk["self"]["k"] = jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None],
                                               (stack,) + a.shape), one)
        return pool

    full = build_and_write(10)
    freed = full.truncate(1, 5)
    assert freed == 1                           # rows 8..11's page released
    ref = build_and_write(5)                    # ref page 3 alloc'd, zero
    assert full.pages_of(1) == ref.pages_of(1)[:2]
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), full.pages, ref.pages)
    assert full.n_free == ref.n_free + 1        # ref still owns 3 pages


def test_truncate_validation_and_page_accounting():
    pool = PagedKVPool(TINY, n_pages=8, page_size=4)
    assert pool.alloc(1, 3) and pool.alloc(2, 2)
    with pytest.raises(ValueError):
        pool.truncate(1, -1)
    with pytest.raises(ValueError):             # can't keep more than owned
        pool.truncate(1, 13)
    assert pool.truncate(1, 12) == 0            # exact fit: nothing freed
    assert pool.truncate(1, 5) == 1             # 2 pages cover 5 tokens
    assert pool.pages_of(2) == pool.pages_of(2)  # other rids untouched
    assert pool.truncate(1, 0) == 2             # full release
    assert pool.n_free == pool.n_allocatable - 2
    assert pool.truncate(99, 0) == 0            # unknown rid is a no-op


def test_paired_pool_defrag_permutes_draft_coherently():
    pool = PairedKVPool(TINY, n_pages=10, page_size=4, kv_bits=8,
                        kv_group=16, draft_kv_bits=2, draft_kv_group=16)
    pool.alloc(1, 2), pool.alloc(2, 3), pool.alloc(3, 1)
    x = jax.random.normal(jax.random.key(0),
                          (1, 1, TINY.n_kv_heads, TINY.head_dim))
    page = jnp.asarray([[pool.pages_of(2)[0]]])
    row = jnp.asarray([[0]])
    def block0(side):
        (run,) = transformer.stack_runs(side)  # a uniform map is one run
        return run[0]["self"]

    for side, bits in ((pool.pages, 8), (pool.draft.pages, 2)):
        leaf = jax.tree.map(lambda a: a[0], block0(side)["k"])
        leaf = kvwire.scatter_tokens(leaf, x, page, row, page_size=4,
                                     bits=bits, group_size=16)
        block0(side)["k"] = jax.tree.map(lambda a: a[None], leaf)

    def views():
        tbl = jnp.asarray([pool.pages_of(2)], jnp.int32)
        return [jax.tree.map(
            lambda a: kvwire.gather_pages(a[0], tbl, 4, TINY.n_kv_heads),
            block0(side)["k"])
            for side in (pool.pages, pool.draft.pages)]

    before = views()
    pool.free(1)
    pool.defrag()
    after = views()
    for want, got in zip(before, after):
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), want, got)


# ---------------------------------------------------------------------------
# the acceptance bar: speculative greedy == verifier-only, exactly
# ---------------------------------------------------------------------------

def _run_server(srv, prompts, max_new):
    rids = []
    for i, (p, n) in enumerate(zip(prompts, max_new)):
        rids.append(srv.submit(p, RequestParams(max_new_tokens=n)))
        srv.step()                              # staggered arrivals
    outs = srv.drain(max_steps=400)
    return [outs[r] for r in rids]


@pytest.mark.parametrize("verifier,draft", [
    # (draft=2-bit, verifier=8-bit) over a mixed per-layer kv plan
    (_plan("lq8w", kv={"layer.0": 8}, kv_default=2), _plan("lq2w")),
    # (draft=4-bit, verifier=fp) — draft carries its own mixed kv map
    (_plan("fp32", kv={"layer.0": 8}, kv_default=2),
     _plan("lq4w", kv={"layer.0": 8}, kv_default=2)),
])
@pytest.mark.parametrize("spec_k", [2, 3])
def test_speculative_matches_verifier_only_token_for_token(
        params, verifier, draft, spec_k):
    """Speculative greedy decode is token-for-token identical to the
    verifier-only PagedEngine across mixed weight x kv plans, with ONE
    compiled trace for the batched verify step."""
    prompts = _prompts()
    max_new = [10, 6, 8]
    ecfg = EngineConfig(max_len=32, plan=verifier, backend="ref")

    ref = _run_server(Server(TINY, params, ecfg, PCFG), prompts, max_new)

    eng = SpeculativeEngine(TINY, params, ecfg, PCFG, draft_plan=draft,
                            spec_k=spec_k)
    srv = Server(TINY, params, ecfg, PCFG, engine=eng)
    outs = _run_server(srv, prompts, max_new)

    assert outs == ref
    assert eng.decode_compilations == 1          # one batched verify trace
    assert eng.draft_compilations == 1           # one draft step trace
    s = srv.scheduler.stats()
    assert s["preemptions"] == 0                 # rollbacks != preemptions
    if s["rejected_tokens"]:
        assert eng.verify_steps_per_token() < 1.0 or \
            eng.acceptance_rate() == 0.0
    assert eng.verify_steps_per_token() <= 1.0


def test_mismatched_verifier_scheme_still_exact(params):
    """A uniform-scheme verifier (no plan) with a planned draft: the
    engine quantizes the verifier through the scheme path and stays
    token-exact (no weight sharing possible, shared bytes == 0)."""
    ecfg = EngineConfig(max_len=32, weight_scheme="lq8w", a_bits=8,
                        kv_bits=8, kv_group=16, backend="ref")
    prompts = _prompts()
    max_new = [8, 6, 7]
    ref = _run_server(Server(TINY, params, ecfg, PCFG), prompts, max_new)
    eng = SpeculativeEngine(TINY, params, ecfg, PCFG,
                            draft_plan=_plan("lq2w"), spec_k=2)
    srv = Server(TINY, params, ecfg, PCFG, engine=eng)
    assert _run_server(srv, prompts, max_new) == ref
    assert eng.shared_weight_bytes() == 0


def test_speculative_survives_preemption_exactly(params):
    """Pool pressure under speculation: lookahead pages force preemption;
    the rolled-back victim still reproduces the verifier-only stream."""
    prompts = _prompts()[:2]
    max_new = [14, 14]
    ecfg = EngineConfig(max_len=32, plan=_plan("lq8w"), backend="ref")
    tight = PagedConfig(max_slots=2, page_size=4, n_pages=11,
                        max_context=32)
    ref = _run_server(Server(TINY, params, ecfg, tight), prompts, max_new)

    eng = SpeculativeEngine(TINY, params, ecfg, tight,
                            draft_plan=_plan("lq2w"), spec_k=2)
    srv = Server(TINY, params, ecfg, tight, engine=eng)
    outs = _run_server(srv, prompts, max_new)
    assert outs == ref
    assert srv.pool.n_allocated == 0


def test_identical_plans_accept_everything(params):
    """Draft == verifier: every proposal accepted, k tokens per cycle,
    verifier steps/token == 1/k, and the packed leaves are SHARED."""
    plan = _plan("lq8w", kv={}, kv_default=8)
    ecfg = EngineConfig(max_len=32, plan=plan, backend="ref")
    eng = SpeculativeEngine(TINY, params, ecfg, PCFG, draft_plan=plan,
                            spec_k=3)
    srv = Server(TINY, params, ecfg, PCFG, engine=eng)
    rid = srv.submit(_prompts()[0], RequestParams(max_new_tokens=13))
    srv.drain(max_steps=200)
    assert len(srv.output(rid)) == 13
    assert eng.acceptance_rate() == 1.0
    # 12 post-prefill tokens in 4 cycles of k=3
    assert eng.verify_steps_per_token() == pytest.approx(1 / 3, abs=0.01)
    assert srv.scheduler.stats()["rejected_tokens"] == 0
    # full sharing: draft params ARE the verifier's buffers
    v_leaves = jax.tree.leaves(eng.verifier.params["decoder"])
    d_leaves = jax.tree.leaves(eng.draft.params["decoder"])
    assert all(x is y for x, y in zip(v_leaves, d_leaves))
    assert eng.shared_weight_bytes() > 0


def test_rejected_tokens_counted_not_preempted(params):
    """The satellite bar: speculative rejections roll the slot back in
    place — rejected_tokens counts them, preemptions stays 0."""
    ecfg = EngineConfig(max_len=32, plan=_plan("lq8w"), backend="ref")
    eng = SpeculativeEngine(TINY, params, ecfg, PCFG,
                            draft_plan=_plan("lq2w"), spec_k=3)
    srv = Server(TINY, params, ecfg, PCFG, engine=eng)
    rids = [srv.submit(p, RequestParams(max_new_tokens=8))
            for p in _prompts()]
    srv.drain(max_steps=300)
    s = srv.scheduler.stats()
    assert s["rejected_tokens"] > 0              # 2-bit draft misses often
    assert s["preemptions"] == 0
    done = [srv.scheduler.request(r) for r in rids]
    assert sum(r.rejected_tokens for r in done) == s["rejected_tokens"]


# ---------------------------------------------------------------------------
# weight sharing mechanics
# ---------------------------------------------------------------------------

def test_shared_segment_keys_partial_overlap(params):
    verifier = QuantPlan.from_assignment({"layer.0": CANDS["lq8w"]},
                                         default=CANDS["lq4w"])
    draft = QuantPlan.from_assignment({"layer.0": CANDS["lq8w"]},
                                      default=CANDS["lq2w"])
    shared = shared_segment_keys(TINY, verifier, draft)
    assert shared                                # layer.0's segment aligns
    assert all(k[-1] == CANDS["lq8w"] for k in shared)
    eng = SpeculativeEngine(TINY, params,
                            EngineConfig(max_len=32, plan=verifier,
                                         backend="ref"),
                            PCFG, draft_plan=draft, spec_k=2)
    assert set(eng.shared_keys) == set(shared)
    assert 0 < eng.shared_weight_bytes()


def test_engine_validation(params):
    ecfg = EngineConfig(max_len=32, plan=_plan("lq8w"), backend="ref")
    with pytest.raises(ValueError, match="greedy-only"):
        SpeculativeEngine(TINY, params,
                          dataclasses.replace(ecfg, temperature=0.7),
                          PCFG, draft_plan=_plan("lq2w"))
    with pytest.raises(ValueError, match="spec_k"):
        SpeculativeEngine(TINY, params, ecfg, PCFG,
                          draft_plan=_plan("lq2w"), spec_k=0)
    with pytest.raises(ValueError, match="draft"):
        SpeculativeEngine(TINY, params, ecfg, PCFG, draft_plan=None)
    packed = transformer.quantize_params(params, TINY, _plan("lq8w"))
    with pytest.raises(ValueError, match="raw fp checkpoint"):
        SpeculativeEngine(TINY, packed, ecfg, PCFG,
                          draft_plan=_plan("lq2w"))


def test_draft_shadow_mirrors_verifier_plan_kv_map(params):
    """A verifier plan with a per-layer kv map must NOT leave the draft's
    shadow pool at fp pages: a draft plan without its own kv map mirrors
    the verifier's resolved per-layer layout."""
    verifier = _plan("lq8w", kv={"layer.0": 8}, kv_default=2)
    ecfg = EngineConfig(max_len=32, plan=verifier, backend="ref")
    eng = SpeculativeEngine(TINY, params, ecfg, PCFG,
                            draft_plan=_plan("lq2w"), spec_k=2)
    assert eng.draft._kv_layout == eng.verifier._kv_layout
    pool = eng.new_pool()
    assert pool.draft_nbytes() == pool.nbytes()     # same wire geometry
    assert len(transformer.stack_runs(pool.draft.pages)) > 1  # per-layer
    fp = PagedKVPool(TINY, n_pages=PCFG.n_pages,
                     page_size=PCFG.page_size).nbytes()
    assert pool.draft_nbytes() < fp                 # not fp fallback


def test_draft_rows_overwritten_before_read(params):
    """The no-rewind draft invariant: long drains never let a stale draft
    row reach an attention read (checked indirectly — a run with heavy
    rejection still matches the verifier-only stream exactly, which
    would fail if stale draft K/V leaked into later proposals' context
    and desynced the draft from its own accepted history)."""
    ecfg = EngineConfig(max_len=48, plan=_plan("lq8w"), backend="ref")
    pcfg = PagedConfig(max_slots=2, page_size=4, n_pages=40,
                       max_context=48)
    prompts = _prompts(lens=(5, 9))
    max_new = [30, 24]
    ref = _run_server(Server(TINY, params, ecfg, pcfg), prompts, max_new)
    eng = SpeculativeEngine(TINY, params, ecfg, pcfg,
                            draft_plan=_plan("lq2w"), spec_k=4)
    srv = Server(TINY, params, ecfg, pcfg, engine=eng)
    assert _run_server(srv, prompts, max_new) == ref
    assert eng.decode_compilations == 1
