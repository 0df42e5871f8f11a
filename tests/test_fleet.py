"""Multi-tenant fleet: registry pricing/budget, plan-tagged admission,
weighted round-robin routing, telemetry, and solo-engine parity."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.fleet import (FleetAdmissionError, FleetBudgetError,
                         FleetManifest, FleetRegistry, FleetRouter,
                         FleetTelemetry, TenantSpec, build_fleet,
                         load_manifest)
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.plan import QuantPlan, plan_cost
from repro.serve import PagedEngine, Scheduler, pool_nbytes

TINY = ModelConfig(name="tiny", family="dense", n_layers=3, d_model=64,
                   vocab_size=256, n_heads=4, n_kv_heads=2, head_dim=16,
                   d_ff=128, dtype="float32", remat="none")

GOLD_PLAN = QuantPlan.from_assignment({"layer.0": "lq8w"}, default="lq4w")


def _spec(tid="t0", **kw):
    base = dict(kv_group=16, max_slots=2, page_size=4, n_pages=24,
                max_context=32)
    base.update(kw)
    return TenantSpec(tid, **base)


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(TINY, jax.random.key(0))


def _prompts(seed=3, lens=(6, 9, 5)):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(0, 256, size=n))) for n in lens]


# ---------------------------------------------------------------------------
# spec validation
# ---------------------------------------------------------------------------

def test_spec_rejects_plan_and_scheme():
    with pytest.raises(ValueError):
        TenantSpec("t", plan=GOLD_PLAN, scheme="lq2w")


def test_spec_rejects_bad_weight_and_quota():
    with pytest.raises(ValueError):
        TenantSpec("t", weight=0)
    with pytest.raises(ValueError):
        TenantSpec("t", max_queued=0)
    with pytest.raises(ValueError):
        TenantSpec("")


def test_spec_resolved_plan_fits_regions():
    # registry schemes default to group_size=128; a d_model=64 model must
    # get a fitted region size, matching the planner's candidates_for
    cfgs = _spec(scheme="lq4w").resolved_plan(TINY).resolve(TINY)
    assert all(TINY.d_model % c.group_size == 0 for c in cfgs)
    assert all(c.w_bits == 4 for c in cfgs)


def test_spec_a_bits_folds_into_uniform_plan():
    cfgs = _spec(scheme="lq4w", a_bits=4).resolved_plan(TINY).resolve(TINY)
    assert all(c.a_bits == 4 for c in cfgs)
    with pytest.raises(ValueError):          # per-layer under a plan
        TenantSpec("t", plan=GOLD_PLAN, a_bits=4)


# ---------------------------------------------------------------------------
# registry: pricing + shared budget
# ---------------------------------------------------------------------------

def test_registry_pricing_matches_costmodel(params):
    reg = FleetRegistry(TINY, params, backend="ref")
    spec = _spec(plan=GOLD_PLAN, kv_bits=8)
    priced = reg.price(spec)
    want_w = plan_cost(TINY, spec.resolved_plan(TINY).resolve(TINY))["bytes"]
    want_p = pool_nbytes(TINY, n_pages=spec.n_pages,
                         page_size=spec.page_size, kv_bits=8, kv_group=16)
    assert priced["weight_bytes"] == want_w
    assert priced["pool_bytes"] == want_p
    assert priced["total"] == want_w + want_p


def test_registry_enforces_shared_budget(params):
    reg = FleetRegistry(TINY, params, budget_mb=0.01, backend="ref")
    with pytest.raises(FleetBudgetError):
        reg.register(_spec(scheme="lq2w"))
    assert len(reg) == 0                       # nothing half-registered

    # two tenants fit one budget only together under a roomier cap
    # (share_weights off: this test is about the budget gate itself —
    # identical-plan tenants WOULD dedup and admit, see
    # test_identical_plan_tenants_share_packed_leaves)
    one = FleetRegistry(TINY, params, backend="ref").price(
        _spec(scheme="lq2w", kv_bits=2))
    budget_mb = 1.5 * one["total"] / 2**20     # fits one, not two
    reg = FleetRegistry(TINY, params, budget_mb=budget_mb, backend="ref",
                        share_weights=False)
    reg.register(_spec("a", scheme="lq2w", kv_bits=2))
    with pytest.raises(FleetBudgetError):
        reg.register(_spec("b", scheme="lq2w", kv_bits=2))
    assert sorted(reg.tenants) == ["a"]


def test_registry_rejects_duplicate_ids(params):
    reg = FleetRegistry(TINY, params, backend="ref")
    reg.register(_spec("dup"))
    with pytest.raises(ValueError):
        reg.register(_spec("dup"))


def test_registry_tracks_aggregate_bytes(params):
    reg = FleetRegistry(TINY, params, budget_mb=64, backend="ref")
    t1 = reg.register(_spec("a", scheme="lq8w", kv_bits=8))
    t2 = reg.register(_spec("b", scheme="lq2w", kv_bits=2))
    assert reg.total_bytes() == t1.total_bytes + t2.total_bytes
    assert t2.weight_bytes < t1.weight_bytes   # 2-bit wire < 8-bit wire
    assert t2.pool_bytes < t1.pool_bytes       # 2-bit pool < 8-bit pool
    assert reg.remaining_bytes() == reg.budget_bytes - reg.total_bytes()


# ---------------------------------------------------------------------------
# mixed-KV tenants: heterogeneous pool pricing under the shared budget
# ---------------------------------------------------------------------------

MIXED_KV_PLAN = QuantPlan.from_assignment(
    {"layer.0": "lq4w"}, default="lq4w",
    kv_bits={"layer.0": 8}, kv_default=2, kv_group=16)


def test_spec_rejects_kv_bits_with_kv_plan():
    with pytest.raises(ValueError, match="per-layer under a plan"):
        TenantSpec("t", plan=MIXED_KV_PLAN, kv_bits=8)
    # a plan without a kv map still takes the spec's uniform kv_bits
    TenantSpec("t", plan=GOLD_PLAN, kv_bits=8)


def test_mixed_kv_pricing_matches_exact_pool_bytes(params):
    """Registry totals are eval_shape-exact for heterogeneous geometry."""
    reg = FleetRegistry(TINY, params, backend="ref")
    spec = _spec(plan=MIXED_KV_PLAN)
    kv_bits, kv_group = spec.pool_kv(TINY)
    assert kv_bits == (8, 2, 2) and kv_group == 16
    priced = reg.price(spec)
    want_p = pool_nbytes(TINY, n_pages=spec.n_pages,
                         page_size=spec.page_size, kv_bits=(8, 2, 2),
                         kv_group=16)
    assert priced["pool_bytes"] == want_p
    tenant = reg.register(spec)
    assert tenant.pool_bytes == want_p == tenant.pool.nbytes()
    # the engine's actual pool is genuinely heterogeneous
    assert len(transformer.stack_runs(tenant.pool.pages)) > 1


def test_mixed_kv_tenants_fit_where_uniform8_do_not(params):
    """The packing win: two mixed-KV tenants admit under a budget that
    rejects their uniform-8-bit-cache equivalents."""
    reg0 = FleetRegistry(TINY, params, backend="ref")
    uni8 = _spec("u", plan=GOLD_PLAN, kv_bits=8)
    mixed = _spec("m", plan=QuantPlan(
        assignments=GOLD_PLAN.assignments, default=GOLD_PLAN.default,
        kv_bits=(("layer.0", 8),), kv_default=2, kv_group=16))
    cost_uni, cost_mixed = reg0.price(uni8), reg0.price(mixed)
    assert cost_mixed["weight_bytes"] == cost_uni["weight_bytes"]
    assert cost_mixed["pool_bytes"] < cost_uni["pool_bytes"]

    # midpoint budget: two mixed-KV tenants fit, two uniform-8 do not
    budget_mb = (cost_mixed["total"] + cost_uni["total"]) / 2**20
    assert 2 * cost_mixed["total"] <= budget_mb * 2**20
    assert 2 * cost_uni["total"] > budget_mb * 2**20

    # share_weights off: this test isolates the POOL pricing win — with
    # dedup on, the identical weight plans would be priced once and both
    # pairs would fit
    reg = FleetRegistry(TINY, params, budget_mb=budget_mb, backend="ref",
                        share_weights=False)
    reg.register(dataclasses.replace(uni8, tenant_id="u1"))
    with pytest.raises(FleetBudgetError):           # second uniform-8: no
        reg.register(dataclasses.replace(uni8, tenant_id="u2"))

    reg = FleetRegistry(TINY, params, budget_mb=budget_mb, backend="ref",
                        share_weights=False)
    t1 = reg.register(dataclasses.replace(mixed, tenant_id="m1"))
    t2 = reg.register(dataclasses.replace(mixed, tenant_id="m2"))
    assert reg.total_bytes() == t1.total_bytes + t2.total_bytes
    assert t1.pool_bytes == t1.pool.nbytes()        # exact, not modeled
    # and the registered mixed tenants actually serve
    sched = t1.scheduler
    rid = sched.submit(_prompts()[0], max_new_tokens=3)
    outs = sched.drain(max_steps=200)
    assert len(outs[rid]) == 3


# ---------------------------------------------------------------------------
# cross-tenant weight sharing: identical packed leaves priced once
# ---------------------------------------------------------------------------

def test_identical_plan_tenants_share_packed_leaves(params):
    """The dedup regression bar: two identical-plan tenants admit under a
    budget that would reject private weight copies — the second tenant's
    packed leaves come from the registry cache and are priced once."""
    one = FleetRegistry(TINY, params, backend="ref").price(
        _spec(plan=GOLD_PLAN, kv_bits=8))
    # fits one full copy + one extra pool, NOT two full copies
    budget_mb = (one["total"] + one["pool_bytes"]
                 + 0.5 * one["weight_bytes"]) / 2**20

    private = FleetRegistry(TINY, params, budget_mb=budget_mb,
                            backend="ref", share_weights=False)
    private.register(_spec("a", plan=GOLD_PLAN, kv_bits=8))
    with pytest.raises(FleetBudgetError):
        private.register(_spec("b", plan=GOLD_PLAN, kv_bits=8))

    shared = FleetRegistry(TINY, params, budget_mb=budget_mb, backend="ref")
    ta = shared.register(_spec("a", plan=GOLD_PLAN, kv_bits=8))
    tb = shared.register(_spec("b", plan=GOLD_PLAN, kv_bits=8))
    assert ta.shared_bytes == 0
    assert tb.shared_bytes == one["weight_bytes"]   # every leaf re-used
    assert tb.weight_bytes == 0                     # incremental cost: pool
    assert shared.total_bytes() == \
        one["total"] + one["pool_bytes"]
    # the share is real, not just an accounting fiction: the engines hold
    # the SAME packed arrays (same buffers, not equal copies)
    a_leaves = jax.tree.leaves(ta.engine.params["decoder"])
    b_leaves = jax.tree.leaves(tb.engine.params["decoder"])
    assert all(x is y for x, y in zip(a_leaves, b_leaves))
    # and both still serve, token-for-token alike (same plan, same seed
    # stream is irrelevant under greedy)
    pa = _prompts()[0]
    ra = ta.scheduler.submit(pa, max_new_tokens=4)
    rb = tb.scheduler.submit(pa, max_new_tokens=4)
    outs_a = ta.scheduler.drain(max_steps=200)
    outs_b = tb.scheduler.drain(max_steps=200)
    assert outs_a[ra] == outs_b[rb]


def test_partial_plan_overlap_shares_aligned_segments(params):
    """Tenants whose plans agree on some layers share those segments only
    — the discount equals the overlapping layers' wire bytes."""
    from repro.plan import leaf_key_bytes
    from repro.models.transformer import plan_leaf_keys
    reg = FleetRegistry(TINY, params, backend="ref")
    a = _spec("a", plan=GOLD_PLAN, kv_bits=8)              # 8w / 4w / 4w
    b_plan = QuantPlan.from_assignment({"layer.0": "lq8w"}, default="lq2w")
    b = _spec("b", plan=b_plan, kv_bits=8)                 # 8w / 2w / 2w
    reg.register(a)
    tb = reg.register(b)
    keys_a = set(plan_leaf_keys(TINY, a.resolved_plan(TINY)))
    keys_b = set(plan_leaf_keys(TINY, b.resolved_plan(TINY)))
    overlap = keys_a & keys_b
    assert overlap                                         # layer.0 aligns
    assert tb.shared_bytes == sum(leaf_key_bytes(TINY, k) for k in overlap)
    assert 0 < tb.shared_bytes < reg.price(b)["weight_bytes"]


def test_price_without_sharing_is_pure(params):
    """``price()`` stays a pure full-cost quote; only registration (and
    ``with_sharing=True``) applies the dedup discount."""
    reg = FleetRegistry(TINY, params, backend="ref")
    spec = _spec(plan=GOLD_PLAN, kv_bits=8)
    before = reg.price(spec)
    reg.register(dataclasses.replace(spec, tenant_id="a"))
    assert reg.price(spec) == before
    discounted = reg.price(spec, with_sharing=True)
    assert discounted["weight_bytes"] == 0
    assert discounted["shared_bytes"] == before["weight_bytes"]


# ---------------------------------------------------------------------------
# manifest
# ---------------------------------------------------------------------------

def test_manifest_round_trip(tmp_path):
    plan_path = tmp_path / "gold.json"
    GOLD_PLAN.save(str(plan_path))
    manifest = {"arch": "llama3.2-1b", "budget_mb": 8, "tenants": [
        {"id": "gold", "plan": "gold.json", "kv_bits": 8, "kv_group": 16,
         "weight": 3},
        {"id": "bronze", "scheme": "lq2w", "kv_bits": 2, "kv_group": 16},
    ]}
    mpath = tmp_path / "fleet.json"
    mpath.write_text(json.dumps(manifest))
    m = load_manifest(str(mpath))
    assert m.arch == "llama3.2-1b" and m.budget_mb == 8
    gold, bronze = m.tenants
    assert gold.plan == GOLD_PLAN              # relative path resolved
    assert gold.weight == 3 and bronze.scheme == "lq2w"


def test_manifest_rejects_duplicates_and_empty():
    with pytest.raises(ValueError):
        FleetManifest(arch="x", tenants=(_spec("a"), _spec("a")))
    with pytest.raises(ValueError):
        FleetManifest(arch="x", tenants=())


def test_manifest_entry_needs_id():
    with pytest.raises(ValueError):
        TenantSpec.from_manifest({"scheme": "lq2w"})


# ---------------------------------------------------------------------------
# router: admission, quotas, weighted round-robin
# ---------------------------------------------------------------------------

def _router(params, **reg_kw):
    reg = FleetRegistry(TINY, params, backend="ref", **reg_kw)
    reg.register(_spec("gold", plan=GOLD_PLAN, kv_bits=8, weight=3))
    reg.register(_spec("bronze", scheme="lq2w", kv_bits=2, weight=1,
                       max_queued=2))
    return FleetRouter(reg)


def test_router_rejects_unknown_tenant(params):
    router = _router(params)
    with pytest.raises(FleetAdmissionError):
        router.submit("nobody", _prompts()[0])


def test_router_quota_rejection_counted(params):
    router = _router(params)
    p = _prompts()[0]
    router.submit("bronze", p, max_new_tokens=4)
    router.submit("bronze", p, max_new_tokens=4)
    with pytest.raises(FleetAdmissionError):   # max_queued=2
        router.submit("bronze", p, max_new_tokens=4)
    assert router.telemetry.per_tenant["bronze"].rejected == 1
    assert router.telemetry.per_tenant["bronze"].submitted == 2
    router.drain(max_steps=500)                # the admitted two complete
    assert router.telemetry.per_tenant["bronze"].completed == 2


def test_router_invalid_request_propagates(params):
    router = _router(params)
    with pytest.raises(ValueError):
        router.submit("gold", _prompts()[0], max_new_tokens=0)
    with pytest.raises(ValueError):
        router.submit("gold", [])


def test_weighted_round_robin_split(params):
    """With both tenants saturated, a 3:1 weight split yields a 3:1 step
    split (smooth WRR), measured over a window where both have work."""
    router = _router(params)
    for p in _prompts(lens=(5, 5)):
        router.submit("gold", p, max_new_tokens=24)
        router.submit("bronze", p, max_new_tokens=24)
    picks = []
    for _ in range(16):                        # both stay busy >= 16 steps
        tid, _ = router.step()
        picks.append(tid)
    assert picks.count("gold") == 12 and picks.count("bronze") == 4
    # smooth WRR interleaves rather than bursting: bronze never starves
    # longer than one full cycle of 4
    gaps = [i for i, t in enumerate(picks) if t == "bronze"]
    assert all(b - a <= 4 for a, b in zip(gaps, gaps[1:]))
    router.drain(max_steps=2000)


def test_step_returns_none_when_idle(params):
    router = _router(params)
    assert router.step() is None
    assert not router.has_work


# ---------------------------------------------------------------------------
# end-to-end: tagging, parity, telemetry
# ---------------------------------------------------------------------------

def test_completions_report_tenant(params):
    router = _router(params)
    done = []
    router.on_complete = done.append
    router.submit("gold", _prompts()[0], max_new_tokens=3)
    router.submit("bronze", _prompts()[1], max_new_tokens=3)
    router.drain(max_steps=500)
    assert sorted(c.tenant for c in done) == ["bronze", "gold"]
    assert all(len(c.tokens) == 3 for c in done)


def test_fleet_matches_solo_engines_token_for_token(params):
    """The acceptance bar: interleaved multi-tenant routing reproduces
    each tenant's solo PagedEngine greedy output exactly."""
    reg = FleetRegistry(TINY, params, backend="ref")
    reg.register(_spec("gold", plan=GOLD_PLAN, kv_bits=8, weight=3))
    reg.register(_spec("bronze", scheme="lq2w", kv_bits=2, weight=1))
    router = FleetRouter(reg)
    prompts = _prompts()
    rids = {}
    for i, p in enumerate(prompts):            # interleaved arrivals
        for tid in ("gold", "bronze"):
            rids.setdefault(tid, []).append(
                router.submit(tid, p, max_new_tokens=8))
        router.step()
    outs = router.drain(max_steps=2000)

    for tid in ("gold", "bronze"):
        spec = router.registry[tid].spec
        ecfg = dataclasses.replace(spec.engine_config(TINY), backend="ref")
        engine = PagedEngine(TINY, params, ecfg, spec.paged_config())
        sched = Scheduler(engine, engine.new_pool())
        solo_rids = [sched.submit(p, max_new_tokens=8) for p in prompts]
        solo = sched.drain(max_steps=2000)
        for fleet_rid, solo_rid in zip(rids[tid], solo_rids):
            assert outs[tid][fleet_rid] == solo[solo_rid]
    # the two tenants' plans genuinely differ: so do their outputs
    assert any(outs["gold"][a] != outs["bronze"][b]
               for a, b in zip(rids["gold"], rids["bronze"]))


def test_telemetry_counts_and_snapshot(params):
    clock = iter(float(i) for i in range(10_000))
    router = _router(params)
    router.reset_telemetry(FleetTelemetry(clock=lambda: next(clock)))
    router.submit("gold", _prompts()[0], max_new_tokens=5)
    router.drain(max_steps=500)
    snap = router.telemetry.snapshot()
    g = snap["tenants"]["gold"]
    assert g["submitted"] == 1 and g["completed"] == 1
    assert g["tokens"] == 5
    assert g["steps"] >= 4                     # first token at admission
    assert g["tok_per_s"] > 0                  # deterministic fake clock
    assert snap["aggregate"]["tokens"] == 5
    json.loads(router.telemetry.to_json())     # JSON-able


def test_telemetry_rejected_tokens_distinct_from_preemptions():
    """Speculative rollbacks ride Completion.rejected_tokens into their
    own counter — preemptions are not inflated by them."""
    t = FleetTelemetry()
    t.note_complete("a", 1, 7)
    t.note_complete("a", 0, 5)
    snap = t.snapshot()
    assert snap["tenants"]["a"]["preemptions"] == 1
    assert snap["tenants"]["a"]["rejected_tokens"] == 12
    assert snap["aggregate"]["rejected_tokens"] == 12
    assert snap["aggregate"]["preemptions"] == 1


def test_telemetry_aggregate_uses_union_window():
    clock = iter([0.0, 1.0, 2.0, 3.0])
    t = FleetTelemetry(clock=lambda: next(clock))
    for tid in ("a", "b", "a", "b"):
        t.note_step(tid, 0.5)
        t.note_token(tid)
    snap = t.snapshot()
    # host rate = 4 tokens over the union window [0, 3] — NOT the sum of
    # per-tenant rates (1.0 + 1.0), whose windows overlap
    assert snap["aggregate"]["tok_per_s"] == round(4 / 3, 3)
    assert snap["tenants"]["a"]["tok_per_s"] == 1.0


def test_idle_tenant_snapshot_schema(params):
    """A tenant that never saw traffic still gets a full zeroed stats
    row, so --stats-out consumers see one schema for every tenant."""
    router = _router(params)
    router.submit("gold", _prompts()[0], max_new_tokens=2)
    router.drain(max_steps=200)
    snap = router.telemetry.snapshot()
    assert set(snap["tenants"]["bronze"]) == set(snap["tenants"]["gold"])
    assert snap["tenants"]["bronze"]["tokens"] == 0
    assert snap["tenants"]["bronze"]["tok_per_s"] == 0.0
    assert router.stats()["tenants"]["bronze"]["queued"] == 0


def test_router_stats_include_budget(params):
    router = _router(params, budget_mb=64)
    s = router.stats()
    assert s["budget_mb"] == 64
    assert s["used_mb"] > 0
    assert set(s["tenants"]) == {"gold", "bronze"}
    assert "bytes" in s["tenants"]["gold"]


def test_build_fleet_from_manifest(tmp_path, params):
    plan_path = tmp_path / "gold.json"
    GOLD_PLAN.save(str(plan_path))
    mpath = tmp_path / "fleet.json"
    mpath.write_text(json.dumps({
        "arch": "tiny", "budget_mb": 64, "tenants": [
            {"id": "gold", "plan": "gold.json", "kv_bits": 8,
             "kv_group": 16, "max_slots": 2, "page_size": 4, "n_pages": 24,
             "max_context": 32},
            {"id": "bronze", "scheme": "lq2w", "kv_bits": 2, "kv_group": 16,
             "max_slots": 2, "page_size": 4, "n_pages": 24,
             "max_context": 32}]}))
    router = build_fleet(str(mpath), TINY, params, backend="ref")
    assert router.registry.budget_mb == 64
    router = build_fleet(str(mpath), TINY, params, budget_mb=32,
                         backend="ref")      # CLI override wins
    assert router.registry.budget_mb == 32
    rid = router.submit("gold", _prompts()[0], max_new_tokens=2)
    outs = router.drain(max_steps=200)
    assert len(outs["gold"][rid]) == 2

    with pytest.raises(FleetBudgetError):    # over-budget manifest rejected
        build_fleet(str(mpath), TINY, params, budget_mb=0.01, backend="ref")
