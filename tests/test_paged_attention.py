"""Fused paged-attention kernel (kernels/paged_attention.py): interpret-mode
parity with the XLA gather+dequant+attention path, kernel-level and through
every engine.

The contract is TOKEN identity, not bit identity — the online softmax
re-associates the reduction — so the kernel-level checks use float
tolerance and the serving checks require exact greedy token streams.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kvwire
from repro.kernels import paged_attention as paged_attn
from repro.models import attention, transformer
from repro.models.config import ModelConfig
from repro.plan import QuantPlan
from repro.plan.plan import candidates_for
from repro.serve import Engine, EngineConfig, PagedConfig, RequestParams, \
    Server
from repro.spec import SpeculativeEngine

pytestmark = pytest.mark.skipif(
    not paged_attn.available(),
    reason="Pallas unavailable: fused kernel gated off on this host")

TINY = ModelConfig(name="tiny", family="dense", n_layers=3, d_model=64,
                   vocab_size=256, n_heads=4, n_kv_heads=2, head_dim=16,
                   d_ff=128, dtype="float32", remat="none")

KEY = jax.random.key(0)


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(TINY, jax.random.key(0))


# ---------------------------------------------------------------------------
# kernel level: parity vs gather -> dequant -> decode_attention
# ---------------------------------------------------------------------------

def _case(bits, *, b=2, lq=1, kvh=2, gq=2, d=32, gs=16, page_size=4,
          pps=4, ragged=True):
    """One synthetic paged-pool decode case + its XLA baseline inputs,
    the pages in the pool's stored layout.

    Page 0 (the scratch page) is filled with large garbage so any leak
    past the position mask shows up as a parity failure, and table rows
    past each slot's live pages point at scratch (the padded-table state
    the pool hands the engine).
    """
    n_pages = b * pps + 1
    kf = jax.random.normal(KEY, (n_pages, page_size, kvh, d), jnp.float32)
    vf = jax.random.normal(jax.random.fold_in(KEY, 1), kf.shape,
                           jnp.float32)
    kf = kf.at[0].set(1e4)                     # scratch garbage
    vf = vf.at[0].set(-1e4)
    q = jax.random.normal(jax.random.fold_in(KEY, 2),
                          (b, lq, kvh, gq, d), jnp.float32)
    table = (1 + jnp.arange(b * pps, dtype=jnp.int32)).reshape(b, pps)
    # slot 0 sits mid-page (padded entries after its live prefix resolve
    # to real-but-masked rows); slot 1 at a page boundary
    full = pps * page_size
    pos = jnp.asarray([full - page_size - 2, full - lq] if ragged
                      else [full - lq] * b, jnp.int32)[:b]
    if bits is not None:
        kf = kvwire.quantize_kv(kf, bits, gs)
        vf = kvwire.quantize_kv(vf, bits, gs)
    return q, kvwire.stored_pages(kf), kvwire.stored_pages(vf), table, pos


def _baseline(q, k_pg, v_pg, table, pos, d, page_size=4):
    kk = kvwire.gather_pages(k_pg, table, page_size, q.shape[2])
    vv = kvwire.gather_pages(v_pg, table, page_size, q.shape[2])
    if isinstance(kk, dict):
        kk = kvwire.dequantize_kv(kk, d)
        vv = kvwire.dequantize_kv(vv, d)
    return attention.decode_attention(q, kk, vv, pos)


@pytest.mark.parametrize("lq", [1, 3])
@pytest.mark.parametrize("bits", [None, 8, 4, 2])
def test_kernel_matches_xla_baseline(bits, lq):
    q, k_pg, v_pg, table, pos = _case(bits, lq=lq)
    want = _baseline(q, k_pg, v_pg, table, pos, q.shape[-1])
    got = paged_attn.paged_attention(q, k_pg, v_pg, table, pos,
                                     page_size=4, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# the chat cells' page geometry: 16-token pages of 8 kv heads of 64 (the
# page's 128 rows fill the lanes) and of 4 of 128 (64 rows: two feature
# blocks share the lanes), 4-bit codes in 16-wide regions
SERVING = {"kv8-d64": dict(kvh=8, gq=4, d=64),
           "kv4-d128": dict(kvh=4, gq=8, d=128)}


@pytest.mark.parametrize("geom", SERVING)
def test_kernel_matches_xla_at_serving_geometry(geom):
    """The kernel reads the stored layout as the pool keeps it, folded
    feature blocks and all: parity with gather + dequant + attend."""
    kw = SERVING[geom]
    q, k_pg, v_pg, table, pos = _case(4, page_size=16, pps=3, **kw)
    want = _baseline(q, k_pg, v_pg, table, pos, kw["d"], 16)
    got = paged_attn.paged_attention(q, k_pg, v_pg, table, pos,
                                     page_size=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_kernel_reads_the_layer_it_is_given():
    """A scan-stacked leaf goes to the kernel whole with the layer's
    index: the output is the one of that layer's pages alone."""
    q, k_pg, v_pg, table, pos = _case(4)
    k2, v2 = (jax.tree.map(lambda a: jnp.stack([a * 0 + 1, a]), t)
              for t in (k_pg, v_pg))
    want = paged_attn.paged_attention(q, k_pg, v_pg, table, pos,
                                      page_size=4, interpret=True)
    got = paged_attn.paged_attention(q, k2, v2, table, pos, page_size=4,
                                     layer=jnp.asarray(1), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _poison(leaf, pages):
    """``leaf`` with ``pages`` overwritten by NaN: any read of them that
    reaches the accumulator (even times a zero probability) shows."""
    if isinstance(leaf, dict):
        return {**leaf, "scale": leaf["scale"].at[pages].set(jnp.nan),
                "zmin": leaf["zmin"].at[pages].set(jnp.nan)}
    return leaf.at[pages].set(jnp.nan)


@pytest.mark.parametrize("lq", [1, 3])
@pytest.mark.parametrize("bits,dequant", [(None, "auto"), (8, "auto"),
                                          (4, "lut"), (4, "affine"),
                                          (2, "auto")])
def test_entries_past_the_last_live_page_are_never_read(bits, dequant, lq):
    """Each slot stops at table entry ``(pos + lq - 1) // page_size``.
    Pointing every entry past it at real pages full of NaN instead of
    the scratch page changes no bit of the output.  Slots: the last key
    of page 0 (pos 15) and the first of page 1 (16), an idle slot
    (pos 0), a run that crosses a boundary at lq 3 (30 -> 32), a context
    that fills the whole table, and a run tailing past it."""
    b, page_size, pps, kvh, d = 6, 16, 4, 2, 32
    full = pps * page_size
    pos = jnp.asarray([15, 16, 0, 30, full - lq, full - 1], jnp.int32)
    n_live = pps * b
    kf = jax.random.normal(KEY, (2 * n_live + 1, page_size, kvh, d),
                           jnp.float32)
    vf = jax.random.normal(jax.random.fold_in(KEY, 1), kf.shape,
                           jnp.float32)
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (b, lq, kvh, 2, d),
                          jnp.float32)
    live = (1 + jnp.arange(n_live, dtype=jnp.int32)).reshape(b, pps)
    last = np.minimum((np.asarray(pos) + lq - 1) // page_size, pps - 1)
    dead = np.arange(pps)[None] > last[:, None]
    assert dead.any() and not dead.all(axis=1).any()
    scratch = jnp.where(dead, 0, live)
    stale = jnp.where(dead, live + n_live, live)    # other real pages
    if bits is not None:
        kf, vf = kvwire.quantize_kv(kf, bits, 16), kvwire.quantize_kv(
            vf, bits, 16)
    kf, vf = kvwire.stored_pages(kf), kvwire.stored_pages(vf)
    poisoned = np.asarray(stale)[dead]
    k_pg, v_pg = _poison(kf, poisoned), _poison(vf, poisoned)

    def run(table):
        return np.asarray(paged_attn.paged_attention(
            q, k_pg, v_pg, table, pos, page_size=page_size, dequant=dequant,
            interpret=True))

    got = run(stale)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, run(scratch))
    want = _baseline(q, kf, vf, scratch, pos, d, page_size)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bits", [4, 2])
def test_lut_and_affine_dequant_agree(bits):
    """The LUT masked-matmul dataflow is an exact reformulation of the
    affine dequant (section V): same pages, same scores, same output."""
    q, k_pg, v_pg, table, pos = _case(bits)
    affine = paged_attn.paged_attention(q, k_pg, v_pg, table, pos,
                                        page_size=4, dequant="affine",
                                        interpret=True)
    lut = paged_attn.paged_attention(q, k_pg, v_pg, table, pos,
                                     page_size=4, dequant="lut",
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(lut), np.asarray(affine),
                               rtol=2e-5, atol=2e-5)


def test_auto_mode_selects_lut_at_low_bits():
    assert paged_attn.dequant_path(4) == "lut"
    assert paged_attn.dequant_path(2) == "lut"
    assert paged_attn.dequant_path(8) == "affine"
    assert paged_attn.dequant_path(None) == "fp"
    assert paged_attn.dequant_path(8, "affine") == "affine"


def test_rejects_bad_dequant_modes():
    q, k_pg, v_pg, table, pos = _case(8)
    with pytest.raises(ValueError, match="dequant"):
        paged_attn.paged_attention(q, k_pg, v_pg, table, pos, page_size=4,
                                   dequant="nearest", interpret=True)
    with pytest.raises(ValueError, match="bits <= 4"):
        paged_attn.paged_attention(q, k_pg, v_pg, table, pos, page_size=4,
                                   dequant="lut", interpret=True)


def test_resolve_mode_gates_on_flag_and_host():
    assert paged_attn.resolve_mode(False) is None
    assert paged_attn.resolve_mode(True) in ("pallas", "interpret")


# ---------------------------------------------------------------------------
# engine level: token-exact serving across formats, one compiled step
# ---------------------------------------------------------------------------

def _prompts(seed=1, lens=(7, 12, 5)):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(0, 256, size=n))) for n in lens]


def _serve(params, ecfg, pcfg, prompts, max_new, stagger=True):
    srv = Server(TINY, params, ecfg, pcfg)
    rids = []
    for i, (p, n) in enumerate(zip(prompts, max_new)):
        rids.append(srv.submit(p, RequestParams(max_new_tokens=n)))
        if stagger and i == 0:
            srv.step(); srv.step()
    outs = srv.drain(max_steps=500)
    return [outs[r] for r in rids], srv


@pytest.mark.parametrize("kv_bits", [None, 8, 4, 2])
def test_fused_serving_token_identical(params, kv_bits):
    """The acceptance bar: --fused-attention changes the dataflow, never
    a token — staggered continuous batching, every wire format."""
    kw = dict(kv_bits=kv_bits, kv_group=16) if kv_bits else {}
    pcfg = PagedConfig(max_slots=2, page_size=4, n_pages=24,
                       max_context=32)
    prompts, max_new = _prompts(), [8, 6, 7]
    ref, rsrv = _serve(params, EngineConfig(max_len=32, **kw), pcfg,
                       prompts, max_new)
    out, srv = _serve(params,
                      EngineConfig(max_len=32, fused_attention=True, **kw),
                      pcfg, prompts, max_new)
    assert srv.engine.fused_mode is not None
    assert rsrv.engine.fused_mode is None
    assert out == ref
    assert srv.engine.decode_compilations == 1


def test_fused_survives_preemption_mid_stream(params):
    """Preempt -> free -> realloc -> recompute resume under the fused
    kernel: the truncate/restore cycle mid-stream stays token-exact."""
    prompts = _prompts()[:2]
    pcfg = PagedConfig(max_slots=2, page_size=4, n_pages=10,
                       max_context=32)
    ecfg = EngineConfig(max_len=32, kv_bits=4, kv_group=16,
                        fused_attention=True)
    base = dataclasses.replace(ecfg, fused_attention=False)
    ref, rsrv = _serve(params, base, pcfg, prompts, [16, 16],
                       stagger=False)
    out, srv = _serve(params, ecfg, pcfg, prompts, [16, 16],
                      stagger=False)
    pre = sum(srv.scheduler.request(r).n_preemptions
              for r in srv.scheduler._requests)
    assert pre >= 1                            # pool pressure really hit
    assert out == ref
    assert srv.engine.decode_compilations == 1


def test_fused_hetero_kv_plan_matches_baseline(params):
    """Per-layer kv bits (one pool run per format): each stack run launches
    the fused kernel on its own wire format; tokens still exact."""
    plan = QuantPlan.uniform("fp32").with_kv(
        {"layer.0": 8, "layer.2": 2}, default=None, kv_group=16)
    pcfg = PagedConfig(max_slots=2, page_size=4, n_pages=40,
                       max_context=32)
    prompts, max_new = _prompts(), [10, 6, 8]
    base = EngineConfig(max_len=32, plan=plan, backend="ref")
    ref, rsrv = _serve(params, base, pcfg, prompts, max_new)
    assert len(transformer.stack_runs(rsrv.pool.pages)) > 1  # mixed
    out, srv = _serve(params,
                      dataclasses.replace(base, fused_attention=True),
                      pcfg, prompts, max_new)
    assert out == ref
    assert srv.engine.decode_compilations == 1


def test_fused_speculative_verify_multi_query(params):
    """The spec verify step sends Lq = k+1 query rows through the same
    kernel; acceptance and tokens must match the unfused engine."""
    cands = candidates_for(TINY, ["lq8w"])
    ecfg = EngineConfig(max_len=32, kv_bits=8, kv_group=16, backend="ref")
    pcfg = PagedConfig(max_slots=2, page_size=4, n_pages=40,
                       max_context=32)

    def run(fused):
        eng = SpeculativeEngine(
            TINY, params, dataclasses.replace(ecfg, fused_attention=fused),
            pcfg, draft_plan=QuantPlan(default=cands["lq8w"]), spec_k=2)
        srv = Server(TINY, params, ecfg, pcfg, engine=eng)
        rids = [srv.submit(p, RequestParams(max_new_tokens=n))
                for p, n in zip(_prompts(), [8, 6, 7])]
        outs = srv.drain(max_steps=500)
        return [outs[r] for r in rids], eng

    ref, reng = run(False)
    out, eng = run(True)
    assert eng.verifier.fused_mode is not None
    assert out == ref
    assert eng.decode_compilations == 1


def test_fused_fleet_routing_matches_baseline(params):
    """fused_attention is host-level: the registry applies it to every
    tenant engine, and routed streams match the unfused fleet."""
    from repro.fleet import FleetManifest, TenantSpec, build_fleet

    manifest = FleetManifest(arch="tiny", tenants=(
        TenantSpec("gold", scheme="lq8w", kv_bits=8, kv_group=16,
                   max_slots=2, page_size=4, n_pages=24, max_context=32),
        TenantSpec("bronze", scheme="lq4w", kv_bits=4, kv_group=16,
                   max_slots=2, page_size=4, n_pages=24, max_context=32),
    ))

    def run(fused):
        router = build_fleet(manifest, TINY, params, backend="ref",
                             fused_attention=fused)
        for tid in ("gold", "bronze"):
            for p in _prompts()[:2]:
                router.submit(tid, p, max_new_tokens=6)
        return router.drain(max_steps=500), router

    ref, _ = run(False)
    out, router = run(True)
    assert out == ref
    for tenant in router.registry:
        assert tenant.engine.fused_mode is not None
        assert tenant.engine.decode_compilations == 1


# ---------------------------------------------------------------------------
# XLA fallback: decode_attention keeps the cache storage dtype
# ---------------------------------------------------------------------------

def test_decode_attention_accumulates_f32_without_upcast_copy():
    """Regression: the fallback used to ``.astype(f32)`` both caches,
    materializing full upcast copies.  ``preferred_element_type`` gives
    the same f32 accumulation with the caches staying in storage dtype.
    This is the parity half; the temp-bytes bound is checked where the
    property matters, on the v5e compile (tests/test_chip_compile.py)."""
    b, s, kvh, g, d = 2, 2048, 2, 2, 64
    q = jax.random.normal(KEY, (b, 1, kvh, g, d), jnp.float32)
    kc = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, kvh, d),
                           jnp.bfloat16)
    vc = jax.random.normal(jax.random.fold_in(KEY, 2), kc.shape,
                           jnp.bfloat16)
    pos = jnp.asarray([s - 1, s // 2], jnp.int32)
    got = attention.decode_attention(q, kc, vc, pos)
    assert got.dtype == q.dtype
    want = attention.decode_attention(q, kc.astype(jnp.float32),
                                      vc.astype(jnp.float32), pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused_solo_engine_unaffected(params):
    """The solo (non-paged) engine has no page table; the flag must not
    perturb plain generate."""
    prompt = _prompts()[0]
    outs = []
    for fused in (False, True):
        eng = Engine(TINY, params,
                     EngineConfig(max_len=32, kv_bits=8, kv_group=16,
                                  fused_attention=fused))
        out, _ = eng.generate({"tokens": jnp.asarray([prompt], jnp.int32)},
                              steps=7)
        outs.append(np.asarray(out)[0].tolist())
    assert outs[0] == outs[1]
