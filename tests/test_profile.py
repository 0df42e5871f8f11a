"""Tests for the perf-attribution plane (obs/profile.py, obs/check.py
--profile): the sampled phase profiler is host-side-only (bit-identical
tokens, one compiled decode step), every phase lands in the
``serve_phase_ms`` histograms, the utilization gauges stay in (0, 1],
the artifacts pass ``check_profile``, named scopes reach the lowered
HLO, and the spec engine profiles through its verifier."""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import transformer
from repro.models.config import ModelConfig
from repro.obs import Observability
from repro.obs.check import check_profile
from repro.obs.profile import (PHASES, PhaseProfiler,
                               record_utilization, xprof_capture)
from repro.plan import QuantPlan
from repro.plan.plan import candidates_for
from repro.serve import EngineConfig, PagedConfig, RequestParams, Server
from repro.spec import SpeculativeEngine

TINY = ModelConfig(name="tiny", family="dense", n_layers=3, d_model=64,
                   vocab_size=256, n_heads=4, n_kv_heads=2, head_dim=16,
                   d_ff=128, dtype="float32", remat="none")


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(TINY, jax.random.key(0))


def _server(params, obs=None, kv_bits=8, engine=None):
    ecfg = EngineConfig(max_len=32, kv_bits=kv_bits, kv_group=16,
                        backend="ref")
    pcfg = PagedConfig(max_slots=2, page_size=4, n_pages=24, max_context=32)
    return Server(TINY, params, ecfg, pcfg, seed=0, obs=obs, engine=engine)


def _drive(server, n_req=3, max_new=6):
    rng = np.random.default_rng(3)
    rids = [server.submit(list(map(int, rng.integers(0, 256, size=5))),
                          RequestParams(max_new_tokens=max_new))
            for _ in range(n_req)]
    server.drain()
    return [server.output(r) for r in rids]


@pytest.fixture(scope="module")
def profiled_run(params):
    """One profiled serve run (quant KV, probes every 2 steps) + its
    uninstrumented reference."""
    ref = _drive(_server(params))
    obs = Observability()
    server = _server(params, obs=obs)
    profiler = server.attach_profiler(PhaseProfiler(
        obs, TINY, server.engine, every_n_steps=2))
    out = _drive(server)
    util = record_utilization(obs, TINY, server.engine, server.pool)
    return {"ref": ref, "out": out, "obs": obs, "server": server,
            "profiler": profiler, "util": util}


# ---------------------------------------------------------------------------
# invisibility: the hard contract
# ---------------------------------------------------------------------------

class TestInvisibility:
    def test_tokens_bit_identical(self, profiled_run):
        assert profiled_run["out"] == profiled_run["ref"]

    def test_one_compiled_decode_step(self, profiled_run):
        # the probe's standalone jits and the step replay reuse or avoid
        # the engine's traces; a second compile would mean the profiler
        # perturbed the serving path
        assert profiled_run["server"].engine.decode_compilations == 1

    def test_scheduler_key_stream_untouched(self, profiled_run):
        # the step replay folds its own key; the scheduler's fold counter
        # advanced only once per real decode step
        sched = profiled_run["server"].scheduler
        assert sched._decode_steps == profiled_run["profiler"].steps


# ---------------------------------------------------------------------------
# phase histograms
# ---------------------------------------------------------------------------

class TestPhaseHistograms:
    def test_every_phase_recorded(self, profiled_run):
        m = profiled_run["obs"].metrics
        probes = m.find("profile_probes_total")
        assert probes is not None and probes.value > 0
        snap = m.snapshot()["histograms"]
        for phase in PHASES:
            keys = [k for k in snap if k.startswith("serve_phase_ms{")
                    and f'phase="{phase}"' in k]
            assert keys, f"phase {phase!r} never recorded"
            assert all(snap[k]["count"] == probes.value for k in keys)

    def test_step_replay_recorded(self, profiled_run):
        h = profiled_run["obs"].metrics.find("serve_step_replay_ms")
        assert h is not None and h.count > 0

    def test_fp_wire_records_zero_dequant(self, params):
        obs = Observability()
        server = _server(params, obs=obs, kv_bits=None)
        server.attach_profiler(PhaseProfiler(obs, TINY, server.engine,
                                             every_n_steps=2))
        _drive(server, n_req=2)
        snap = obs.metrics.snapshot()["histograms"]
        dq = [snap[k] for k in snap if 'phase="dequant"' in k]
        assert dq and all(h["sum"] == 0.0 for h in dq)
        ga = [snap[k] for k in snap if 'phase="gather"' in k]
        assert ga and all(h["sum"] > 0.0 for h in ga)

    def test_probe_returns_breakdown(self, profiled_run):
        out = profiled_run["profiler"].probe(
            profiled_run["server"].scheduler)
        assert "gather/run0" in out and "lm_head/all" in out
        assert out["step_replay/all"] > 0.0


# ---------------------------------------------------------------------------
# utilization gauges
# ---------------------------------------------------------------------------

class TestUtilization:
    def test_gauges_in_unit_interval(self, profiled_run):
        u = profiled_run["util"]
        assert u is not None
        assert 0.0 < u["mfu"] <= 1.0
        assert 0.0 < u["hbm_util"] <= 1.0
        m = profiled_run["obs"].metrics
        assert m.find("serve_mfu").value == u["mfu"]
        assert m.find("serve_hbm_util").value == u["hbm_util"]

    def test_calibrated_hw_clamps_to_one(self, profiled_run):
        # a roof calibrated onto this very run can imply >100% on the
        # tiny model; the gauge contract clamps at 1.0
        from repro.obs import calibrated_hw
        srv = profiled_run["server"]
        hw = calibrated_hw({"ms_factor": 1e9, "model": "tiny"})
        u = record_utilization(profiled_run["obs"], TINY, srv.engine,
                               srv.pool, hw=hw,
                               labels={"tenant": "clamped"})
        assert u["mfu"] == 1.0 and u["hbm_util"] == 1.0

    def test_none_before_any_step(self, params):
        obs = Observability()
        server = _server(params, obs=obs)
        assert record_utilization(obs, TINY, server.engine,
                                  server.pool) is None


# ---------------------------------------------------------------------------
# artifact gate (check --profile)
# ---------------------------------------------------------------------------

class TestCheckProfile:
    def test_artifacts_pass(self, profiled_run, tmp_path):
        obs = profiled_run["obs"]
        tp, mp = tmp_path / "trace.json", tmp_path / "metrics.json"
        obs.save_trace(str(tp))
        obs.save_metrics(str(mp))
        trace = json.loads(tp.read_text())
        snap = json.loads(mp.read_text())
        found = check_profile(trace, snap)
        assert any("serve_mfu" in k for k in found)

    def test_missing_phase_fails(self, profiled_run):
        snap = profiled_run["obs"].metrics.snapshot()
        snap["histograms"] = {
            k: v for k, v in snap["histograms"].items()
            if 'phase="attention"' not in k}
        with pytest.raises(AssertionError, match="attention"):
            check_profile({"traceEvents": []}, snap)

    def test_out_of_range_gauge_fails(self, profiled_run, tmp_path):
        obs = profiled_run["obs"]
        tp = tmp_path / "trace.json"
        obs.save_trace(str(tp))
        snap = obs.metrics.snapshot()
        snap["gauges"]["serve_mfu"] = 1.7
        with pytest.raises(AssertionError, match="outside"):
            check_profile(json.loads(tp.read_text()), snap)


# ---------------------------------------------------------------------------
# fused engine: honest single-phase attribution
# ---------------------------------------------------------------------------

def test_fused_engine_profiles_single_fused_phase(params, tmp_path):
    """A fused engine runs gather+dequant+attention as one kernel, so the
    probe must record ONE ``fused_attention`` phase per stack run — never
    the XLA triplet — and the artifacts must pass ``check --profile``
    under that decomposition, tokens and compile count untouched."""
    from repro.kernels import paged_attention as paged_attn
    if not paged_attn.available():
        pytest.skip("Pallas unavailable: no fused mode on this host")
    ref = _drive(_server(params))
    obs = Observability()
    ecfg = EngineConfig(max_len=32, kv_bits=8, kv_group=16, backend="ref",
                        fused_attention=True)
    pcfg = PagedConfig(max_slots=2, page_size=4, n_pages=24,
                       max_context=32)
    server = Server(TINY, params, ecfg, pcfg, seed=0, obs=obs)
    server.attach_profiler(PhaseProfiler(obs, TINY, server.engine,
                                         every_n_steps=2))
    out = _drive(server)
    assert out == ref                          # profiling + fusion: no drift
    assert server.engine.decode_compilations == 1
    record_utilization(obs, TINY, server.engine, server.pool)
    snap = obs.metrics.snapshot()
    hists = snap["histograms"]
    assert any('phase="fused_attention"' in k for k in hists)
    for phase in ("gather", "dequant", "attention"):
        assert not any(f'phase="{phase}"' in k for k in hists), \
            f"fused probe still records the XLA phase {phase!r}"
    tp = tmp_path / "trace.json"
    obs.save_trace(str(tp))
    check_profile(json.loads(tp.read_text()), snap)


# ---------------------------------------------------------------------------
# speculative engine: profile through the verifier
# ---------------------------------------------------------------------------

def test_spec_engine_profiles_via_verifier(params):
    cands = candidates_for(TINY, ["lq8w"])
    ecfg = EngineConfig(max_len=32, kv_bits=8, kv_group=16, backend="ref")
    pcfg = PagedConfig(max_slots=2, page_size=4, n_pages=40, max_context=32)
    eng = SpeculativeEngine(TINY, params, ecfg, pcfg,
                            draft_plan=QuantPlan(default=cands["lq8w"]),
                            spec_k=2)
    ref_eng = SpeculativeEngine(TINY, params, ecfg, pcfg,
                                draft_plan=QuantPlan(
                                    default=cands["lq8w"]), spec_k=2)
    ref = _drive(Server(TINY, params, ecfg, pcfg, engine=ref_eng))
    obs = Observability()
    server = Server(TINY, params, ecfg, pcfg, engine=eng, obs=obs)
    server.attach_profiler(PhaseProfiler(obs, TINY, eng, every_n_steps=2))
    out = _drive(server)
    assert out == ref                       # replay through _multi_paged
    assert eng.decode_compilations == 1     # reused the verify trace
    snap = obs.metrics.snapshot()["histograms"]
    assert any('phase="attention"' in k for k in snap)
    u = record_utilization(obs, TINY, eng, server.pool)
    assert u is not None and 0.0 < u["mfu"] <= 1.0


# ---------------------------------------------------------------------------
# annotations + capture
# ---------------------------------------------------------------------------

def _host_events(trace_dir) -> dict:
    """{name: [stats dict, ...]} of the host-plane events of a capture."""
    from jax.profiler import ProfileData
    (path,) = trace_dir.rglob("*.xplane.pb")
    out: dict = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


@pytest.mark.parametrize("enabled", [False, True])
def test_annotate_is_a_context_manager(tmp_path, enabled):
    # every repro.obs span, recording or not, is a profiler TraceMe: its
    # name and args land on the host plane under a profiler session
    obs = Observability(enabled=enabled)
    with xprof_capture(str(tmp_path)):
        with obs.span("unit-test-span", rid=3, queued_ms=1.5):
            x = jnp.ones((2, 2)) + 1
    assert float(x.sum()) == 8.0
    (stats,) = _host_events(tmp_path)["unit-test-span"]
    assert stats == {"rid": 3, "queued_ms": 1.5}
    assert len(obs.tracer.events) == (1 if enabled else 0)


# the layer-kind scopes of the decode program (models/transformer.py,
# models/attention.py, serve/engine.py) and the layer scan's own ops
SCOPES = ("norm", "qkv", "kv_write", "attention", "attn_out", "ffn",
          "lm_head", "sample", "layer_scan")


@pytest.fixture(scope="module", params=[False, True], ids=["xla", "fused"])
def decode_ir(request, params):
    """The engine's decode program lowered with debug locations, on the
    XLA gather path and through the fused kernel (interpret mode)."""
    ecfg = EngineConfig(max_len=32, kv_bits=8, kv_group=16, backend="ref",
                        fused_attention=request.param)
    pcfg = PagedConfig(max_slots=2, page_size=4, n_pages=24, max_context=32)
    server = Server(TINY, params, ecfg, pcfg, seed=0)
    eng = server.engine
    lowered = eng._step_paged.lower(
        eng.params, server.pool.pages, jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 8), jnp.int32), jnp.zeros((2,), jnp.int32),
        jax.random.key(0))
    return lowered.compiler_ir().operation.get_asm(enable_debug_info=True)


@pytest.mark.parametrize("scope", SCOPES)
def test_named_scopes_reach_lowered_hlo(decode_ir, scope):
    # named scopes land in the HLO location metadata, not the op text;
    # inside the scan body the path restarts at the body's own scopes
    assert re.search(rf'["/]{scope}/', decode_ir)
    assert "paged_decode_step" in decode_ir


def test_xprof_capture_writes_or_degrades(tmp_path):
    # the capture writes a profiler trace; a profiler failure raises
    # rather than leaving a run without the trace it was asked for
    with xprof_capture(str(tmp_path / "xprof")):
        jax.block_until_ready(jnp.ones((4, 4)) @ jnp.ones((4, 4)))
    assert list((tmp_path / "xprof").rglob("*.xplane.pb"))
