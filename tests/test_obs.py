"""Tests for repro.obs: tracer span trees, metric percentiles, no-op
cost, and the serve/spec/fleet wiring (traces + latency histograms with
no extra decode retraces and token-identical outputs)."""
import json

import jax
import numpy as np
import pytest

from repro.models import transformer
from repro.models.config import ModelConfig
from repro.obs import (NOOP, DEFAULT_MS_BUCKETS, Histogram, MetricsRegistry,
                       Observability, Stopwatch, Tracer)
from repro.obs.check import check_metrics, check_trace
from repro.serve import EngineConfig, PagedConfig, RequestParams, Server

TINY = ModelConfig(name="tiny", family="dense", n_layers=3, d_model=64,
                   vocab_size=256, n_heads=4, n_kv_heads=2, head_dim=16,
                   d_ff=128, dtype="float32", remat="none")


class FakeClock:
    """Deterministic injectable clock: advance() moves time explicitly."""

    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_span_ts_dur_from_injected_clock(self):
        clk = FakeClock(10.0)
        tr = Tracer(clock=clk)
        with tr.span("outer"):
            clk.advance(0.5)
        ev = tr.events[0]
        assert ev["name"] == "outer" and ev["ph"] == "X"
        assert ev["ts"] == 0.0 and ev["dur"] == pytest.approx(0.5e6)

    def test_span_tree_nesting(self):
        clk = FakeClock()
        tr = Tracer(clock=clk)
        with tr.span("decode", step=0):
            with tr.span("draft"):
                clk.advance(0.001)
            with tr.span("verify"):
                clk.advance(0.002)
        with tr.span("decode", step=1):
            clk.advance(0.001)
        forest = tr.span_tree(tid=0)
        assert [n["name"] for n in forest] == ["decode", "decode"]
        assert [c["name"] for c in forest[0]["children"]] == \
            ["draft", "verify"]
        assert forest[0]["args"] == {"step": 0}
        assert forest[1]["children"] == []

    def test_span_tree_deterministic_under_frozen_clock(self):
        tr = Tracer(clock=lambda: 42.0)       # time never moves
        with tr.span("a"):
            with tr.span("b"):
                pass
            with tr.span("c"):
                pass
        (root,) = tr.span_tree()
        assert [c["name"] for c in root["children"]] == ["b", "c"]

    def test_lanes_are_independent(self):
        tr = Tracer(clock=FakeClock())
        r1 = tr.new_tid("req-1")
        r2 = tr.new_tid("req-2")
        assert r1 != r2 and r1 != 0
        with tr.span("request", tid=r1):
            with tr.span("decode"):           # engine lane, not nested in r1
                pass
        assert [n["name"] for n in tr.span_tree(tid=r1)] == ["request"]
        assert [n["name"] for n in tr.span_tree(tid=0)] == ["decode"]

    def test_retro_complete_span(self):
        clk = FakeClock(50.0)
        tr = Tracer(clock=clk)
        t0 = clk()
        clk.advance(1.25)
        tr.complete("request", t0, 1.25, tid=3, rid=7)
        ev = tr.events[0]
        assert ev["ts"] == pytest.approx(0.0)
        assert ev["dur"] == pytest.approx(1.25e6)
        assert ev["tid"] == 3 and ev["args"] == {"rid": 7}

    def test_chrome_export_is_valid(self):
        clk = FakeClock()
        tr = Tracer(clock=clk)
        tr.name_thread(0, "engine")
        rid = tr.new_tid("req-0")
        with tr.span("prefill", n_tokens=4):
            clk.advance(0.01)
        tr.event("first_token", tid=rid)
        doc = json.loads(tr.to_json())
        assert doc["displayTimeUnit"] == "ms"
        phs = {ev["ph"] for ev in doc["traceEvents"]}
        assert phs == {"M", "X", "i"}
        names = {ev["name"] for ev in doc["traceEvents"] if ev["ph"] == "M"}
        assert names == {"process_name", "thread_name"}
        for ev in doc["traceEvents"]:
            assert "depth" not in ev       # internal field stays internal

    def test_instant_event_fields(self):
        tr = Tracer(clock=FakeClock())
        tr.event("preempt", rid=2)
        ev = tr.events[0]
        assert ev["ph"] == "i" and ev["s"] == "t" and ev["args"]["rid"] == 2


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_percentiles_uniform(self):
        h = Histogram(DEFAULT_MS_BUCKETS)
        for v in range(1, 101):               # 1..100 ms
            h.record(float(v))
        assert h.count == 100
        assert h.percentile(50) == pytest.approx(50.0, rel=0.25)
        assert h.percentile(95) == pytest.approx(95.0, rel=0.25)
        assert h.percentile(99) == pytest.approx(99.0, rel=0.25)

    def test_percentile_clamped_to_observed_range(self):
        h = Histogram(DEFAULT_MS_BUCKETS)
        h.record(3.0)
        h.record(3.5)
        assert h.percentile(0) >= 3.0
        assert h.percentile(100) <= 3.5

    def test_overflow_bucket_reports_max(self):
        h = Histogram(buckets=(1.0, 2.0))
        h.record(1000.0)
        assert h.percentile(99) == 1000.0
        assert h.snapshot()["max"] == 1000.0

    def test_snapshot_fields(self):
        h = Histogram(DEFAULT_MS_BUCKETS)
        h.record(2.0)
        snap = h.snapshot()
        for field in ("count", "sum", "min", "max", "p50", "p95", "p99"):
            assert field in snap
        assert snap["count"] == 1 and snap["sum"] == 2.0


class TestRegistry:
    def test_counter_gauge_histogram_and_labels(self):
        reg = MetricsRegistry()
        reg.counter("toks", tenant="gold").inc(3)
        reg.counter("toks", tenant="gold").inc()
        reg.counter("toks", tenant="bronze").inc()
        reg.gauge("occ").set(0.5)
        reg.histogram("lat_ms").record(4.0)
        snap = reg.snapshot()
        assert snap["counters"]['toks{tenant="gold"}'] == 4
        assert snap["counters"]['toks{tenant="bronze"}'] == 1
        assert snap["gauges"]["occ"] == 0.5
        assert snap["histograms"]["lat_ms"]["count"] == 1

    def test_find_does_not_create(self):
        reg = MetricsRegistry()
        assert reg.find("nope") is None
        assert reg.snapshot()["counters"] == {}
        reg.counter("yes").inc()
        assert reg.find("yes").value == 1

    def test_kind_collision_raises(self):
        reg = MetricsRegistry()
        reg.counter("m")
        with pytest.raises(TypeError):
            reg.gauge("m")

    def test_prometheus_export(self):
        reg = MetricsRegistry()
        reg.counter("toks", tenant="gold").inc(2)
        reg.histogram("lat_ms", buckets=(1.0, 10.0)).record(5.0)
        text = reg.to_prometheus()
        assert '# TYPE toks counter' in text
        assert 'toks{tenant="gold"} 2' in text
        assert 'lat_ms_bucket{le="10"} 1' in text
        assert 'lat_ms_bucket{le="+Inf"} 1' in text
        assert 'lat_ms_count 1' in text

    def test_save_selects_format_by_suffix(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        p_json = tmp_path / "m.json"
        p_prom = tmp_path / "m.prom"
        reg.save(str(p_json))
        reg.save(str(p_prom))
        assert json.loads(p_json.read_text())["counters"]["c"] == 1
        assert p_prom.read_text().startswith("# TYPE c counter")

    def test_stopwatch_uses_injected_clock(self):
        clk = FakeClock(7.0)
        sw = Stopwatch(clock=clk)
        clk.advance(0.25)
        assert sw.elapsed() == pytest.approx(0.25)
        assert sw.elapsed_ms() == pytest.approx(250.0)
        sw.reset()
        assert sw.elapsed() == 0.0


# ---------------------------------------------------------------------------
# no-op path
# ---------------------------------------------------------------------------

class TestNoop:
    def test_noop_records_nothing(self):
        obs = Observability(enabled=False)
        with obs.span("decode"):
            pass
        obs.event("preempt")
        obs.metrics.counter("c", tenant="x").inc(5)
        obs.metrics.histogram("h").record(1.0)
        assert obs.tracer.events == ()
        assert obs.metrics.snapshot() == {}
        assert obs.metrics.find("c", tenant="x") is None

    def test_noop_singleton_disabled(self):
        assert NOOP.enabled is False
        assert NOOP.tracer.enabled is False
        assert NOOP.metrics.enabled is False


# ---------------------------------------------------------------------------
# serve wiring
# ---------------------------------------------------------------------------

def _serve(obs=None, n_req=3, max_new=6, seed=0):
    params = transformer.init_params(TINY, jax.random.key(0))
    ecfg = EngineConfig(max_len=32, kv_bits=8, kv_group=16, backend="ref")
    pcfg = PagedConfig(max_slots=2, page_size=4, n_pages=24, max_context=32)
    server = Server(TINY, params, ecfg, pcfg, seed=seed, obs=obs)
    rng = np.random.default_rng(3)
    rids = [server.submit(list(map(int, rng.integers(0, 256, size=5))),
                          RequestParams(max_new_tokens=max_new))
            for _ in range(n_req)]
    server.drain()
    return server, [server.output(r) for r in rids]


class TestServeWiring:
    def test_trace_and_metrics_valid(self):
        obs = Observability()
        server, _ = _serve(obs=obs)
        names = check_trace(obs.tracer.to_chrome())
        assert names["prefill"] == 3 and names["queued"] == 3
        assert names["request"] == 3 and names["decode"] >= 1
        keys = check_metrics(obs.metrics.snapshot())
        assert 'serve_ttft_ms{tenant="default"}' in keys
        ttft = obs.metrics.find("serve_ttft_ms", tenant="default")
        assert ttft.count == 3
        itl = obs.metrics.find("serve_itl_ms", tenant="default")
        assert itl.count == 3 * (6 - 1)       # max_new-1 gaps per request
        assert obs.metrics.find("serve_tokens_total",
                                tenant="default").value == 18
        assert obs.metrics.find("serve_completions_total",
                                tenant="default").value == 3

    def test_tokens_identical_and_no_retrace(self):
        _, plain = _serve(obs=None)
        server, traced = _serve(obs=Observability())
        assert traced == plain                 # instrumentation is invisible
        assert server.engine.decode_compilations == 1

    def test_request_lane_carries_lifecycle(self):
        obs = Observability()
        server, _ = _serve(obs=obs, n_req=1)
        req = server.scheduler.request(0)
        assert req.trace_tid != 0
        lane = obs.tracer.span_tree(tid=req.trace_tid)
        assert sorted(n["name"] for n in lane) == ["queued", "request"]
        events = [e["name"] for e in obs.tracer.events
                  if e["tid"] == req.trace_tid and e["ph"] == "i"]
        assert "submit" in events and "first_token" in events

    def test_set_obs_swaps_sink(self):
        server, _ = _serve(obs=None)
        obs = Observability()
        server.set_obs(obs)
        server.submit([1, 2, 3], RequestParams(max_new_tokens=3))
        server.drain()
        assert obs.metrics.find("serve_ttft_ms", tenant="default").count == 1
        assert any(e["name"] == "prefill" for e in obs.tracer.events)

    def test_pool_events(self):
        obs = Observability()
        _serve(obs=obs, n_req=2)
        allocs = [e for e in obs.tracer.events if e["name"] == "alloc"]
        frees = [e for e in obs.tracer.events if e["name"] == "free"]
        assert len(allocs) >= 2 and len(frees) == 2   # growth allocs too
        pages = sum(e["args"]["n_pages"] for e in allocs)
        assert obs.metrics.find("pool_alloc_total").value == pages


# ---------------------------------------------------------------------------
# speculative wiring
# ---------------------------------------------------------------------------

class TestSpecWiring:
    def test_draft_verify_spans_and_counters(self):
        from repro.plan import QuantPlan
        from repro.plan.plan import candidates_for
        from repro.spec import SpeculativeEngine
        cands = candidates_for(TINY, ["lq2w"])
        params = transformer.init_params(TINY, jax.random.key(0))
        ecfg = EngineConfig(max_len=32, kv_bits=8, kv_group=16,
                            backend="ref")
        pcfg = PagedConfig(max_slots=2, page_size=4, n_pages=24,
                           max_context=32)
        obs = Observability()
        eng = SpeculativeEngine(TINY, params, ecfg, pcfg,
                                draft_plan=QuantPlan(default=cands["lq2w"]),
                                spec_k=3, obs=obs)
        server = Server(TINY, params, ecfg, pcfg, engine=eng, obs=obs)
        rng = np.random.default_rng(3)
        server.submit(list(map(int, rng.integers(0, 256, size=5))),
                      RequestParams(max_new_tokens=6))
        server.drain()
        check_trace(obs.tracer.to_chrome(), spec=True)
        check_metrics(obs.metrics.snapshot(), spec=True)
        # the engine lane: one step span per scheduler step, the decode
        # span inside it
        decodes = [c for n in obs.tracer.span_tree(tid=0)
                   if n["name"] == "step" for c in n["children"]
                   if c["name"] == "decode"]
        assert decodes, "no decode spans on the engine lane"
        kids = [c["name"] for c in decodes[0]["children"]]
        assert kids == ["draft", "verify"]
        drafted = obs.metrics.find("spec_drafted_total").value
        accepted = obs.metrics.find("spec_accepted_total").value
        assert drafted > 0 and 0 <= accepted <= drafted
        rate = obs.metrics.find("spec_acceptance_rate").value
        assert rate == pytest.approx(accepted / drafted)
        assert eng.decode_compilations == 1    # batched verify: one trace
        draft_hist = obs.metrics.find("serve_decode_step_ms", engine="draft")
        assert draft_hist is not None and draft_hist.count > 0


# ---------------------------------------------------------------------------
# fleet wiring + telemetry
# ---------------------------------------------------------------------------

class TestFleetTelemetry:
    def test_degenerate_window_still_reports_rate(self):
        from repro.fleet import FleetTelemetry
        t = FleetTelemetry(clock=lambda: 5.0, min_window_s=1e-3)
        t.note_step("a", 0.25)                # first == last step instant
        t.note_token("a")
        t.note_token("a")
        snap = t.snapshot()
        assert snap["tenants"]["a"]["tok_per_s"] == pytest.approx(2000.0)
        assert snap["aggregate"]["tok_per_s"] == pytest.approx(2000.0)

    def test_idle_tenant_still_zero(self):
        from repro.fleet import FleetTelemetry
        t = FleetTelemetry(clock=lambda: 5.0)
        t.register("idle")
        assert t.snapshot()["tenants"]["idle"]["tok_per_s"] == 0.0

    def test_moving_clock_unchanged_by_floor(self):
        from repro.fleet import FleetTelemetry
        clk = FakeClock(0.0)
        t = FleetTelemetry(clock=clk)
        t.note_step("a", 0.5)
        for _ in range(4):
            t.note_token("a")
        clk.advance(2.0)
        t.note_step("a", 0.5)
        assert t.snapshot()["tenants"]["a"]["tok_per_s"] == \
            pytest.approx(2.0)

    def test_snapshot_merges_latency_percentiles(self):
        from repro.fleet import FleetTelemetry
        obs = Observability()
        obs.metrics.histogram("serve_ttft_ms", tenant="gold").record(10.0)
        obs.metrics.histogram("serve_itl_ms", tenant="gold").record(2.0)
        t = FleetTelemetry(obs=obs)
        t.note_step("gold", 0.1)
        snap = t.snapshot()
        assert "p50" in snap["tenants"]["gold"]["ttft_ms"]
        assert "p95" in snap["tenants"]["gold"]["itl_ms"]

    def test_router_snapshot_has_per_tenant_latency(self):
        from repro.fleet import FleetRegistry, FleetRouter, TenantSpec
        params = transformer.init_params(TINY, jax.random.key(0))
        registry = FleetRegistry(TINY, params, budget_mb=64, backend="ref")
        for tid, scheme, bits in (("gold", "lq8w", 8), ("bronze", "lq2w", 2)):
            registry.register(TenantSpec(tid, scheme=scheme, kv_bits=bits,
                                         kv_group=16, max_slots=2,
                                         page_size=4, n_pages=16,
                                         max_context=24))
        router = FleetRouter(registry, obs=Observability())
        rng = np.random.default_rng(0)
        for tid in ("gold", "bronze"):
            router.submit(tid, list(map(int, rng.integers(0, 256, size=6))),
                          max_new_tokens=4)
        router.drain(max_steps=1000)
        snap = router.telemetry.snapshot()
        for tid in ("gold", "bronze"):
            assert snap["tenants"][tid]["ttft_ms"]["p50"] > 0
            assert snap["tenants"][tid]["itl_ms"]["p95"] > 0
        check_trace(router.obs.tracer.to_chrome())
