"""Tests for the online quality plane (obs/numerics.py, obs/residuals.py,
obs/flight.py, obs/export.py): shadow-divergence and KV dequant probes
are host-side-only (bit-identical tokens, one compiled decode step),
error gauges move with bitwidth, cost-model residual ratios self-check at
1.0 and the calibration loop round-trips, the flight recorder dumps on
anomalies under its rate limits, and the live /metrics endpoint serves
Prometheus text."""
import json
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from repro.core import kvwire, schemes
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.obs import (FlightRecorder, MetricsServer, Observability,
                       calibrated_hw, fit_calibration, load_calibration,
                       record_residuals, record_weight_wire_error,
                       save_calibration)
from repro.obs.check import check_numerics
from repro.obs.numerics import (AcceptanceDrift, NumericsConfig,
                                QualityMonitor, layer_blocks)
from repro.plan.costmodel import plan_cost
from repro.plan.plan import candidates_for
from repro.serve import EngineConfig, PagedConfig, RequestParams, Server

TINY = ModelConfig(name="tiny", family="dense", n_layers=3, d_model=64,
                   vocab_size=256, n_heads=4, n_kv_heads=2, head_dim=16,
                   d_ff=128, dtype="float32", remat="none")


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(TINY, jax.random.key(0))


def _server(params, obs=None, kv_bits=8, seed=0):
    ecfg = EngineConfig(max_len=32, kv_bits=kv_bits, kv_group=16,
                        backend="ref")
    pcfg = PagedConfig(max_slots=2, page_size=4, n_pages=24, max_context=32)
    return Server(TINY, params, ecfg, pcfg, seed=seed, obs=obs)


def _drive(server, n_req=3, max_new=6):
    rng = np.random.default_rng(3)
    rids = [server.submit(list(map(int, rng.integers(0, 256, size=5))),
                          RequestParams(max_new_tokens=max_new))
            for _ in range(n_req)]
    server.drain()
    return [server.output(r) for r in rids]


@pytest.fixture(scope="module")
def quality_run(params):
    """One instrumented serve run with probes on + its plain reference."""
    ref = _drive(_server(params))
    obs = Observability()
    server = _server(params, obs=obs)
    monitor = server.attach_quality(QualityMonitor(
        obs, TINY, params, server.engine,
        ncfg=NumericsConfig(every_n_steps=2)))
    out = _drive(server)
    residuals = record_residuals(obs, TINY, server.engine, server.pool)
    return {"ref": ref, "out": out, "obs": obs, "server": server,
            "monitor": monitor, "residuals": residuals}


# ---------------------------------------------------------------------------
# shadow divergence + KV dequant probes
# ---------------------------------------------------------------------------

class TestQualityMonitor:
    def test_probes_are_invisible(self, quality_run):
        # bit-identical tokens, ONE compiled decode step: the replay jits
        # never touch the engine's functions
        assert quality_run["out"] == quality_run["ref"]
        assert quality_run["server"].engine.decode_compilations == 1

    def test_shadow_metrics_recorded(self, quality_run):
        m = quality_run["obs"].metrics
        kl = m.find("quality_shadow_kl")
        probes = m.find("quality_shadow_probes_total")
        assert kl is not None and kl.count == probes.value > 0
        assert kl.max < 1.0          # fp weights + 8-bit KV: tiny divergence
        agree = m.find("quality_shadow_top1_agree")
        assert agree is not None and 0.0 <= agree.value <= 1.0

    def test_kv_gauges_cover_every_layer(self, quality_run):
        m = quality_run["obs"].metrics
        for i in range(TINY.n_layers):
            g = m.find("kv_dequant_mse", layer=f"layer{i}")
            assert g is not None and 0.0 <= g.value < 1e-2   # 8-bit: small
            assert m.find("kv_dequant_maxabs", layer=f"layer{i}") is not None
            bits = m.find("kv_dequant_bits", layer=f"layer{i}")
            assert bits is not None and bits.value == 8.0    # deployed wire

    def test_snapshot_passes_check_numerics(self, quality_run):
        found = check_numerics(quality_run["obs"].metrics.snapshot())
        assert "quality_shadow_kl" in found

    def test_lower_kv_bits_larger_dequant_error(self, params):
        def mean_mse(kv_bits):
            obs = Observability()
            server = _server(params, obs=obs, kv_bits=kv_bits)
            server.attach_quality(QualityMonitor(
                obs, TINY, params, server.engine,
                ncfg=NumericsConfig(every_n_steps=2)))
            _drive(server, n_req=2)
            vals = [obs.metrics.find("kv_dequant_mse",
                                     layer=f"layer{i}").value
                    for i in range(TINY.n_layers)]
            return float(np.mean(vals))
        assert mean_mse(2) > mean_mse(8) > 0.0

    def test_probe_sampling_knob(self, params):
        obs = Observability()
        server = _server(params, obs=obs)
        server.attach_quality(QualityMonitor(
            obs, TINY, params, server.engine,
            ncfg=NumericsConfig(every_n_steps=0)))    # probes disabled
        _drive(server, n_req=1)
        assert obs.metrics.find("quality_shadow_kl") is None


def test_layer_blocks_enumerates_params_in_order(params):
    idx = [i for i, _ in layer_blocks(params["decoder"], TINY)]
    assert idx == list(range(TINY.n_layers))
    blocks = dict(layer_blocks(params["decoder"], TINY))
    leaves = jax.tree.leaves(blocks[0])
    assert all(leaf.ndim >= 1 for leaf in leaves)    # stack dim sliced away


# two pattern positions over five layers: two superblocks and a tail layer
TWO = ModelConfig(name="two", family="dense", n_layers=5, d_model=64,
                  vocab_size=256, n_heads=4, n_kv_heads=2, head_dim=16,
                  d_ff=128, dtype="float32", remat="none", window=8,
                  pattern=(("attn", "swiglu"), ("attn_local", "swiglu")))


def _weight_bits(block):
    return getattr(block["mixer"]["wq"]["w"], "bits", None)


def _cache_bits(block):
    k = block["self"]["k"]
    return kvwire.kv_bits_of(k, TWO.head_dim) if kvwire.is_quant_kv(k) \
        else None


def _page_bits(block):
    spec = kvwire.page_spec(block["self"]["k"], TWO.head_dim,
                            4 * TWO.n_kv_heads)
    return spec[0] if spec else None


def _stack_tree(kind):
    """(tree, per-layer bits, bits reader, run sizes) of one tree kind."""
    from repro.plan import QuantPlan
    from repro.serve import make_pool_pages
    if kind == "uniform_params":
        tree = transformer.init_params(TWO, jax.random.key(0))["decoder"]
        return tree, (None,) * 5, _weight_bits, [2]
    if kind == "plan_params":
        c = candidates_for(TWO, ["lq8w", "lq4w"])
        plan = QuantPlan.from_assignment(
            {"layer.0": c["lq8w"], "layer.1": c["lq8w"]}, default=c["lq4w"])
        fp = transformer.init_params(TWO, jax.random.key(0))
        tree = transformer.quantize_params(fp, TWO, plan)["decoder"]
        return tree, (8, 8, 4, 4, 4), _weight_bits, [1, 1]
    if kind == "contiguous_cache":
        bits = (8, 8, 2, 2, 4)
        return (transformer.init_cache(TWO, 1, 16, kv_quant=(bits, 16)),
                bits, _cache_bits, [1, 1])
    bits = 4 if kind == "uniform_pool" else (8, 8, None, None, 2)
    pages = make_pool_pages(TWO, n_pages=4, page_size=4, kv_bits=bits,
                            kv_group=16)
    if kind == "uniform_pool":
        return pages, (4,) * 5, _page_bits, [2]
    return pages, bits, _page_bits, [1, 1]


@pytest.mark.parametrize("kind", ["uniform_params", "plan_params",
                                  "contiguous_cache", "uniform_pool",
                                  "mixed_pool"])
def test_stack_runs_give_every_layer_once_in_order(kind):
    """``transformer.stack_runs`` reads any decoder-stack tree as its runs
    in layer order, and ``layer_blocks`` walks them layer by layer."""
    tree, want_bits, bits_of, want_sizes = _stack_tree(kind)
    runs = transformer.stack_runs(tree)
    sizes = [jax.tree.leaves(run)[0].shape[0] for run in runs]
    assert sizes == want_sizes and sum(sizes) == TWO.n_super
    blocks = list(layer_blocks(tree, TWO))
    assert [i for i, _ in blocks] == list(range(TWO.n_layers))
    assert tuple(bits_of(block) for _, block in blocks) == want_bits


# ---------------------------------------------------------------------------
# weight wire-error
# ---------------------------------------------------------------------------

class TestWeightWireError:
    def test_lower_bits_larger_error(self, params):
        cands = candidates_for(TINY, ["lq8w", "lq2w"])
        e8 = record_weight_wire_error(None, TINY, params, cands["lq8w"])
        e2 = record_weight_wire_error(None, TINY, params, cands["lq2w"])
        assert set(e8) == {f"layer{i}" for i in range(TINY.n_layers)}
        for label in e8:
            assert e8[label]["n_weights"] == e2[label]["n_weights"] > 0
            assert 0.0 < e8[label]["mse"] < e2[label]["mse"]

    def test_fp_scheme_zero_error(self, params):
        out = record_weight_wire_error(None, TINY, params, schemes.FP32)
        assert all(s["mse"] == 0.0 and s["n_weights"] == 0
                   for s in out.values())

    def test_gauges_recorded(self, params):
        cands = candidates_for(TINY, ["lq8w"])
        obs = Observability()
        record_weight_wire_error(obs, TINY, params, cands["lq8w"])
        g = obs.metrics.find("quant_weight_mse", layer="layer0")
        assert g is not None and g.value > 0.0


# ---------------------------------------------------------------------------
# spec-acceptance drift
# ---------------------------------------------------------------------------

class TestAcceptanceDrift:
    def test_fires_once_per_breach_episode(self):
        d = AcceptanceDrift(alpha=1.0, threshold=0.1, min_cycles=2,
                            baseline=0.9)
        assert d.update(0.9) is False      # warmup cycle
        assert d.update(0.9) is False      # settled, no breach
        assert d.update(0.5) is True       # breach edge fires
        assert d.update(0.5) is False      # latched: no re-fire
        assert d.update(0.9) is False      # recovery clears the latch
        assert d.update(0.5) is True       # next episode fires again

    def test_baseline_auto_calibrates(self):
        d = AcceptanceDrift(alpha=1.0, threshold=0.1, min_cycles=3)
        for _ in range(3):
            assert d.update(0.8) is False
        assert d.baseline == pytest.approx(0.8)
        assert d.update(0.4) is True

    def test_alpha_validated(self):
        with pytest.raises(ValueError, match="alpha"):
            AcceptanceDrift(alpha=0.0)

    def test_spec_engine_feeds_drift(self, params):
        from repro.plan import QuantPlan
        from repro.spec import SpeculativeEngine
        cands = candidates_for(TINY, ["lq2w"])
        ecfg = EngineConfig(max_len=32, kv_bits=8, kv_group=16,
                            backend="ref")
        pcfg = PagedConfig(max_slots=2, page_size=4, n_pages=24,
                           max_context=32)
        obs = Observability()
        eng = SpeculativeEngine(TINY, params, ecfg, pcfg,
                                draft_plan=QuantPlan(default=cands["lq2w"]),
                                spec_k=3, obs=obs)
        server = Server(TINY, params, ecfg, pcfg, engine=eng, obs=obs)
        server.attach_quality(QualityMonitor(
            obs, TINY, params, eng,
            ncfg=NumericsConfig(every_n_steps=0)))   # drift only
        _drive(server, n_req=2)
        ewma = obs.metrics.find("spec_acceptance_ewma")
        assert ewma is not None and 0.0 <= ewma.value <= 1.0


# ---------------------------------------------------------------------------
# cost-model residuals + calibration loop
# ---------------------------------------------------------------------------

class TestResiduals:
    def test_byte_ratios_are_exact(self, quality_run):
        res = quality_run["residuals"]
        assert res["weight_bytes"]["ratio"] == pytest.approx(1.0)
        assert res["kv_bytes"]["ratio"] == pytest.approx(1.0)
        assert res["decode_ms"]["measured"] > 0.0

    def test_residual_gauges(self, quality_run):
        g = quality_run["obs"].metrics.find(
            "costmodel_residual", quantity="kv_bytes", stat="ratio")
        assert g is not None and g.value == pytest.approx(1.0)

    def test_calibration_roundtrip(self, quality_run, tmp_path):
        calib = fit_calibration(quality_run["residuals"], model=TINY.name)
        assert calib["ms_factor"] > 0 and calib["model"] == "tiny"
        path = tmp_path / "calib.json"
        save_calibration(path, calib)
        assert load_calibration(path)["ms_factor"] == calib["ms_factor"]

    def test_load_rejects_non_calibration(self, tmp_path):
        p = tmp_path / "other.json"
        p.write_text(json.dumps({"foo": 1}))
        with pytest.raises(ValueError, match="ms_factor"):
            load_calibration(p)

    def test_calibrated_hw_scales_predicted_ms(self):
        configs = (schemes.get("lq8w"),) * TINY.n_layers
        base = plan_cost(TINY, configs)
        slow = plan_cost(TINY, configs, calibrated_hw(2.5))
        assert slow["ms"] == pytest.approx(2.5 * base["ms"])
        assert slow["bytes"] == base["bytes"]      # bytes are hw-free

    def test_calibrated_hw_rejects_nonpositive(self):
        with pytest.raises(ValueError, match="positive"):
            calibrated_hw(0.0)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def _event(name, **args):
    return {"name": name, "ph": "i", "ts": 0.0, "pid": 0, "tid": 0,
            "args": args}


class TestFlightRecorder:
    def test_ring_is_bounded(self):
        fr = FlightRecorder(capacity=4, clock=FakeClock())
        for i in range(10):
            fr.on_record(_event("decode", step=i))
        assert len(fr.ring) == 4
        assert fr.ring[0]["args"]["step"] == 6

    def test_alloc_fail_triggers_dump(self):
        fr = FlightRecorder(clock=FakeClock())
        fr.on_record(_event("decode"))
        fr.on_record(_event("alloc_fail", rid=7, n_pages=3, free=1))
        assert len(fr.dumps) == 1
        d = fr.dumps[0]
        assert d["reason"] == "alloc_fail" and d["info"]["rid"] == 7
        assert [e["name"] for e in d["events"]] == ["decode", "alloc_fail"]

    def test_cooldown_suppresses_then_recovers(self):
        clk = FakeClock()
        fr = FlightRecorder(cooldown_s=5.0, clock=clk)
        fr.on_record(_event("alloc_fail"))
        fr.on_record(_event("alloc_fail"))      # inside cooldown
        assert len(fr.dumps) == 1 and fr.dropped_dumps == 1
        clk.advance(6.0)
        fr.on_record(_event("alloc_fail"))
        assert len(fr.dumps) == 2

    def test_preempt_storm_window(self):
        clk = FakeClock()
        fr = FlightRecorder(storm_n=3, storm_window_s=1.0, clock=clk)
        for _ in range(2):
            fr.on_record(_event("preempt"))
        clk.advance(2.0)                        # the window slides past
        fr.on_record(_event("preempt"))
        assert not fr.dumps
        fr.on_record(_event("preempt"))
        fr.on_record(_event("preempt"))
        assert len(fr.dumps) == 1
        assert fr.dumps[0]["reason"] == "preempt_storm"
        assert fr.dumps[0]["info"]["preempts"] == 3

    def test_max_dumps_cap(self):
        clk = FakeClock()
        fr = FlightRecorder(max_dumps=2, cooldown_s=0.0, clock=clk)
        for _ in range(4):
            fr.on_record(_event("alloc_fail"))
            clk.advance(1.0)
        assert len(fr.dumps) == 2 and fr.dropped_dumps == 2

    def test_dump_files_and_save(self, tmp_path):
        out = tmp_path / "flight.json"
        fr = FlightRecorder(out=str(out), clock=FakeClock())
        fr.on_record(_event("drift_alarm", ewma=0.3))
        dump_path = tmp_path / "flight.json.1.drift_alarm.json"
        assert json.loads(dump_path.read_text())["reason"] == "drift_alarm"
        fr.save(out)
        snap = json.loads(out.read_text())
        assert len(snap["dumps"]) == 1 and snap["dropped_dumps"] == 0

    def test_pool_exhaustion_reaches_recorder(self, params):
        obs = Observability()
        fr = obs.attach_flight(FlightRecorder())
        server = _server(params, obs=obs)
        ok = server.pool.alloc(99, server.pool.n_allocatable + 1)
        assert ok is False
        assert fr.dumps and fr.dumps[0]["reason"] == "alloc_fail"
        assert obs.metrics.find("pool_alloc_fail_total").value == 1


# ---------------------------------------------------------------------------
# live /metrics endpoint
# ---------------------------------------------------------------------------

class TestMetricsServer:
    def test_routes(self):
        obs = Observability()
        obs.metrics.counter("serve_tokens_total", tenant="t").inc(5)
        obs.metrics.histogram("serve_itl_ms").record(1.5)
        with MetricsServer(obs, port=0) as srv:
            text = urllib.request.urlopen(f"{srv.url}/metrics").read()
            body = text.decode()
            assert 'serve_tokens_total{tenant="t"} 5' in body
            assert "# TYPE serve_itl_ms histogram" in body
            assert urllib.request.urlopen(
                f"{srv.url}/healthz").read() == b"ok\n"
            snap = json.loads(urllib.request.urlopen(
                f"{srv.url}/snapshot.json").read())
            assert snap["counters"]['serve_tokens_total{tenant="t"}'] == 5
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(f"{srv.url}/nope")
            assert exc.value.code == 404

    def test_close_releases_port(self):
        obs = Observability()
        srv = MetricsServer(obs, port=0)
        url = srv.url
        srv.close()
        with pytest.raises(urllib.error.URLError):
            urllib.request.urlopen(f"{url}/healthz", timeout=0.5)

    def test_concurrent_scrapes_while_recording(self):
        # a GET storm against /metrics + /snapshot.json while the
        # registry is being written: every response parses, none hangs
        # (scrapers race the serving threads in production)
        import threading

        obs = Observability()
        for i in range(8):
            obs.metrics.histogram("serve_itl_ms",
                                  tenant=f"t{i}").record(1.0)
        stop = threading.Event()

        def writer():
            i = 0
            while not stop.is_set():
                obs.metrics.counter("serve_tokens_total",
                                    tenant=f"t{i % 8}").inc()
                obs.metrics.histogram(
                    "serve_itl_ms", tenant=f"t{i % 8}").record(i % 7 + 0.5)
                i += 1

        errors: list = []

        def scraper(url, n=20):
            try:
                for _ in range(n):
                    body = urllib.request.urlopen(
                        f"{url}/metrics", timeout=5).read().decode()
                    assert "# TYPE serve_itl_ms histogram" in body
                    snap = json.loads(urllib.request.urlopen(
                        f"{url}/snapshot.json", timeout=5).read())
                    assert "histograms" in snap
            except Exception as e:                 # pragma: no cover
                errors.append(e)

        with MetricsServer(obs, port=0) as srv:
            wt = threading.Thread(target=writer, daemon=True)
            wt.start()
            threads = [threading.Thread(target=scraper, args=(srv.url,))
                       for _ in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            stop.set()
            wt.join(timeout=5)
        assert not errors, errors
        assert not any(t.is_alive() for t in threads), "scrape hung"


# ---------------------------------------------------------------------------
# straggler monitor on the shared Stopwatch
# ---------------------------------------------------------------------------

def test_straggler_uses_injectable_clock():
    from repro.distributed.straggler import StragglerMonitor
    clk = FakeClock()
    mon = StragglerMonitor(clock=clk)
    mon.start()
    clk.advance(0.25)
    assert mon.stop() == pytest.approx(0.25)
    assert mon.stats()["count"] == 1
