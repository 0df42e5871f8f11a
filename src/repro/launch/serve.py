"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Spins up the batched engine on the reduced config (``--full``: the
published config), optionally with the paper's quantization applied to
weights (--scheme lq4w), activations (--a-bits) and the KV cache
(--kv-bits), and reports tokens/s plus the cache-bytes saving.  Under
``--full --scheme`` the weights are drawn and packed one layer at a time
(``transformer.init_params(qcfg=...)``), so a published-width model never
holds its fp32 masters on the device next to the packed copy.  Kernels
run through ``backend="auto"``: compiled Pallas on a TPU, the jnp
reference elsewhere.

Compiled programs persist in JAX's compilation cache: the directory
``JAX_COMPILATION_CACHE_DIR`` names when it is set, else ``.jax_cache``
at the root of the checkout (:func:`enable_compile_cache`).

``--continuous N`` switches to the continuous-batching serve layer
(serve/server.py): N requests with staggered arrivals are scheduled over
the paged quantized KV pool, reporting throughput and pool occupancy.

``--fleet fleet.json`` hosts a multi-tenant fleet (repro.fleet): every
manifest tenant gets its own per-plan engine + pool behind one router and
one ``--budget-mb`` host budget; a staggered workload is routed across
tenants and per-tenant telemetry (tok/s, occupancy, rejections) is
reported.  The manifest carries the arch, so ``--arch`` is optional.

``--trace-out trace.json`` / ``--metrics-out metrics.json`` attach a
:class:`repro.obs.Observability` *after* jit warmup and write a
Chrome/Perfetto trace (open at ``ui.perfetto.dev``) and a metrics
snapshot (TTFT/ITL/queue-wait p50/p95, counters).  A ``.prom`` metrics
path emits Prometheus text format instead of JSON.

The quality plane (``repro.obs`` numerics/residuals/flight/export) rides
the same switch: ``--numerics`` samples shadow-divergence + KV
dequant-error probes every ``--numerics-every`` decode steps and prints
cost-model residuals at the end (``--calibration-out`` persists the
fitted roofline correction ``repro.launch.plan --calibration`` consumes);
``--serve-metrics PORT`` serves live ``/metrics`` (Prometheus text),
``/healthz`` and ``/snapshot.json`` over stdlib HTTP (port 0 picks an
ephemeral port); ``--flight-out`` arms a flight recorder that dumps the
recent span/event ring on anomalies (preemption storm, pool alloc
failure, drift alarm, SLO breach) and saves it at exit.

The SLO plane (``repro.obs.slo`` / ``repro.obs.health``) judges the
measurements against targets: ``--slo slo.json`` loads an
:class:`repro.obs.SLOSpec` (``--fleet`` manifests may carry an ``slo:``
section instead), polls an :class:`repro.obs.SLOTracker` plus a
:class:`repro.obs.HealthMonitor` every decode step, exposes
``/slo.json`` on the live endpoint, and ``--slo-report out.json``
persists the final per-tenant budget/burn/episode report —
``python -m repro.obs.slo out.json`` gates on it (exit 1 on breach).
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import schemes
from repro.models import transformer
from repro.obs import Observability, Stopwatch
from repro.serve import (Engine, EngineConfig, PagedConfig, RequestParams,
                         Server)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    no other directory is set here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache`` — a fixed path, since the path is part of
    what a later run must find again.  Entry points call this from
    ``main()``; importing the module changes nothing.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _make_obs(args) -> Observability | None:
    """One Observability per run when any instrumentation was requested."""
    if (args.trace_out or args.metrics_out or args.numerics
            or args.flight_out or args.calibration_out or args.profile
            or args.slo or args.slo_report
            or args.serve_metrics is not None):
        return Observability()
    return None


def _report_utilization(obs, cfg, engine, pool, args, *, labels=None):
    """MFU / HBM-utilization gauges against the *measured* roof.

    Residuals are recorded first so the roofline constants can be
    calibrated to this host before the utilization division — a stock
    roof on a laptop would report a meaninglessly small MFU.
    """
    from repro.obs.profile import record_utilization
    from repro.obs.residuals import (calibrated_hw, fit_calibration,
                                     record_residuals)
    res = record_residuals(obs, cfg, engine, pool, labels=labels)
    hw = calibrated_hw(fit_calibration(res, model=cfg.name))
    u = record_utilization(obs, cfg, engine, pool, hw=hw, labels=labels)
    tag = f" [{labels}]" if labels else ""
    if u is None:
        print(f"utilization{tag}: no decode-step latency recorded")
        return None
    print(f"utilization{tag}: mfu {u['mfu']:.4f}, hbm {u['hbm_util']:.4f} "
          f"of the calibrated roof ({u['flops_per_step']:,.0f} FLOPs, "
          f"{u['bytes_per_step']:,.0f} B per {u['step_ms']:.3f} ms step)")
    return u


def _attach_extras(obs, args):
    """Flight recorder + live /metrics endpoint (both obs-taps; neither
    touches the engines).  Returns (flight, metrics_server)."""
    flight = msrv = None
    if args.flight_out:
        from repro.obs import FlightRecorder
        flight = obs.attach_flight(FlightRecorder(out=args.flight_out))
    if args.serve_metrics is not None:
        from repro.obs import MetricsServer
        msrv = MetricsServer(obs, port=args.serve_metrics)
        print(f"metrics endpoint: {msrv.url}/metrics (+ /healthz, "
              f"/snapshot.json)")
    return flight, msrv


def _finish_extras(flight, msrv, args):
    """Scrape the live endpoint once (proves it serves during the run),
    then save the flight ring."""
    if msrv is not None:
        import urllib.request
        with urllib.request.urlopen(f"{msrv.url}/metrics") as r:
            text = r.read().decode()
        print(f"/metrics live scrape: {len(text.splitlines())} lines of "
              f"Prometheus text")
        msrv.close()
    if flight is not None:
        flight.save(args.flight_out)
        print(f"wrote {args.flight_out} ({len(flight.ring)} ring events, "
              f"{len(flight.dumps)} anomaly dumps)")


def _load_slo_spec(args, manifest=None):
    """The run's SLOSpec: ``--slo`` file, else the manifest's ``slo:``
    section (fleet mode).  None when neither declares objectives."""
    if args.slo:
        from repro.obs.slo import SLOSpec
        return SLOSpec.load(args.slo)
    return manifest.slo if manifest is not None else None


def _report_slo(tracker, health, args):
    """Print the judgment summary; persist ``--slo-report`` (with the
    health snapshot riding along under ``"health"``)."""
    import json

    rep = tracker.report()
    if health is not None:
        rep["health"] = health.snapshot()
    for tid, objectives in sorted(rep["tenants"].items()):
        for objective, row in sorted(objectives.items()):
            print(f"slo [{tid}] {objective}: {row['state']}, budget "
                  f"{row['budget_remaining']:.3f}, burn fast "
                  f"{row['burn_fast']:.2f} / slow {row['burn_slow']:.2f}")
    print(f"slo: worst state {rep['worst_state']} over {rep['steps']} "
          f"steps ({tracker.suppressed_events} suppressed events)")
    if health is not None:
        for tid, row in sorted(health.snapshot()["tenants"].items()):
            print(f"health [{tid}]: {row['health']:.2f} "
                  f"({row.get('attention_mode', '?')})")
    if args.slo_report:
        with open(args.slo_report, "w") as f:
            json.dump(rep, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.slo_report} (gate with "
              f"python -m repro.obs.slo {args.slo_report})")


def _report_residuals(obs, cfg, engine, pool, args, *, labels=None):
    """Cost-model residuals (+ optional persisted calibration factor)."""
    from repro.obs.residuals import (fit_calibration, record_residuals,
                                     save_calibration)
    res = record_residuals(obs, cfg, engine, pool, labels=labels)
    tag = f" [{labels}]" if labels else ""
    for q, row in res.items():
        print(f"costmodel residual{tag} {q}: predicted "
              f"{row['predicted']:.5g} measured {row['measured']:.5g} "
              f"ratio {row['ratio']:.3f}")
    if args.calibration_out:
        save_calibration(args.calibration_out,
                         fit_calibration(res, model=cfg.name))
        print(f"wrote {args.calibration_out}")
    return res


def _save_obs(obs, args):
    """Write the requested trace/metrics artifacts + a latency summary."""
    if obs is None:
        return
    for name in ("serve_ttft_ms", "serve_itl_ms"):
        parts = []
        for key, h in sorted(obs.metrics.histograms.items()):
            if h.count and (key == name or key.startswith(name + "{")):
                parts.append(f"{key} p50={h.percentile(50):.1f} "
                             f"p95={h.percentile(95):.1f} (n={h.count})")
        if parts:
            print("latency:", "; ".join(parts))
    if args.trace_out:
        obs.save_trace(args.trace_out)
        print(f"wrote {args.trace_out} ({len(obs.tracer.events)} events; "
              f"open at ui.perfetto.dev)")
    if args.metrics_out:
        obs.save_metrics(args.metrics_out)
        print(f"wrote {args.metrics_out}")


def run_continuous(cfg, params, ecfg, args) -> dict:
    """Staggered-arrival continuous batching over the paged pool.

    Returns ``{"server", "rids", "warmup_s", "serve_s", "tokens"}``:
    the live server, the measured requests' ids, the wall time of the
    warm-up request (both jits compile there) and of the measured run.
    """
    import dataclasses
    want = args.prompt_len + args.steps + 8
    mc = -(-want // args.page_size) * args.page_size
    ecfg = dataclasses.replace(ecfg, max_len=max(ecfg.max_len, mc))
    pcfg = PagedConfig(max_slots=args.max_slots, page_size=args.page_size,
                       n_pages=args.n_pages, max_context=mc)
    engine = None
    if args.spec_plan is not None:
        from repro.plan import QuantPlan
        from repro.spec import SpeculativeEngine
        draft = QuantPlan.load(args.spec_plan)
        engine = SpeculativeEngine(cfg, params, ecfg, pcfg,
                                   draft_plan=draft, spec_k=args.spec_k)
        print(f"speculative: k={args.spec_k} draft={args.spec_plan} "
              f"shared {engine.shared_weight_bytes():,.0f} B of packed "
              f"leaves with the verifier")
    server = Server(cfg, params, ecfg, pcfg, engine=engine)
    rng = jax.random.key(2)
    warm = jax.random.randint(jax.random.fold_in(rng, args.continuous),
                              (args.prompt_len,), 0, cfg.vocab_size)
    sw = Stopwatch()
    server.submit(warm.tolist(), RequestParams(max_new_tokens=2))
    server.drain()                          # warm both jits off the clock
    warmup_s = sw.elapsed()
    print(f"warm-up (compiles prefill + decode): {warmup_s:.2f}s")
    obs = _make_obs(args)
    flight = msrv = quality = profiler = tracker = health = None
    if obs is not None:
        server.set_obs(obs)                 # compile time stays off the books
        flight, msrv = _attach_extras(obs, args)
        spec = _load_slo_spec(args)
        if args.slo_report and spec is None:
            raise SystemExit("--slo-report needs --slo in --continuous "
                             "mode (no manifest to carry targets)")
        if spec is not None:
            from repro.obs.health import HealthMonitor
            from repro.obs.slo import SLOTracker
            tracker = SLOTracker(spec, obs)
            health = HealthMonitor(obs, slo=tracker)
            # single-cell serves record under the "default" tenant label
            health.register("default", engine=server.engine,
                            pool=server.pool)
            if msrv is not None:
                msrv.attach_slo(tracker)
        if args.profile:
            from repro.obs.profile import PhaseProfiler
            profiler = server.attach_profiler(PhaseProfiler(
                obs, cfg, server.engine,
                every_n_steps=args.profile_every))
        if args.numerics:
            from repro.core import schemes
            from repro.obs.numerics import (NumericsConfig, QualityMonitor,
                                            record_weight_wire_error)
            record_weight_wire_error(
                obs, cfg, params,
                ecfg.plan if ecfg.plan is not None
                else schemes.get(args.scheme))
            quality = server.attach_quality(QualityMonitor(
                obs, cfg, params, server.engine,
                ncfg=NumericsConfig(every_n_steps=args.numerics_every)))
    import contextlib

    from repro.obs.profile import xprof_capture
    capture = (xprof_capture(args.xprof_out) if args.xprof_out
               else contextlib.nullcontext())
    occ, sw = [], Stopwatch()
    rids = []

    def tick():                 # one judgment poll per decode step
        if tracker is not None:
            tracker.on_step()
            health.on_step()

    with capture:
        for i in range(args.continuous):
            prompt = jax.random.randint(jax.random.fold_in(rng, i),
                                        (args.prompt_len,), 0,
                                        cfg.vocab_size)
            rids.append(server.submit(prompt.tolist(), RequestParams(
                max_new_tokens=args.steps + 1)))
            for _ in range(args.arrival_every):  # staggered arrivals
                server.step()
                occ.append(server.pool.occupancy())
                tick()
        while server.has_work:
            server.step()
            occ.append(server.pool.occupancy())
            tick()
    dt = sw.elapsed()
    if args.xprof_out:
        print(f"wrote xprof capture under {args.xprof_out} (open in "
              f"TensorBoard / XProf)")
    toks = sum(len(server.output(r)) for r in rids)
    s = server.stats()
    print(f"continuous: {len(rids)} requests, {toks} tokens in {dt:.2f}s "
          f"-> {toks / dt:.1f} tok/s")
    print(f"pool: {server.pool.n_pages} pages x "
          f"{server.pool.page_nbytes():,} B, peak occupancy "
          f"{max(occ):.2f}, mean {sum(occ) / len(occ):.2f}")
    print(f"decode compilations: {s['decode_compilations']} "
          f"(1 == no per-step retrace)")
    if args.spec_plan is not None:
        sp = server.engine.spec_stats()
        print(f"speculative: acceptance {sp['acceptance_rate']:.3f}, "
              f"verifier steps/token {sp['verify_steps_per_token']:.3f} "
              f"(< 1.0 == decode speedup), rejected "
              f"{server.scheduler.stats()['rejected_tokens']} drafts")
    if obs is not None and (args.numerics or args.calibration_out):
        _report_residuals(obs, cfg, server.engine, server.pool, args)
    if profiler is not None:
        probes = obs.metrics.counter("profile_probes_total").value
        print(f"profile: {probes} phase probes "
              f"(every {args.profile_every} steps)")
        _report_utilization(obs, cfg, server.engine, server.pool, args)
    if quality is not None:
        probes = obs.metrics.counter("quality_shadow_probes_total").value
        agree = obs.metrics.gauge("quality_shadow_top1_agree").value
        print(f"quality: {probes} shadow probes, top-1 agreement "
              f"{agree:.3f}")
    if tracker is not None:
        _report_slo(tracker, health, args)
    _save_obs(obs, args)
    _finish_extras(flight, msrv, args)
    print("sample:", server.output(rids[0])[:16])
    return {"server": server, "rids": rids, "warmup_s": warmup_s,
            "serve_s": dt, "tokens": toks}


def _fleet(args):
    """Multi-tenant fleet from a manifest: route, drain, report."""
    import json

    from repro.fleet import FleetAdmissionError, build_fleet, load_manifest

    manifest = load_manifest(args.fleet)
    cfg = configs.smoke(manifest.arch)
    params = transformer.init_params(cfg, jax.random.key(0))
    router = build_fleet(manifest, cfg, params, budget_mb=args.budget_mb,
                         fused_attention=args.fused_attention)
    print(router.registry.describe())

    rng = jax.random.key(3)
    tenants = [t.tenant_id for t in router.registry]
    for i, tid in enumerate(tenants):          # warm both jits off the clock
        warm = jax.random.randint(jax.random.fold_in(rng, 1000 + i),
                                  (args.prompt_len,), 0, cfg.vocab_size)
        router.submit(tid, warm.tolist(), max_new_tokens=2)
    router.drain(max_steps=10_000)
    obs = _make_obs(args)
    flight = msrv = tracker = health = None
    if obs is not None:                        # attach after warmup so jit
        router.obs = obs                       # compiles stay off the books
    router.reset_telemetry()                   # drop warmup counters; re-wire
    if obs is not None:
        flight, msrv = _attach_extras(obs, args)
        spec = _load_slo_spec(args, manifest)
        if args.slo_report and spec is None:
            raise SystemExit("--slo-report needs --slo or a manifest "
                             "'slo:' section")
        if spec is not None:
            from repro.obs.health import attach_fleet_health
            from repro.obs.slo import SLOTracker
            tracker = SLOTracker(spec, obs, telemetry=router.telemetry)
            router.telemetry.slo = tracker
            health = attach_fleet_health(router, slo=tracker)
            if msrv is not None:
                msrv.attach_slo(tracker)
        if args.profile:
            from repro.obs.profile import attach_fleet_profilers
            attach_fleet_profilers(router, cfg,
                                   every_n_steps=args.profile_every)
        if args.numerics:
            from repro.obs.numerics import (NumericsConfig,
                                            attach_fleet_quality)
            attach_fleet_quality(router, params, ncfg=NumericsConfig(
                every_n_steps=args.numerics_every))

    def tick():                 # one judgment poll per decode step
        if tracker is not None:
            tracker.on_step()
            health.on_step()

    sw = Stopwatch()
    for i in range(args.fleet_requests):
        for j, tid in enumerate(tenants):
            prompt = jax.random.randint(jax.random.fold_in(rng, i * 64 + j),
                                        (args.prompt_len,), 0,
                                        cfg.vocab_size)
            try:
                router.submit(tid, prompt.tolist(),
                              max_new_tokens=args.steps + 1)
            except FleetAdmissionError as e:     # quota full: shed + go on
                print(f"[fleet] rejected: {e}")
            for _ in range(args.arrival_every):  # staggered arrivals
                router.step()
                tick()
    steps = 0
    while router.has_work:                     # drain, polling per step
        router.step()
        tick()
        steps += 1
        if steps > 100_000:
            raise RuntimeError("fleet drain exceeded max_steps")
    dt = sw.elapsed()

    stats = router.stats()
    toks = stats["aggregate"]["tokens"]
    print(f"fleet: {len(tenants)} tenants x {args.fleet_requests} requests, "
          f"{toks} tokens in {dt:.2f}s -> {toks / dt:.1f} tok/s aggregate")
    print(json.dumps(stats, indent=1))
    if args.stats_out:
        with open(args.stats_out, "w") as f:
            json.dump(stats, f, indent=1)
        print(f"wrote {args.stats_out}")
    if obs is not None and args.numerics:
        from repro.obs.residuals import record_residuals
        for t in router.registry:              # per-tenant residual gauges
            res = record_residuals(obs, cfg, t.engine, t.pool,
                                   labels={"tenant": t.tenant_id})
            row = res["weight_bytes"]
            print(f"costmodel residual [{t.tenant_id}] weight_bytes: "
                  f"ratio {row['ratio']:.3f}")
    if obs is not None and args.profile:
        probes = obs.metrics.counter("profile_probes_total").value
        print(f"profile: {probes} phase probes across "
              f"{len(tenants)} tenants")
        for t in router.registry:              # per-tenant MFU / HBM gauges
            _report_utilization(obs, cfg, t.engine, t.pool, args,
                                labels={"tenant": t.tenant_id})
    if tracker is not None:
        _report_slo(tracker, health, args)
    _save_obs(obs, args)
    _finish_extras(flight, msrv, args)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(configs.names()),
                    help="required unless --fleet supplies the arch")
    ap.add_argument("--full", action="store_true",
                    help="the published config (configs.get) instead of "
                         "the reduced smoke config (not with --fleet); "
                         "with --scheme the weights are packed one layer "
                         "at a time")
    ap.add_argument("--scheme", default=None, help="weight scheme, e.g. lq4w")
    ap.add_argument("--plan", default=None, metavar="PLAN.json",
                    help="mixed-precision QuantPlan (repro.launch.plan "
                         "output); mutually exclusive with --scheme")
    ap.add_argument("--a-bits", type=int, default=None)
    ap.add_argument("--kv-bits", type=int, default=None)
    ap.add_argument("--kv-group", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--continuous", type=int, default=0, metavar="N",
                    help="serve N staggered requests via the paged "
                         "continuous-batching layer")
    ap.add_argument("--arrival-every", type=int, default=2,
                    help="decode steps between request arrivals")
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--n-pages", type=int, default=128)
    ap.add_argument("--fused-attention", action="store_true",
                    help="paged decode through the fused flash-decode "
                         "kernel (kernels/paged_attention.py): wire pages "
                         "stream through VMEM and dequantize in-register "
                         "(LUT path at kv bits <= 4) instead of gather -> "
                         "fp pool view -> attend; compiled on TPU (a "
                         "kernel failure there is an error), "
                         "interpret-mode elsewhere; --continuous and "
                         "--fleet")
    ap.add_argument("--spec-plan", default=None, metavar="DRAFT.json",
                    help="speculative decoding (with --continuous): a "
                         "low-bit draft QuantPlan of the same checkpoint "
                         "proposes tokens the main engine verifies")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per verify cycle")
    ap.add_argument("--fleet", default=None, metavar="FLEET.json",
                    help="multi-tenant manifest (repro.fleet); per-plan "
                         "engines behind one host budget")
    ap.add_argument("--budget-mb", type=float, default=None,
                    help="shared host byte budget for --fleet (overrides "
                         "the manifest's budget_mb)")
    ap.add_argument("--fleet-requests", type=int, default=4,
                    help="requests submitted per tenant in --fleet mode")
    ap.add_argument("--stats-out", default=None,
                    help="write the fleet stats snapshot to this JSON file")
    ap.add_argument("--trace-out", default=None, metavar="TRACE.json",
                    help="write a Chrome/Perfetto trace of the run "
                         "(--continuous / --fleet); view at ui.perfetto.dev")
    ap.add_argument("--metrics-out", default=None, metavar="METRICS.json",
                    help="write the metrics snapshot (TTFT/ITL/queue-wait "
                         "histograms, counters); a .prom suffix selects "
                         "Prometheus text format")
    ap.add_argument("--numerics", action="store_true",
                    help="online quality probes: shadow-divergence KL / "
                         "top-1 agreement, per-layer KV dequant error, "
                         "weight wire error, spec-acceptance drift, plus "
                         "cost-model residuals at exit")
    ap.add_argument("--numerics-every", type=int, default=4, metavar="N",
                    help="decode steps between shadow probes (--numerics)")
    ap.add_argument("--serve-metrics", type=int, default=None,
                    metavar="PORT",
                    help="serve live /metrics (Prometheus text), /healthz "
                         "and /snapshot.json on 127.0.0.1:PORT during the "
                         "run (0 = ephemeral port)")
    ap.add_argument("--flight-out", default=None, metavar="FLIGHT.json",
                    help="arm the flight recorder: ring of recent "
                         "spans/events, auto-dumped on anomalies "
                         "(preemption storm / pool alloc failure / drift "
                         "alarm / SLO breach) and saved here at exit")
    ap.add_argument("--slo", default=None, metavar="SLO.json",
                    help="judge the run against an SLOSpec (repro.obs.slo):"
                         " per-tenant TTFT/ITL p95, tok/s, availability "
                         "and acceptance targets through error budgets + "
                         "multi-window burn rates; breaches fire slo_breach"
                         " events (a flight-recorder dump trigger) and "
                         "per-tenant health gauges track silent "
                         "degradation; --fleet manifests may carry an "
                         "'slo:' section instead")
    ap.add_argument("--slo-report", default=None, metavar="OUT.json",
                    help="write the final SLO report (budgets, burn rates, "
                         "breach episodes, health) for the python -m "
                         "repro.obs.slo gate")
    ap.add_argument("--profile", action="store_true",
                    help="perf-attribution plane: sampled per-phase "
                         "decode-step breakdown (serve_phase_ms{phase,"
                         "layer_run} histograms) plus MFU / HBM-"
                         "utilization gauges against the calibrated "
                         "roofline at exit; host-side only — tokens and "
                         "compile counts are unchanged")
    ap.add_argument("--profile-every", type=int, default=4, metavar="N",
                    help="decode steps between phase probes (--profile)")
    ap.add_argument("--xprof-out", default=None, metavar="DIR",
                    help="capture a programmatic jax.profiler trace of "
                         "the serve loop under DIR (TensorBoard/XProf); "
                         "--continuous only")
    ap.add_argument("--calibration-out", default=None, metavar="CALIB.json",
                    help="persist the measured/predicted decode-ms "
                         "correction factor for repro.launch.plan "
                         "--calibration")
    return ap


def build(args):
    """The single-engine setup: ``(cfg, params, ecfg)`` for ``args``."""
    cfg = configs.get(args.arch) if args.full else configs.smoke(args.arch)
    qcfg = schemes.get(args.scheme) if args.full and args.scheme else None
    params = transformer.init_params(cfg, jax.random.key(0), qcfg=qcfg)
    plan = None
    if args.plan is not None:
        from repro.plan import QuantPlan
        plan = QuantPlan.load(args.plan)
        print(plan.describe(cfg))
    ecfg = EngineConfig(max_len=args.prompt_len + args.steps + 8,
                        kv_bits=args.kv_bits, kv_group=args.kv_group,
                        weight_scheme=args.scheme, a_bits=args.a_bits,
                        plan=plan, temperature=args.temperature,
                        fused_attention=args.fused_attention)
    return cfg, params, ecfg


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.full and args.fleet is not None:
        ap.error("--full serves one published config; --fleet runs the "
                 "reduced configs")
    enable_compile_cache()

    obs_flags = (args.trace_out or args.metrics_out or args.numerics
                 or args.flight_out or args.calibration_out or args.profile
                 or args.slo or args.slo_report
                 or args.serve_metrics is not None)
    if obs_flags and not (args.continuous or args.fleet):
        ap.error("--trace-out/--metrics-out/--numerics/--serve-metrics/"
                 "--flight-out/--calibration-out/--profile/--slo/"
                 "--slo-report instrument the serve layer; use them with "
                 "--continuous or --fleet")
    if args.xprof_out and not args.continuous:
        ap.error("--xprof-out captures the --continuous serve loop")
    if args.calibration_out and args.fleet:
        ap.error("--calibration-out fits one engine's roofline correction; "
                 "use it with --continuous (fleet runs report per-tenant "
                 "residual gauges instead)")

    if args.spec_plan is not None and (args.fleet is not None
                                       or not args.continuous):
        ap.error("--spec-plan needs --continuous (speculation runs on the "
                 "paged serve layer; per-tenant speculative fleets are not "
                 "wired yet)")
    if args.fleet is not None:
        _fleet(args)
        return
    if args.arch is None:
        ap.error("--arch is required without --fleet")
    if args.fused_attention and not args.continuous:
        ap.error("--fused-attention fuses the *paged* decode path; use it "
                 "with --continuous or --fleet")
    if args.full and args.scheme and args.numerics:
        ap.error("--numerics compares against fp32 masters, which --full "
                 "--scheme does not keep")

    cfg, params, ecfg = build(args)
    if args.continuous:
        print(f"arch={args.arch} ({cfg.n_layers}L d_model={cfg.d_model}) "
              f"scheme={args.scheme} plan={args.plan} "
              f"a_bits={args.a_bits} kv_bits={args.kv_bits}")
        run_continuous(cfg, params, ecfg, args)
        return
    engine = Engine(cfg, params, ecfg)

    key = jax.random.key(1)
    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size, jnp.int32)}
    if cfg.frontend == "audio_stub":
        batch["frames"] = jax.random.normal(
            key, (args.batch, cfg.enc_len, cfg.frontend_dim))
    elif cfg.frontend == "patch_stub":
        batch["patches"] = jax.random.normal(
            key, (args.batch, cfg.n_patches, cfg.frontend_dim))

    out, _ = engine.generate(batch, steps=args.steps)          # warm up
    jax.block_until_ready(out)
    sw = Stopwatch()
    out, _ = engine.generate(batch, steps=args.steps)
    jax.block_until_ready(out)
    dt = sw.elapsed()
    toks = args.batch * (args.steps + 1)
    print(f"arch={args.arch} scheme={args.scheme} a_bits={args.a_bits} "
          f"kv_bits={args.kv_bits}")
    print(f"generated {toks} tokens in {dt:.2f}s -> {toks / dt:.1f} tok/s")
    print(f"decode-cache bytes: {engine.cache_bytes(args.batch):,}")
    print("sample:", out[0, :16].tolist())


if __name__ == "__main__":
    main()
