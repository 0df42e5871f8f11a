"""Request-lifecycle tracing + latency metrics for the serving stack.

    obs = Observability()                     # or the global NOOP default
    server = Server(cfg, params, ecfg, pcfg, obs=obs)
    ...serve...
    obs.save_trace("trace.json")              # chrome://tracing / Perfetto
    obs.save_metrics("metrics.json")          # p50/p95/p99 snapshots

See README.md in this directory for the span model, metric names, and
export formats; ``repro.launch.serve --trace-out/--metrics-out`` is the
CLI entry point and ``python -m repro.obs.check`` validates artifacts.
"""
from .export import MetricsServer
from .flight import FlightRecorder
from .metrics import (DEFAULT_CLOCK, DEFAULT_MS_BUCKETS, Counter, Gauge,
                      Histogram, MetricsRegistry, NoopMetrics, NOOP_METRICS,
                      Stopwatch, time_fn)
from .obs import NOOP, Observability
from .trace import NOOP_TRACER, NoopTracer, Tracer

__all__ = [
    "DEFAULT_CLOCK", "DEFAULT_MS_BUCKETS", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "NoopMetrics", "NOOP_METRICS", "Stopwatch",
    "time_fn",
    "NOOP", "Observability",
    "NOOP_TRACER", "NoopTracer", "Tracer",
    "FlightRecorder", "MetricsServer",
    # quality plane (lazy: numerics/residuals pull in jax + the model
    # stack, which the lightweight consumers of this package never need)
    "AcceptanceDrift", "NumericsConfig", "QualityMonitor",
    "attach_fleet_quality", "record_weight_wire_error",
    "engine_weight_configs", "record_residuals", "fit_calibration",
    "save_calibration", "load_calibration", "calibrated_hw",
    "PhaseProfiler", "attach_fleet_profilers",
    "record_utilization", "xprof_capture",
    "SLOSpec", "TenantSLO", "SLOTracker", "good_fraction",
    "validate_report", "HealthMonitor", "attach_fleet_health",
]

_LAZY = {
    "AcceptanceDrift": "numerics", "NumericsConfig": "numerics",
    "QualityMonitor": "numerics", "attach_fleet_quality": "numerics",
    "record_weight_wire_error": "numerics",
    "engine_weight_configs": "residuals", "record_residuals": "residuals",
    "fit_calibration": "residuals", "save_calibration": "residuals",
    "load_calibration": "residuals", "calibrated_hw": "residuals",
    "PhaseProfiler": "profile",
    "attach_fleet_profilers": "profile", "record_utilization": "profile",
    "xprof_capture": "profile",
    "SLOSpec": "slo", "TenantSLO": "slo", "SLOTracker": "slo",
    "good_fraction": "slo", "validate_report": "slo",
    "HealthMonitor": "health", "attach_fleet_health": "health",
}


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib
    return getattr(importlib.import_module(f".{mod}", __name__), name)
