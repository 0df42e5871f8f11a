"""Perf-attribution plane: where a decode step's device time actually goes.

The latency plane (PR 6) says *how long* a decode step takes; the cost
model (plan/costmodel.py) says how long it *should* take.  This module
closes the gap with three instruments, all host-side and NOOP-default —
nothing here ever enters the engine's compiled functions, so
``decode_compilations`` stays 1 and token streams are bit-identical with
profiling on:

* **annotations** — every ``repro.obs`` span is a profiler TraceMe
  (obs/trace.py), so step / admit / prefill / decode / fetch / emit /
  draft / verify host spans show in xprof captures, and
  ``jax.named_scope`` markers inside the model code (transformer.py,
  attention.py) label the HLO ops per layer kind / walker segment.  Both
  are metadata-only: numerics and trace caches are untouched.
* **phase profiler** — :class:`PhaseProfiler`, a scheduler tap (attach
  via ``Server.attach_profiler``).  Every ``every_n_steps`` decode steps
  it replays the step's sub-phases against the engine's *live* pool state
  in standalone jits (compiled once each, never shared with the engine's):
  page ``gather``, wire ``dequant``, ``attention`` over the gathered
  cache, and the ``lm_head`` (final norm + logits), each
  ``block_until_ready``-bounded, plus one full decode-step replay through
  the engine's own already-compiled jit (same shapes — no new trace).
  A fused engine (``EngineConfig.fused_attention``) replays ONE
  ``fused_attention`` phase per stack run instead of the gather/dequant/
  attention triplet — the decomposition no longer exists on device, and
  pretending it does would mis-attribute the step.
  Histograms ``serve_phase_ms{phase=...,layer_run=...}`` per stack run
  (``run0``/``run1``/.../``tail0``; ``all`` for stack-wide phases), with
  the unattributed remainder ``phase="other"`` defined as
  ``max(0, step_replay - sum(measured phases))`` so the phases sum to at
  least the replayed step by construction.
* **utilization gauges** — :func:`record_utilization` divides the cost
  model's per-step FLOPs and wire bytes by the measured
  ``serve_decode_step_ms`` p50: gauges ``serve_mfu`` and
  ``serve_hbm_util``.  Pass ``hw=repro.obs.calibrated_hw(...)`` to
  normalize against the measured host roof instead of the stock
  roofline (both gauges are clamped to (0, 1] — calibration folds batch
  efficiency into the roof, so the clamp guards the gauge contract).

``python -m repro.launch.serve --profile [--profile-every N]`` wires the
profiler + gauges into a serve run; ``--xprof-out DIR`` additionally
captures a programmatic ``jax.profiler`` trace (:func:`xprof_capture`)
viewable in TensorBoard/XProf.  ``python -m repro.obs.check trace.json
metrics.json --profile`` validates the artifacts.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kvwire
from repro.kernels import paged_attention as paged_attn
from repro.models import attention, transformer
from repro.obs.metrics import Stopwatch

# the two decompositions a decode step can attribute to: the XLA path
# splits into gather/dequant/attention; a fused engine
# (EngineConfig.fused_attention) runs all three as ONE kernel, so its
# honest attribution is a single fused_attention phase per stack run
PHASES = ("gather", "dequant", "attention", "lm_head", "other")
FUSED_PHASES = ("fused_attention", "lm_head", "other")


@contextlib.contextmanager
def xprof_capture(out_dir: str):
    """Programmatic ``jax.profiler`` capture around a block.

    Writes a TensorBoard/XProf trace under ``out_dir`` (the
    ``--xprof-out`` flag of ``repro.launch.serve``).  A profiler that
    fails to start or stop is an error: a run asked to trace must not
    exit cleanly without its trace.
    """
    jax.profiler.start_trace(out_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# sampled phase profiler (scheduler tap)
# ---------------------------------------------------------------------------

def _pool_runs(pages) -> list:
    """``[(layer_run, block_tuple, stacked)]`` over a pool's stack runs.

    One entry per run of the pool (``transformer.stack_runs``: one per kv
    wire format) plus one per tail block — the granularity the stack
    walker scans at, so phase times attribute to the units that can
    actually be optimized separately.
    """
    runs = [(f"run{r}", run, True)
            for r, run in enumerate(transformer.stack_runs(pages))]
    runs += [(f"tail{t}", (block,), False)
             for t, block in enumerate(pages["tail"])]
    return runs


def _run_kv(block_tuple) -> list:
    """The attention K/V leaves of one run (skips mixers with no cache)."""
    out = []
    for block in block_tuple:
        self_kv = block.get("self") if isinstance(block, dict) else None
        if isinstance(self_kv, dict) and "k" in self_kv and "v" in self_kv:
            out.append({"k": self_kv["k"], "v": self_kv["v"]})
    return out


class PhaseProfiler:
    """Sampled per-phase decode-step attribution over one scheduler.

    Attach via ``Server.attach_profiler`` (or ``scheduler.profiler = p``);
    the scheduler calls :meth:`on_step` after each decode step.  Works
    with plain and speculative engines — a :class:`SpeculativeEngine`
    profiles through its verifier, whose step dominates the cycle.

    Every probe replays the current step's sub-phases against the live
    pool pages / page tables / positions in standalone jits, so the
    recorded milliseconds are the real gather/dequant/attention cost of
    the traffic being served — not a synthetic microbenchmark.  Probe
    keys are self-owned: the scheduler's sampling key stream is never
    advanced, which keeps token streams bit-identical with profiling on.
    """

    def __init__(self, obs, cfg, engine, *, every_n_steps: int = 8):
        self.obs = obs
        self.cfg = cfg
        self.engine = engine
        # the paged engine whose params/policy/pool the replays mirror
        self.core = getattr(engine, "verifier", engine)
        self.every_n_steps = every_n_steps
        self.steps = 0
        self._jits: dict = {}           # layer_run -> (gather, dequant, attend)
        self._lm_head = None
        pcfg = self.core.pcfg
        g = cfg.n_heads // cfg.n_kv_heads
        key = self._key = jax.random.key(0)
        # fixed synthetic query / pre-lm-head activation: phase cost
        # depends on shapes and cache contents, not these values
        self._q = jax.random.normal(
            key, (pcfg.max_slots, 1, cfg.n_kv_heads, g, cfg.head_dim),
            cfg.activation_dtype)
        self._x = jax.random.normal(
            jax.random.fold_in(key, 1), (pcfg.max_slots, 1, cfg.d_model),
            cfg.activation_dtype)

    # -------------------------------------------------------------- hook
    def on_step(self, sched):
        """Scheduler tap: runs after each decode step (host-side only)."""
        self.steps += 1
        every = self.every_n_steps
        if every <= 0 or self.steps % every:
            return None
        if not any(r is not None for r in sched._slots):
            return None
        return self.probe(sched)

    # -------------------------------------------------------------- jits
    def _phase_jits(self, label: str, kvs, stacked: bool):
        """Standalone gather/dequant/attention jits for one stack run,
        compiled once (fixed pool shapes) and never shared with the
        engine's functions — profiling cannot retrace the serving path."""
        if label in self._jits:
            return self._jits[label]
        d = self.cfg.head_dim
        dtype = self.cfg.activation_dtype
        quant = any(kvwire.is_quant_kv(kv["k"]) for kv in kvs)

        def gather(kv_list, table):
            one = functools.partial(kvwire.gather_pages,
                                    page_size=self.core.pcfg.page_size,
                                    kv_heads=self.cfg.n_kv_heads)
            fn = jax.vmap(one, in_axes=(0, None)) if stacked else one
            return [{k: fn(leaf, table) for k, leaf in kv.items()}
                    for kv in kv_list]

        def dequant(gathered):
            return [{k: (kvwire.dequantize_kv(v, d, dtype)
                         if kvwire.is_quant_kv(v) else v)
                     for k, v in kv.items()} for kv in gathered]

        def attend(dq, q, pos):
            attn = attention.decode_attention
            fn = (jax.vmap(lambda k, v: attn(q, k, v, pos))
                  if stacked else (lambda k, v: attn(q, k, v, pos)))
            return [fn(kv["k"], kv["v"]) for kv in dq]

        jits = (jax.jit(gather), jax.jit(dequant) if quant else None,
                jax.jit(attend))
        self._jits[label] = jits
        return jits

    def _fused_jit(self, label: str, stacked: bool):
        """Standalone fused-kernel replay for one stack run — the single
        phase a fused engine's step actually executes per layer."""
        key = ("fused", label)
        if key in self._jits:
            return self._jits[key]
        interpret = self.core.fused_mode == "interpret"
        page_size = self.core.pcfg.page_size

        def fused(kv_list, q, table, pos):
            outs = []
            for kv in kv_list:
                k, v = kv["k"], kv["v"]
                if stacked:
                    lead = (k["packed"] if kvwire.is_quant_kv(k)
                            else k).shape[0]
                    outs.extend(paged_attn.paged_attention(
                        q, k, v, table, pos, page_size=page_size, layer=i,
                        interpret=interpret) for i in range(lead))
                else:
                    outs.append(paged_attn.paged_attention(
                        q, k, v, table, pos, page_size=page_size,
                        interpret=interpret))
            return outs

        jit = jax.jit(fused)
        self._jits[key] = jit
        return jit

    def _lm_head_jit(self):
        if self._lm_head is None:
            cfg, policy = self.cfg, self.core.policy

            def lm_head(params, x):
                x = transformer._norm_apply(cfg, params["final_norm"], x)
                return transformer._logits(params, cfg, x, policy)

            self._lm_head = jax.jit(lm_head)
        return self._lm_head

    def _timed(self, fn, *args) -> tuple:
        sw = Stopwatch(self.obs.clock)
        out = fn(*args)
        jax.block_until_ready(out)
        return out, sw.elapsed_ms()

    # ------------------------------------------------------------- probe
    def probe(self, sched) -> dict:
        """Replay the current step's phases against the live pool state;
        record ``serve_phase_ms{phase,layer_run}`` histograms."""
        pool, pcfg = sched.pool, self.core.pcfg
        table = np.zeros((pcfg.max_slots, pcfg.pages_per_slot), np.int32)
        live = np.zeros((pcfg.max_slots,), bool)
        for i, r in enumerate(sched._slots):
            if r is not None:
                table[i] = pool.table_array(r.rid, pcfg.pages_per_slot)
                live[i] = True
        pos = np.where(live, sched._pos, 0).astype(np.int32)
        tokens = np.where(live, sched._last_tok, 0).astype(np.int32)
        jtable = jnp.asarray(table)
        jpos = jnp.asarray(pos)

        m = self.obs.metrics
        out: dict = {}

        def record(phase: str, layer_run: str, ms: float):
            m.histogram("serve_phase_ms", phase=phase,
                        layer_run=layer_run).record(ms)
            out[(phase, layer_run)] = out.get((phase, layer_run), 0.0) + ms

        with self.obs.tracer.span("profile", step=self.steps,
                                  n_slots=int(live.sum())):
            fused_mode = getattr(self.core, "fused_mode", None)
            for label, blocks, stacked in _pool_runs(pool.pages):
                kvs = _run_kv(blocks)
                if not kvs:
                    continue            # recurrent mixer: no paged cache
                if fused_mode is not None:
                    with self.obs.tracer.span("phase:fused_attention",
                                              layer_run=label):
                        _, ms = self._timed(self._fused_jit(label, stacked),
                                            kvs, self._q, jtable, jpos)
                    record("fused_attention", label, ms)
                    continue
                gather, dequant, attend = self._phase_jits(label, kvs,
                                                           stacked)
                with self.obs.tracer.span("phase:gather", layer_run=label):
                    gathered, ms = self._timed(gather, kvs, jtable)
                record("gather", label, ms)
                if dequant is None:
                    dq, ms = gathered, 0.0    # fp wire: no dequant op at all
                else:
                    with self.obs.tracer.span("phase:dequant",
                                              layer_run=label):
                        dq, ms = self._timed(dequant, gathered)
                record("dequant", label, ms)
                with self.obs.tracer.span("phase:attention",
                                          layer_run=label):
                    _, ms = self._timed(attend, dq, self._q, jpos)
                record("attention", label, ms)
            with self.obs.tracer.span("phase:lm_head", layer_run="all"):
                _, ms = self._timed(self._lm_head_jit(), self.core.params,
                                    self._x)
            record("lm_head", "all", ms)
            # full-step replay through the engine's own compiled jit: same
            # shapes as the serving calls, so no new trace is cut
            # (decode_compilations stays 1) and the probe's own key never
            # advances the scheduler's sampling stream; its inputs are host
            # arrays, as the engine's own calls pass them.  The step takes
            # its pages donated, so it replays on a copy: the live pool is
            # left as it was
            look = getattr(self.engine, "lookahead_tokens", 1)
            pages = jax.block_until_ready(jax.tree.map(jnp.copy, pool.pages))
            with self.obs.tracer.span("phase:step_replay"):
                if look > 1:      # speculative: the verify step is the step
                    run = np.tile(tokens[:, None], (1, look))
                    _, replay_ms = self._timed(
                        self.core._multi_paged, self.core.params,
                        pages, run, table, pos)
                else:
                    _, replay_ms = self._timed(
                        self.core._step_paged, self.core.params, pages,
                        tokens, table, pos,
                        (self._key, np.uint32(self.steps)))
            m.histogram("serve_step_replay_ms").record(replay_ms)
            # the device time the sub-phase replays do not account for
            # (embed, QKV/out/FFN matmuls, scatter, sampling)
            attributed = sum(out.values())
            record("other", "all", max(0.0, replay_ms - attributed))
        m.counter("profile_probes_total").inc()
        out[("step_replay", "all")] = replay_ms
        return {f"{p}/{r}": ms for (p, r), ms in out.items()}


# ---------------------------------------------------------------------------
# roofline-utilization gauges
# ---------------------------------------------------------------------------

def record_utilization(obs, cfg, engine, pool, *, hw=None,
                       labels: dict | None = None) -> dict | None:
    """MFU / HBM-bandwidth-utilization gauges for one serving cell.

    Per-step achieved FLOPs (cost-model MACs x 2 x active slots) and wire
    bytes (every live weight streamed once per step + each slot's cache
    context read back) over the measured ``serve_decode_step_ms`` p50,
    normalized by the roofline constants: gauges ``serve_mfu`` and
    ``serve_hbm_util`` (plus ``labels``, e.g. ``{"tenant": ...}`` in
    fleet mode), both clamped to (0, 1].

    ``hw`` defaults to the stock :class:`repro.roofline.HW`; pass
    ``repro.obs.calibrated_hw(...)`` to measure utilization of the
    *measured* host roof.  Returns the achieved numbers, or ``None``
    before the engine has recorded any decode step.
    """
    from repro.obs.residuals import engine_kv_list, engine_weight_configs
    from repro.plan.costmodel import plan_cost, plan_kv_cost
    from repro.roofline import HW

    labels = labels or {}
    core = getattr(engine, "verifier", engine)    # spec: the verifier's step
    hw = hw or HW()
    hist = obs.metrics.find("serve_decode_step_ms", **core.obs_metric_labels)
    look = 1
    if hist is None or not hist.count:
        # speculative serving records no plain decode-step histogram — the
        # verify step (a length-(k+1) batched forward) is the step there
        hist = obs.metrics.find("serve_verify_ms")
        look = getattr(engine, "lookahead_tokens", 1)
    if hist is None or not hist.count:
        return None
    step_s = hist.percentile(50) / 1e3
    cost = plan_cost(cfg, engine_weight_configs(cfg, core.ecfg))
    kv = plan_kv_cost(cfg, engine_kv_list(cfg, core),
                      kv_group=core._kv_layout[1], tokens=1)
    n_slots = core.pcfg.max_slots
    flops = 2.0 * sum(p["macs"] for p in cost["per_layer"]) * n_slots * look
    bytes_ = (cost["bytes"] + kv["bytes_per_token"]
              * core.pcfg.max_context * n_slots)
    mfu = min(1.0, (flops / step_s) / hw.peak_flops)
    hbm = min(1.0, (bytes_ / step_s) / hw.hbm_bw)
    obs.metrics.gauge("serve_mfu", **labels).set(mfu)
    obs.metrics.gauge("serve_hbm_util", **labels).set(hbm)
    return {"mfu": mfu, "hbm_util": hbm, "flops_per_step": flops,
            "bytes_per_step": bytes_, "step_ms": step_s * 1e3}


def attach_fleet_profilers(router, cfg, *, every_n_steps: int = 8) -> dict:
    """One :class:`PhaseProfiler` per fleet tenant, attached to each
    tenant's scheduler.  Returns ``{tenant_id: profiler}``."""
    out = {}
    for t in router.registry:
        p = PhaseProfiler(t.scheduler.obs, cfg, t.engine,
                          every_n_steps=every_n_steps)
        t.scheduler.profiler = p
        out[t.tenant_id] = p
    return out
