"""Online numerics probes: quantization error + divergence, measured live.

The paper's headline accuracy claim — low-bit local-quantization regions
retain model quality — is verified here *while traffic is flowing*, not
just in offline evals.  Four probes, all host-side (none ever enters the
engine's compiled decode step, so ``decode_compilations`` stays 1 and
token streams are bit-identical with probes on):

* **weight wire-error** (:func:`record_weight_wire_error`) — at quantize
  time, per decoder layer: MSE / max-abs of ``dequant(quant(w)) - w``
  over exactly the leaves ``transformer.quantize_params`` packs, under
  the layer's planned scheme.  Gauges ``quant_weight_{mse,maxabs}{layer=}``.
* **shadow divergence** (:class:`QualityMonitor`) — every
  ``every_n_steps`` decode steps, one sampled slot's context is replayed
  through (a) the fp reference and (b) the engine's quantized
  weights+policy in two standalone jits; the probe records the logit
  KL(fp‖quant) histogram ``quality_shadow_kl`` and whether the fp
  model's top-1 token agrees with the token the quantized *serving* path
  actually emitted (gauge ``quality_shadow_top1_agree``).
* **KV dequant error** — the same probe gathers the slot's pool pages
  per layer, dequantizes them at that layer's wire format, and compares
  against the fp replay's cache: the *accumulated* cache wire error a
  decode step actually reads (gauges ``kv_dequant_{mse,maxabs}{layer=}``
  — the measurement half of the ROADMAP's decode-time KV sensitivity).
* **spec-acceptance drift** (:class:`AcceptanceDrift`) — EWMA of the
  speculative acceptance rate vs a calibration baseline; crossing the
  threshold emits a ``drift_alarm`` event (a flight-recorder trigger)
  and bumps ``spec_drift_alarms_total``.

Probe cost is bounded by the sampling knobs on :class:`NumericsConfig`:
each shadow probe is two extra prefill-sized forwards (compiled once —
the replay jits are separate functions and never touch the engine's),
and the KV comparison is O(context · layers) host flops.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kvwire, schemes
from repro.kernels import ops as kops
from repro.models import transformer
from repro.models.layers import NO_QUANT

# KL of a shadow replay is tiny when quantization is faithful — the
# serving-latency bucket ladder would dump everything into the first
# bucket.  1-2-5 ladder over 1e-9 .. 500 nats instead.
KL_BUCKETS = tuple(c * 10.0 ** e for e in range(-9, 3) for c in (1, 2, 5))


@dataclasses.dataclass(frozen=True)
class NumericsConfig:
    """Sampling knobs of the online quality probes."""
    every_n_steps: int = 8          # shadow-replay every N decode steps
    kv_probe: bool = True           # per-layer KV dequant error per probe
    drift_alpha: float = 0.2        # acceptance EWMA smoothing
    drift_threshold: float = 0.15   # |ewma - baseline| alarm threshold
    drift_min_cycles: int = 8       # cycles before baseline/alarms engage
    drift_baseline: float | None = None   # None = auto-calibrate


# ---------------------------------------------------------------------------
# layer walkers (shared by the KV probe and the weight wire-error pass)
# ---------------------------------------------------------------------------

def layer_blocks(tree, cfg):
    """Yield ``(layer_idx, block)`` over a cache/pool/params decoder tree,
    in layer order: each of ``transformer.stack_runs(tree)`` with the
    stack dim sliced away (``superblock * p_len + position``), then the
    tail.
    """
    p_len = len(cfg.pattern)
    start = 0
    for run in transformer.stack_runs(tree):
        size = jax.tree.leaves(run)[0].shape[0]
        for s in range(size):
            for j, block in enumerate(run):
                yield ((start + s) * p_len + j,
                       jax.tree.map(lambda a, s=s: a[s], block))
        start += size
    for t, block in enumerate(tree["tail"]):
        yield (start * p_len + t, block)


def _layer_label(i: int) -> str:
    return f"layer{i}"


# ---------------------------------------------------------------------------
# weight wire-error (recorded at quantize time)
# ---------------------------------------------------------------------------

def _wire_error_tree(block, qcfg) -> dict:
    """MSE / max-abs of the wire round-trip over exactly the leaves
    ``transformer._quantize_tree`` would pack under ``qcfg``."""
    if qcfg.w_bits is None:
        return {"mse": 0.0, "maxabs": 0.0, "n_weights": 0}
    bits, gs = qcfg.w_bits, qcfg.group_size
    sq, n, mx = 0.0, 0, 0.0

    def roundtrip(w):
        nonlocal sq, n, mx
        flat = np.asarray(w, np.float32).reshape((-1,) + w.shape[-2:])
        for w2 in flat:                       # MoE expert stacks: per expert
            qw = kops.quantize_weight(jnp.asarray(w2), bits, gs)
            err = (np.asarray(kops.dequantize_weight(qw), np.float64)
                   - w2.astype(np.float64))
            sq += float(np.sum(err * err))
            n += err.size
            mx = max(mx, float(np.max(np.abs(err))))

    def visit(t):
        if isinstance(t, dict):
            for k, v in t.items():
                if k in transformer._EXCLUDE_KEYS:
                    continue
                if k == "w" and hasattr(v, "ndim") and v.ndim >= 2 \
                        and v.shape[-2] % gs == 0:
                    roundtrip(v)
                elif k in ("wi_gate", "wi_up", "wo") \
                        and hasattr(v, "ndim") and not isinstance(v, dict) \
                        and v.ndim >= 3 and v.shape[-2] % gs == 0:
                    roundtrip(v)
                else:
                    visit(v)
        elif isinstance(t, (list, tuple)):
            for v in t:
                visit(v)

    visit(block)
    return {"mse": sq / n if n else 0.0, "maxabs": mx, "n_weights": n}


def record_weight_wire_error(obs, cfg, fp_params, qcfg_or_plan) -> dict:
    """Per-layer wire error of quantizing ``fp_params`` under a scheme
    name / :class:`~repro.core.schemes.QuantConfig` / QuantPlan.

    Records gauges ``quant_weight_mse{layer=...}`` and
    ``quant_weight_maxabs{layer=...}``; returns ``{layer_label: stats}``.
    Runs on the fp checkpoint, so call it where the engine quantizes —
    it is pure measurement and leaves ``fp_params`` untouched.
    """
    if hasattr(qcfg_or_plan, "resolve"):              # QuantPlan
        configs = qcfg_or_plan.resolve(cfg)
    else:
        qcfg = (schemes.get(qcfg_or_plan)
                if not isinstance(qcfg_or_plan, schemes.QuantConfig)
                else qcfg_or_plan)
        configs = (qcfg,) * cfg.n_layers
    out = {}
    for i, block in layer_blocks(fp_params["decoder"], cfg):
        stats = _wire_error_tree(block, configs[i])
        label = _layer_label(i)
        out[label] = stats
        if obs is not None and obs.enabled:
            obs.metrics.gauge("quant_weight_mse", layer=label).set(
                stats["mse"])
            obs.metrics.gauge("quant_weight_maxabs", layer=label).set(
                stats["maxabs"])
    return out


# ---------------------------------------------------------------------------
# spec-acceptance drift
# ---------------------------------------------------------------------------

class AcceptanceDrift:
    """EWMA drift detector over the speculative acceptance rate.

    Feed per-cycle acceptance rates via :meth:`update`; after
    ``min_cycles`` the baseline locks (to the given calibration value, or
    auto-calibrates to the first settled EWMA) and an excursion of more
    than ``threshold`` from it fires — once per breach episode (the alarm
    latches until the EWMA recovers, so a sustained regression does not
    spam one alarm per step).
    """

    def __init__(self, *, alpha: float = 0.2, threshold: float = 0.15,
                 min_cycles: int = 8, baseline: float | None = None):
        if not 0.0 < alpha <= 1.0:
            raise ValueError(f"alpha must be in (0, 1], got {alpha}")
        self.alpha, self.threshold = alpha, threshold
        self.min_cycles, self.baseline = min_cycles, baseline
        self.ewma: float | None = None
        self.cycles = 0
        self.alarmed = False          # currently in a breach episode

    def update(self, rate: float) -> bool:
        """Observe one cycle's acceptance rate; True == alarm fires now."""
        rate = float(rate)
        self.cycles += 1
        self.ewma = (rate if self.ewma is None else
                     self.alpha * rate + (1.0 - self.alpha) * self.ewma)
        if self.cycles < self.min_cycles:
            return False
        if self.baseline is None:
            self.baseline = self.ewma     # calibration window just closed
            return False
        breach = abs(self.ewma - self.baseline) > self.threshold
        fired = breach and not self.alarmed
        self.alarmed = breach
        return fired


# ---------------------------------------------------------------------------
# shadow-divergence + KV dequant monitor
# ---------------------------------------------------------------------------

def _log_softmax(x: np.ndarray) -> np.ndarray:
    x = x - x.max()
    return x - np.log(np.exp(x).sum())


class QualityMonitor:
    """Sampled online divergence probes over one scheduler's traffic.

    Attach via ``Server.attach_quality`` (or ``scheduler.quality = m``);
    the scheduler calls :meth:`on_step` after each decode step.  Works
    with plain and speculative engines — a :class:`SpeculativeEngine`'s
    replays run through its verifier, and its ``drafted``/``accepted``
    counters feed the drift detector.
    """

    def __init__(self, obs, cfg, fp_params, engine, *,
                 ncfg: NumericsConfig | None = None):
        self.obs = obs
        self.cfg = cfg
        self.fp_params = fp_params
        self.engine = engine                      # drift counters live here
        # the paged engine whose params/policy/kv-layout the replays mirror
        self.core = getattr(engine, "verifier", engine)
        self.ncfg = ncfg or NumericsConfig()
        self.steps = 0
        self._probe_cursor = 0
        self._last_drafted = 0
        self._last_accepted = 0
        self.drift = AcceptanceDrift(
            alpha=self.ncfg.drift_alpha, threshold=self.ncfg.drift_threshold,
            min_cycles=self.ncfg.drift_min_cycles,
            baseline=self.ncfg.drift_baseline)

        cfg_, core = cfg, self.core
        bucket = core.pcfg.max_context
        kvq = core._kv_quant()

        # standalone replay jits: compiled once each (fixed bucket shape,
        # traced logits_pos), never shared with the engine's functions —
        # enabling probes cannot retrace the serving path.
        def fp_replay(params, tokens, logits_pos):
            cache = transformer.init_cache(cfg_, 1, bucket, kv_quant=None)
            return transformer.prefill(params, cfg_, {"tokens": tokens},
                                       cache, policy=NO_QUANT,
                                       logits_pos=logits_pos)

        def q_replay(params, tokens, logits_pos):
            # mirrors PagedEngine._prefill_paged_impl: same params, same
            # policy, same cache wire layout as the serving engine
            cache = transformer.init_cache(cfg_, 1, bucket, kv_quant=kvq)
            logits, _ = transformer.prefill(params, cfg_, {"tokens": tokens},
                                            cache, policy=core.policy,
                                            logits_pos=logits_pos)
            return logits

        self._fp_replay = jax.jit(fp_replay)
        self._q_replay = jax.jit(q_replay)

    # -------------------------------------------------------------- hook
    def on_step(self, sched):
        """Scheduler tap: runs after each decode step (host-side only)."""
        self.steps += 1
        self._check_drift()
        every = self.ncfg.every_n_steps
        if every <= 0 or self.steps % every:
            return None
        slot_req = self._pick_slot(sched)
        if slot_req is None:
            return None
        return self.probe(sched, *slot_req)

    def _pick_slot(self, sched):
        """Round-robin over slots that have emitted at least one token."""
        live = [(i, r) for i, r in enumerate(sched._slots)
                if r is not None and r.generated]
        if not live:
            return None
        self._probe_cursor += 1
        return live[self._probe_cursor % len(live)]

    # ------------------------------------------------------------- drift
    def _check_drift(self):
        drafted = getattr(self.engine, "drafted", None)
        if drafted is None:
            return                          # plain engine: nothing drafted
        accepted = self.engine.accepted
        dd = drafted - self._last_drafted
        da = accepted - self._last_accepted
        self._last_drafted, self._last_accepted = drafted, accepted
        if dd <= 0:
            return
        fired = self.drift.update(da / dd)
        m = self.obs.metrics
        m.gauge("spec_acceptance_ewma").set(self.drift.ewma)
        if self.drift.baseline is not None:
            m.gauge("spec_acceptance_baseline").set(self.drift.baseline)
        if fired:
            m.counter("spec_drift_alarms_total").inc()
            self.obs.event("drift_alarm",
                           ewma=round(self.drift.ewma, 4),
                           baseline=round(self.drift.baseline, 4),
                           threshold=self.ncfg.drift_threshold)

    # ------------------------------------------------------------- probe
    def probe(self, sched, slot: int, req) -> dict | None:
        """Shadow-replay ``req``'s context; record KL / agreement / KV
        error.  The context is ``prompt + generated[:-1]`` — exactly the
        tokens whose K/V rows the pool holds for this slot (the last
        generated token is the *input* to the next step, not yet cached).
        """
        context = req.prompt + req.generated[:-1]
        c = len(context)
        bucket = self.core.pcfg.max_context
        if not 0 < c <= bucket:
            return None
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :c] = context
        toks = jnp.asarray(padded)
        pos = jnp.asarray(c - 1, jnp.int32)
        fp_logits, fp_cache = self._fp_replay(self.fp_params, toks, pos)
        q_logits = self._q_replay(self.core.params, toks, pos)

        lp_fp = _log_softmax(np.asarray(fp_logits[0, 0], np.float64))
        lp_q = _log_softmax(np.asarray(q_logits[0, 0], np.float64))
        kl = float(np.sum(np.exp(lp_fp) * (lp_fp - lp_q)))
        kl = max(kl, 0.0)                    # guard fp rounding at ~0
        agree = int(np.argmax(lp_fp)) == int(req.generated[-1])

        m = self.obs.metrics
        m.histogram("quality_shadow_kl", buckets=KL_BUCKETS).record(kl)
        probes = m.counter("quality_shadow_probes_total")
        agrees = m.counter("quality_shadow_agree_total")
        probes.inc()
        if agree:
            agrees.inc()
        if probes.value:
            m.gauge("quality_shadow_top1_agree").set(
                agrees.value / probes.value)
        self.obs.event("shadow_probe", rid=req.rid, context=c,
                       kl=round(kl, 9), agree=bool(agree))
        kv = (self._kv_probe(sched.pool, req.rid, c, fp_cache)
              if self.ncfg.kv_probe else None)
        return {"kl": kl, "agree": agree, "context": c, "kv": kv}

    def _kv_probe(self, pool, rid: int, c: int, fp_cache) -> dict:
        """Per-layer accumulated cache wire error: gather the slot's pool
        pages, dequantize at each layer's own format, compare rows
        ``0..c-1`` against the fp replay's cache."""
        table = jnp.asarray(
            pool.table_array(rid, self.core.pcfg.pages_per_slot)[None])
        page_size = pool.page_size
        d = self.cfg.head_dim
        ref = dict(layer_blocks(fp_cache, self.cfg))
        m = self.obs.metrics
        out = {}
        for i, block in layer_blocks(pool.pages, self.cfg):
            errs = []
            for key in ("k", "v"):
                got = kvwire.gather_pages(block["self"][key], table,
                                          page_size, self.cfg.n_kv_heads)
                if kvwire.is_quant_kv(got):
                    got = kvwire.dequantize_kv(got, d)
                got = np.asarray(got[0, :c], np.float64)
                want = np.asarray(ref[i]["self"][key][0, :c], np.float64)
                errs.append((got - want).ravel())
            err = np.concatenate(errs)
            label = _layer_label(i)
            stats = (float(np.mean(err * err)),
                     float(np.max(np.abs(err))) if err.size else 0.0)
            m.gauge("kv_dequant_mse", layer=label).set(stats[0])
            m.gauge("kv_dequant_maxabs", layer=label).set(stats[1])
            # the deployed wire width alongside the measured error, so a
            # metrics snapshot alone is enough for launch/plan.py
            # --kv-sensitivity-from to map error -> format (0 = fp wire)
            kq = block["self"]["k"]
            spec = kvwire.page_spec(kq, d, page_size * self.cfg.n_kv_heads)
            bits = spec[0] if spec else 0
            m.gauge("kv_dequant_bits", layer=label).set(float(bits))
            out[label] = stats
        return out


def attach_fleet_quality(router, fp_params, *,
                         ncfg: NumericsConfig | None = None) -> dict:
    """One :class:`QualityMonitor` per fleet tenant, attached to each
    tenant's scheduler (each monitor replays through that tenant's own
    engine/plan).  Returns ``{tenant_id: monitor}``."""
    monitors = {}
    for t in router.registry:
        mon = QualityMonitor(t.scheduler.obs, router.registry.model_cfg,
                             fp_params, t.engine, ncfg=ncfg)
        t.scheduler.quality = mon
        monitors[t.tenant_id] = mon
    return monitors
