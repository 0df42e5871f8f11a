"""SpeculativeEngine: a drop-in PagedEngine whose low-bit plan drafts.

Two :class:`~repro.plan.QuantPlan` views of ONE base checkpoint serve
together: the draft plan (e.g. uniform 2-bit) proposes ``spec_k`` greedy
tokens per slot on its own shadow pages, the verifier plan (e.g. 8-bit
or fp) scores the whole run in one batched multi-token paged forward and
accepts the longest matching prefix.  Greedy outputs are token-for-token
identical to the verifier-only engine (``tests/test_spec.py``); the
verifier runs ``< 1`` compiled steps per emitted token whenever drafts
are accepted at all.

Packed weight leaves are SHARED between draft and verifier wherever the
two plans agree per layer-segment (one ``leaf_cache`` threads both
``quantize_params`` calls) — the same dedup mechanism
``repro.fleet.FleetRegistry`` uses across tenants.

Scheduler integration is the engine step contract
(``advance_slots`` / ``lookahead_tokens`` / ``prefill_request`` /
``new_pool``), so :class:`~repro.serve.Scheduler`,
:class:`~repro.serve.Server` and the fleet router compose unchanged.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer
from repro.models.config import ModelConfig
from repro.obs import NOOP, Stopwatch
from repro.serve.engine import EngineConfig, PagedConfig, PagedEngine
from repro.serve.pool import PagedKVPool
from repro.spec.draft import draft_proposals
from repro.spec.verify import accept_lengths, emitted_tokens


def shared_segment_keys(cfg: ModelConfig, plan_a, plan_b) -> list:
    """Leaf-cache keys two plans have in common: the packed segments one
    shared base checkpoint materializes once for both."""
    a = set(transformer.plan_leaf_keys(cfg, plan_a))
    return [k for k in transformer.plan_leaf_keys(cfg, plan_b) if k in a]


class PairedKVPool(PagedKVPool):
    """A verifier page pool plus the draft's shadow pages, one allocator.

    Page ids are shared: page ``p`` of the verifier arrays and page ``p``
    of the draft arrays belong to the same request, so the scheduler's
    alloc/free/table bookkeeping (the :class:`PagedKVPool` base) covers
    both.  The draft side stores the SAME positions in its own wire
    format (the draft plan's kv bitwidths).  ``defrag`` permutes both
    pytrees coherently; ``truncate`` rewinds the verifier side only — the
    draft's stale rows sit ahead of the new position and are overwritten
    before they become attendable (see ``spec/draft.py``).
    """

    def __init__(self, cfg: ModelConfig, *, n_pages: int, page_size: int,
                 kv_bits=None, kv_group: int = 64, draft_kv_bits=None,
                 draft_kv_group: int = 64, dtype=None, obs=None):
        super().__init__(cfg, n_pages=n_pages, page_size=page_size,
                         kv_bits=kv_bits, kv_group=kv_group, dtype=dtype,
                         obs=obs)
        # the draft pool's own allocator is unused (page ids are shared),
        # so it stays un-instrumented: no double-counted alloc events
        self.draft = PagedKVPool(cfg, n_pages=n_pages, page_size=page_size,
                                 kv_bits=draft_kv_bits,
                                 kv_group=draft_kv_group, dtype=dtype)

    def defrag(self) -> dict[int, int]:
        mapping = super().defrag()
        perm = np.zeros((self.n_pages,), np.int32)
        for old, new in mapping.items():
            perm[new] = old
        self.draft.pages = self.draft._permute(self.draft.pages,
                                               jnp.asarray(perm))
        return mapping

    def draft_nbytes(self) -> int:
        return self.draft.nbytes()

    def total_nbytes(self) -> int:
        """Resident bytes of both sides (the draft cache is the price of
        speculation; the draft plan's kv bits keep it small)."""
        return self.nbytes() + self.draft.nbytes()


class SpeculativeEngine:
    """Draft/verify wrapper satisfying the paged-engine step contract."""

    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig,
                 pcfg: PagedConfig, *, draft_plan, spec_k: int = 4,
                 obs=None):
        if ecfg.temperature != 0.0:
            raise ValueError(
                "speculative decoding is greedy-only: acceptance compares "
                "draft tokens against the verifier's argmax, and the "
                "token-exactness guarantee is a greedy statement")
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if draft_plan is None:
            raise ValueError("pass the draft QuantPlan (the low-bit view "
                             "of the shared checkpoint)")
        if transformer.is_quantized_params(params):
            raise ValueError(
                "SpeculativeEngine needs the raw fp checkpoint: the draft "
                "plan packs its own view of the weights (and shares "
                "segments with the verifier via the leaf cache), which "
                "pre-packed params cannot provide")
        self.cfg, self.pcfg, self.spec_k = cfg, pcfg, spec_k
        self.ecfg = ecfg
        self._obs = obs or NOOP

        leaf_cache: dict = {}
        vparams = params
        if ecfg.plan is not None:
            vparams = transformer.quantize_params(params, cfg, ecfg.plan,
                                                  leaf_cache=leaf_cache)
        self.verifier = PagedEngine(cfg, vparams, ecfg, pcfg,
                                    obs=self._obs)
        verifier_keys = set(leaf_cache)

        # the draft inherits the cell geometry and gets its own plan; its
        # cache format comes from the draft plan's kv map when it has one,
        # else it MIRRORS the verifier's kv layout — including a verifier
        # plan's per-layer map (attached to the draft plan itself, so the
        # draft's walker/param segmentation matches its shadow cache) —
        # so the shadow pool never silently falls back to fp pages
        if getattr(draft_plan, "has_kv", False):
            d_kv_bits, d_kv_group = None, ecfg.kv_group
        else:
            v_bits, v_group = self.verifier._kv_layout
            if isinstance(v_bits, tuple):
                draft_plan = draft_plan.with_kv(
                    {f"layer.{i}": b for i, b in enumerate(v_bits)},
                    default=None, kv_group=v_group)
                d_kv_bits, d_kv_group = None, v_group
            else:
                d_kv_bits, d_kv_group = v_bits, v_group
        d_ecfg = dataclasses.replace(
            ecfg, plan=draft_plan, weight_scheme=None, a_bits=None,
            kv_bits=d_kv_bits, kv_group=d_kv_group)
        dparams = transformer.quantize_params(params, cfg, draft_plan,
                                              leaf_cache=leaf_cache)
        self.draft = PagedEngine(cfg, dparams, d_ecfg, pcfg,
                                 obs=self._obs)
        # the draft's per-micro-step timings stay distinguishable from
        # the verifier's in the shared registry
        self.draft.obs_metric_labels = {"engine": "draft"}
        self.shared_keys = [
            k for k in transformer.plan_leaf_keys(cfg, draft_plan)
            if k in verifier_keys]

        # speculation telemetry (live-budget slots only)
        self.cycles = 0           # batched verify forwards run
        self.slot_cycles = 0      # (live slot, cycle) pairs — the per-
        #                           stream cost unit: a plain engine pays
        #                           exactly one of these per emitted token
        self.drafted = 0          # draft tokens proposed
        self.accepted = 0         # draft tokens the verifier accepted
        self.emitted = 0          # tokens actually delivered

    # ------------------------------------------------------ observability
    @property
    def obs(self):
        return self._obs

    @obs.setter
    def obs(self, obs):
        """Adopting a new sink propagates to both wrapped engines (the
        Server/FleetRouter re-wire path)."""
        self._obs = obs
        self.verifier.obs = obs
        self.draft.obs = obs

    @property
    def attention_mode(self) -> str:
        """The verifier's resolved paged-attention path (the one that
        decides token-exactness and dominates device time)."""
        return self.verifier.attention_mode

    @property
    def fused_fallback(self) -> bool:
        """True when either wrapped engine silently downgraded from the
        requested fused kernel to the XLA gather+dequant path."""
        return self.verifier.fused_fallback or self.draft.fused_fallback

    def report_attention_mode(self, obs=None):
        """Forward the one-shot fused-fallback report to both engines."""
        self.verifier.report_attention_mode(obs)
        self.draft.report_attention_mode(obs)

    # ------------------------------------------------------ pool plumbing
    def new_pool(self) -> PairedKVPool:
        vb, vg = self.verifier._kv_layout
        db, dg = self.draft._kv_layout
        return PairedKVPool(self.cfg, n_pages=self.pcfg.n_pages,
                            page_size=self.pcfg.page_size, kv_bits=vb,
                            kv_group=vg, draft_kv_bits=db,
                            draft_kv_group=dg, obs=self._obs)

    def prefill_request(self, pool: PairedKVPool, tokens, page_ids,
                        key, fold: int) -> int:
        """Prefill the prompt into BOTH sides' pages (same ids); the
        emitted first token is the verifier's (token-exactness)."""
        self.draft.prefill_request(pool.draft, tokens, page_ids, key, fold)
        return self.verifier.prefill_request(pool, tokens, page_ids, key,
                                             fold)

    # ------------------------------------------------------- scheduler API
    @property
    def lookahead_tokens(self) -> int:
        """The verify step writes rows ``pos .. pos + spec_k`` per slot."""
        return self.spec_k + 1

    def advance_slots(self, pool: PairedKVPool, tokens, page_table, pos,
                      key, fold: int, budget=None):
        """One speculative cycle for every slot: draft k, verify once,
        accept the longest matching prefix.  Returns per-slot emission
        lists (1..k verifier-greedy tokens each) and per-slot rejected
        draft counts.  The caller rewinds the pool past what it consumes
        (``Scheduler.step`` -> ``pool.truncate``)."""
        k = self.spec_k
        obs = self._obs
        sw = Stopwatch(obs.clock) if obs.enabled else None
        with obs.tracer.span("draft", k=k):
            props = draft_proposals(self.draft, pool.draft, tokens,
                                    page_table, pos, k, key, fold)
            if sw is not None:
                jax.block_until_ready(pool.draft.pages)
        if sw is not None:
            obs.metrics.histogram("serve_draft_ms").record(sw.elapsed_ms())
            sw.reset()
        run = np.concatenate(
            [np.asarray(tokens, np.int32)[:, None], props], axis=1)
        with obs.tracer.span("verify", k=k):
            greedy = self.verifier.decode_multi_batch(pool, run, page_table,
                                                      pos)
            if sw is not None:
                jax.block_until_ready(pool.pages)
        if sw is not None:
            obs.metrics.histogram("serve_verify_ms").record(sw.elapsed_ms())
        m = accept_lengths(props, greedy)
        emitted = emitted_tokens(props, greedy, m)
        rejected = [k - int(mb) for mb in m]

        self.cycles += 1
        cycle_drafted = cycle_accepted = 0
        for b, toks in enumerate(emitted):
            live = budget[b] if budget is not None else len(toks)
            if live <= 0:
                continue
            self.slot_cycles += 1
            cycle_drafted += k
            cycle_accepted += int(m[b])
            self.emitted += min(len(toks), live)
        self.drafted += cycle_drafted
        self.accepted += cycle_accepted
        if obs.enabled:
            obs.metrics.counter("spec_drafted_total").inc(cycle_drafted)
            obs.metrics.counter("spec_accepted_total").inc(cycle_accepted)
            obs.metrics.gauge("spec_acceptance_rate").set(
                self.acceptance_rate())
        return emitted, rejected

    # ------------------------------------------------------------- stats
    @property
    def decode_compilations(self) -> int:
        """Distinct batched-verify traces (1 == one compiled length-(k+1)
        step; the acceptance bar's ``decode_compilations == 1``)."""
        return self.verifier._multi_paged._cache_size()

    @property
    def draft_compilations(self) -> int:
        return self.draft._step_paged._cache_size()

    def acceptance_rate(self) -> float:
        return self.accepted / self.drafted if self.drafted else 0.0

    def verify_steps_per_token(self) -> float:
        """Per-stream verifier cost: (live slot, verify) pairs per emitted
        token.  A plain engine pays exactly 1.0; anything below 1.0 is
        decode speedup bought by accepted drafts."""
        return (self.slot_cycles / self.emitted if self.emitted
                else float("inf"))

    def shared_weight_bytes(self) -> float:
        """Wire bytes the draft re-uses from the verifier's packed leaves
        (priced with the planner's cost model)."""
        from repro.plan.costmodel import leaf_key_bytes
        return sum(leaf_key_bytes(self.cfg, k) for k in self.shared_keys)

    def spec_stats(self) -> dict:
        return {"spec_k": self.spec_k, "cycles": self.cycles,
                "drafted": self.drafted, "accepted": self.accepted,
                "emitted": self.emitted,
                "acceptance_rate": round(self.acceptance_rate(), 4),
                "verify_steps_per_token":
                    round(self.verify_steps_per_token(), 4),
                "shared_weight_bytes": self.shared_weight_bytes(),
                "verify_compilations": self.decode_compilations,
                "draft_compilations": self.draft_compilations,
                "attention_mode": self.attention_mode,
                "draft_attention_mode": self.draft.attention_mode}
