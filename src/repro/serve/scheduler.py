"""Continuous-batching request scheduler over the paged engine.

Lifecycle (see serve/README.md): submit -> QUEUED -> (admit: prefill into
freshly allocated pages, take a decode slot) -> RUNNING -> interleaved
decode steps with every other in-flight request -> COMPLETE.  Admission is
FCFS within a priority lane, higher lanes first.  When the page pool is
exhausted mid-decode the scheduler preempts the lowest-priority,
latest-arrived victim (recompute-style: its pages are freed and it
re-queues at the front of its lane; on re-admission its prompt + generated
prefix is re-prefilled and decoding resumes from its last token).

With an fp KV cache, preempt/resume is bit-exact.  With a quantized cache
the re-prefilled prefix is attended at full precision during the resume
prefill only, so a resumed continuation may deviate from the uninterrupted
run — the same trade vLLM's recompute preemption makes.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable

import jax
import numpy as np

from repro.obs import NOOP
from repro.serve.engine import PagedEngine
from repro.serve.pool import PagedKVPool

QUEUED, RUNNING, COMPLETE = "queued", "running", "complete"


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int
    priority: int = 0
    on_token: Callable[[int, int], None] | None = None   # (rid, token)
    generated: list[int] = dataclasses.field(default_factory=list)
    state: str = QUEUED
    n_preemptions: int = 0
    rejected_tokens: int = 0  # draft tokens a speculative verify rejected
    arrival: int = 0          # submit order; FCFS tiebreak + victim choice
    tenant: str | None = None  # fleet routing tag (fleet/router.py)
    # observability state, absolute clock readings in seconds: t_queued
    # always (the admit span's queued_ms), the rest only when the
    # scheduler's obs is enabled (None otherwise)
    t_submit: float | None = None   # submit() instant
    t_queued: float | None = None   # last (re-)enqueue instant
    t_first: float | None = None    # first emitted token (TTFT anchor)
    t_last: float | None = None     # latest emitted token (ITL anchor)
    trace_tid: int = 0              # the request's trace lane


@dataclasses.dataclass(frozen=True)
class Completion:
    rid: int
    tokens: tuple[int, ...]
    n_preemptions: int
    tenant: str | None = None
    rejected_tokens: int = 0


class Scheduler:
    """Admits a stream of requests and interleaves their decode steps."""

    def __init__(self, engine: PagedEngine, pool: PagedKVPool, *,
                 on_token=None, on_complete=None, seed: int = 0, obs=None):
        self.engine, self.pool = engine, pool
        self.pcfg = engine.pcfg
        self.on_token, self.on_complete = on_token, on_complete
        # repro.obs.Observability: request-lifecycle spans + the serving
        # latency histograms (TTFT / ITL / queue wait).  NOOP by default.
        self.obs = obs or NOOP
        if self.obs.enabled:
            self.obs.tracer.name_thread(0, "engine")
        # optional repro.obs.numerics.QualityMonitor: its on_step tap runs
        # the sampled shadow-divergence / KV dequant probes after each
        # decode step (host-side; never touches the compiled step)
        self.quality = None
        # optional repro.obs.profile.PhaseProfiler: same tap shape — the
        # sampled phase-attribution replays (gather/dequant/attention/...)
        self.profiler = None
        self._lanes: dict[int, deque[Request]] = {}
        self._requests: dict[int, Request] = {}
        self._slots: list[Request | None] = [None] * self.pcfg.max_slots
        self._pos = np.zeros((self.pcfg.max_slots,), np.int32)
        self._last_tok = np.zeros((self.pcfg.max_slots,), np.int32)
        self._next_rid = 0
        self._decode_steps = 0
        self._key_folds = 0
        self._key = jax.random.key(seed)

    # ------------------------------------------------------------- submit
    def submit(self, prompt, *, max_new_tokens: int = 16, priority: int = 0,
               on_token=None, tenant: str | None = None) -> int:
        """Validate-and-enqueue.  Every reason a request could never be
        admitted is rejected here with a ValueError (instead of live-locking
        the admit loop later): empty prompts, non-positive token budgets,
        contexts beyond the prefill bucket, and page demands the pool cannot
        satisfy even when completely empty."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        total = len(prompt) + max_new_tokens
        if total > self.pcfg.max_context:
            raise ValueError(f"prompt+max_new_tokens={total} exceeds "
                             f"max_context={self.pcfg.max_context}")
        need = -(-total // self.pcfg.page_size)
        if need > self.pool.n_allocatable:
            raise ValueError(
                f"request needs {need} pages at full length but the pool "
                f"holds only {self.pool.n_allocatable} allocatable pages "
                f"(n_pages={self.pool.n_pages} minus scratch); it could "
                f"never be admitted")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, prompt, max_new_tokens, priority=priority,
                      on_token=on_token, arrival=rid, tenant=tenant)
        # taken whether or not obs is on: the admit span's queued_ms
        req.t_queued = self.obs.clock()
        if self.obs.enabled:
            req.t_submit = req.t_queued
            label = f"{tenant}/r{rid}" if tenant else f"req-{rid}"
            req.trace_tid = self.obs.tracer.new_tid(label)
            self.obs.event("submit", tid=req.trace_tid, rid=rid,
                           prompt_len=len(prompt))
        self._requests[rid] = req
        self._lanes.setdefault(priority, deque()).append(req)
        return rid

    # -------------------------------------------------------------- state
    @property
    def has_work(self) -> bool:
        return any(self._lanes.values()) or any(
            r is not None for r in self._slots)

    def active_requests(self) -> list[Request]:
        return [r for r in self._slots if r is not None]

    def queued_requests(self) -> list[Request]:
        return [r for lane in self._lanes.values() for r in lane]

    def stats(self) -> dict:
        return {"active": len(self.active_requests()),
                "queued": len(self.queued_requests()),
                "pool_occupancy": self.pool.occupancy(),
                "steps": self._decode_steps,
                "preemptions": sum(r.n_preemptions
                                   for r in self._requests.values()),
                # speculative-rejection rollbacks are NOT preemptions: the
                # slot keeps running, only its cache tail is un-written —
                # they get their own counter (fleet/telemetry.py)
                "rejected_tokens": sum(r.rejected_tokens
                                       for r in self._requests.values())}

    def request(self, rid: int) -> Request:
        return self._requests[rid]

    def outputs(self) -> dict[int, list[int]]:
        """Generated tokens of every submitted request so far."""
        return {rid: list(r.generated) for rid, r in self._requests.items()}

    # ------------------------------------------------------------ helpers
    def _tenant_label(self, req: Request) -> str:
        return req.tenant if req.tenant is not None else "default"

    def _emit(self, req: Request, tok: int):
        req.generated.append(tok)
        if self.obs.enabled:
            now = self.obs.clock()
            tenant = self._tenant_label(req)
            if req.t_first is None:
                req.t_first = now
                if req.t_submit is not None:
                    self.obs.metrics.histogram(
                        "serve_ttft_ms", tenant=tenant).record(
                        (now - req.t_submit) * 1e3)
                self.obs.event("first_token", tid=req.trace_tid,
                               rid=req.rid)
            elif req.t_last is not None:
                self.obs.metrics.histogram(
                    "serve_itl_ms", tenant=tenant).record(
                    (now - req.t_last) * 1e3)
            req.t_last = now
            self.obs.metrics.counter("serve_tokens_total",
                                     tenant=tenant).inc()
        if req.on_token:
            req.on_token(req.rid, tok)
        if self.on_token:
            self.on_token(req.rid, tok)

    def _finish(self, req: Request, slot: int | None,
                events: list[Completion]):
        if slot is not None:
            self._slots[slot] = None
        self.pool.free(req.rid)
        req.state = COMPLETE
        if self.obs.enabled:
            now = self.obs.clock()
            tenant = self._tenant_label(req)
            if req.t_submit is not None:
                self.obs.tracer.complete(
                    "request", req.t_submit, now - req.t_submit,
                    tid=req.trace_tid, rid=req.rid, tenant=tenant,
                    n_tokens=len(req.generated),
                    preemptions=req.n_preemptions)
            self.obs.metrics.counter("serve_completions_total",
                                     tenant=tenant).inc()
        done = Completion(req.rid, tuple(req.generated), req.n_preemptions,
                          tenant=req.tenant,
                          rejected_tokens=req.rejected_tokens)
        events.append(done)
        if self.on_complete:
            self.on_complete(done)

    def _next_queued(self) -> Request | None:
        for prio in sorted(self._lanes, reverse=True):
            if self._lanes[prio]:
                return self._lanes[prio].popleft()
        return None

    def _requeue_front(self, req: Request):
        self._lanes.setdefault(req.priority, deque()).appendleft(req)

    def _fold_key(self):
        self._key_folds += 1
        return jax.random.fold_in(self._key, self._key_folds)

    # -------------------------------------------------------------- admit
    def _admit(self, events: list[Completion]):
        while None in self._slots:
            req = self._next_queued()
            if req is None:
                return
            resume = bool(req.generated)
            # resume re-prefills prompt + generated[:-1]; the last generated
            # token is re-fed through the decode step so the continuation
            # samples from the same (quantized-cache) attention as an
            # uninterrupted run.
            tokens = req.prompt + req.generated[:-1]
            need = -(-len(tokens) // self.pcfg.page_size)
            if not self.pool.alloc(req.rid, need):
                self._requeue_front(req)
                return
            tracer = self.obs.tracer
            args = (dict(rid=req.rid, prompt_len=len(tokens),
                         queued_ms=1e3 * (self.obs.clock() - req.t_queued))
                    if tracer.recording else {})
            with tracer.span("admit", **args):
                self._admit_one(req, tokens, resume, events)

    def _admit_one(self, req: Request, tokens: list[int], resume: bool,
                   events: list[Completion]):
        """Prefill an allocated request, emit its first token, and give it
        a slot (or finish it if that token was its last)."""
        if self.obs.enabled:
            now = self.obs.clock()
            wait = now - req.t_queued
            self.obs.metrics.histogram(
                "serve_queue_wait_ms",
                tenant=self._tenant_label(req)).record(wait * 1e3)
            self.obs.tracer.complete("queued", req.t_queued, wait,
                                     tid=req.trace_tid, rid=req.rid)
        first = self.engine.prefill_request(
            self.pool, tokens, self.pool.pages_of(req.rid),
            self._fold_key())
        slot = self._slots.index(None)
        req.state = RUNNING
        if resume:
            tok = req.generated[-1]
        else:
            tok = first
            self._emit(req, tok)
            if len(req.generated) >= req.max_new_tokens:
                self._finish(req, None, events)
                return
        self._slots[slot] = req
        self._pos[slot] = len(tokens)
        self._last_tok[slot] = tok

    # ------------------------------------------------------------ preempt
    def _preempt_victim(self) -> bool:
        """Evict the lowest-priority, latest-arrived running request."""
        victims = [(r.priority, -r.arrival, i)
                   for i, r in enumerate(self._slots) if r is not None]
        if not victims:
            return False
        _, _, slot = min(victims)
        req = self._slots[slot]
        self._slots[slot] = None
        self.pool.free(req.rid)
        req.state = QUEUED
        req.n_preemptions += 1
        req.t_queued = self.obs.clock()
        if self.obs.enabled:
            self.obs.event("preempt", tid=req.trace_tid, rid=req.rid,
                           priority=req.priority)
            self.obs.metrics.counter(
                "serve_preemptions_total",
                tenant=self._tenant_label(req)).inc()
        self._requeue_front(req)
        return True

    def _ensure_pages(self):
        """Every active slot needs the pages covering every position the
        engine may write this step (``engine.lookahead_tokens`` rows for a
        speculative engine's candidate run); preempt on exhaustion."""
        look = getattr(self.engine, "lookahead_tokens", 1)
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            # lookahead rows past the request's own maximum length need no
            # pages: the slot's scratch-padded table routes those writes
            # to page 0, and tokens scored there are beyond the budget
            total = len(req.prompt) + req.max_new_tokens
            last = min(int(self._pos[slot]) + look - 1, total - 1,
                       self.pcfg.max_context - 1)
            need_idx = last // self.pcfg.page_size
            while need_idx >= len(self.pool.pages_of(req.rid)):
                if self.pool.alloc(req.rid, 1):
                    continue      # may need more than one page (lookahead)
                active = [r for r in self._slots if r is not None]
                if len(active) <= 1:
                    raise RuntimeError(
                        "page pool exhausted with a single request in "
                        "flight; increase n_pages")
                self._preempt_victim()
                if self._slots[slot] is None:   # the victim was this slot
                    break

    # ---------------------------------------------------------------- step
    def step(self) -> list[Completion]:
        """Admit what fits, then advance every in-flight request.

        A plain :class:`~repro.serve.engine.PagedEngine` emits exactly one
        token per slot; a speculative engine may emit several accepted
        tokens per slot per step (``engine.advance_slots`` returns
        per-slot emission lists plus rejected-draft counts).  Emission is
        capped at each request's remaining token budget — any cache rows
        the engine wrote past the cap die with the request's pages.
        """
        with self.obs.tracer.span("step"):
            return self._step()

    def _step(self) -> list[Completion]:
        events: list[Completion] = []
        self._admit(events)
        self._ensure_pages()
        active = [i for i, r in enumerate(self._slots) if r is not None]
        if not active:
            return events

        table = np.zeros((self.pcfg.max_slots, self.pcfg.pages_per_slot),
                         np.int32)
        budget = [0] * self.pcfg.max_slots
        for i in active:
            table[i] = self.pool.table_array(self._slots[i].rid,
                                             self.pcfg.pages_per_slot)
            budget[i] = (self._slots[i].max_new_tokens
                         - len(self._slots[i].generated))
        pos = np.where([r is not None for r in self._slots], self._pos, 0)
        # the engine-lane decode span; a speculative engine opens its
        # draft/verify child spans inside it.  live_tokens: the context
        # every advanced slot attends over, its token included;
        # live_pages: the table entries the fused kernel computes a layer,
        # every slot up to its last live page (an idle slot computes one);
        # window_pages: the entries before each slot's first live page
        # that the sliding-window layers skip, summed over those layers;
        # experts_hit / expert_rows_max: the engine's routing counts of
        # the step (``step_counts``), summed over the MoE layers.
        # Like emit's tokens, counted only while a trace keeps them
        tracer = self.obs.tracer
        recording = tracer.recording
        live = {}
        if recording:
            look = getattr(self.engine, "lookahead_tokens", 1)
            last = np.minimum((pos + look - 1) // self.pcfg.page_size,
                              self.pcfg.pages_per_slot - 1)
            live = {"live_tokens": int(sum(pos[i] + 1 for i in active)),
                    "live_pages": int((last + 1).sum())}
            cfg = self.engine.cfg
            n_local = sum(1 for i in range(cfg.n_layers)
                          if cfg.layer_spec(i)[0] == "attn_local")
            if n_local:
                first = np.maximum(pos - cfg.window + 1, 0) \
                    // self.pcfg.page_size
                live["window_pages"] = int(n_local * first.sum())
        with tracer.span("decode", step=self._decode_steps,
                         n_slots=len(active), **live) as span:
            emitted, rejected = self.engine.advance_slots(
                self.pool, self._last_tok, table, pos.astype(np.int32),
                self._fold_key(), budget=budget)
            counts = getattr(self.engine, "step_counts", None)
            if recording and counts:
                span.set_metadata(**counts)
        self._decode_steps += 1
        out = ({"tokens": sum(min(len(emitted[i]), budget[i])
                              for i in active)} if recording else {})
        with tracer.span("emit", **out):
            self._emit_step(active, emitted, rejected, events)
        if self.quality is not None:
            self.quality.on_step(self)
        if self.profiler is not None:
            self.profiler.on_step(self)
        return events

    def _emit_step(self, active: list[int], emitted, rejected,
                   events: list[Completion]):
        """Emit each slot's accepted tokens, finish the requests that
        reached their budget, and roll back a speculative slot's cache
        rows past what it accepted."""
        look = getattr(self.engine, "lookahead_tokens", 1)
        for i in active:
            req = self._slots[i]
            req.rejected_tokens += int(rejected[i])
            for tok in emitted[i]:
                if len(req.generated) >= req.max_new_tokens:
                    break
                self._pos[i] += 1
                self._last_tok[i] = int(tok)
                self._emit(req, int(tok))
            if len(req.generated) >= req.max_new_tokens:
                self._finish(req, i, events)
            elif look > 1:
                # speculative rollback: un-write cache rows past the
                # accepted prefix and release surplus lookahead pages —
                # the slot keeps running (NOT a preemption)
                self.pool.truncate(req.rid, int(self._pos[i]))

    def drain(self, max_steps: int | None = None) -> dict[int, list[int]]:
        """Run until every submitted request completes."""
        steps = 0
        while self.has_work:
            self.step()
            steps += 1
            if max_steps is not None and steps > max_steps:
                raise RuntimeError("drain exceeded max_steps")
        return self.outputs()
