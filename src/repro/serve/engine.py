"""Batched serving engine: prefill + decode with quantized weights/cache.

The deployment path of the paper's scheme end-to-end:

  * weights:    offline ``transformer.quantize_params`` -> packed QWeight
                (local quantization regions; kernels/quant_matmul on TPU);
  * activations: per-projection runtime quantization via the policy's
                ``a_bits`` (paper section V.B "inputs ... converted into
                fixed point in runtime");
  * KV cache:   ``kv_bits`` stores K/V (or the SSM state) in the LQ wire
                format (core/kvwire.py);
  * mixed precision: ``EngineConfig.plan`` (a ``repro.plan.QuantPlan``)
                assigns a per-layer scheme instead of one uniform
                ``weight_scheme`` — the planned model serves through the
                identical prefill/decode/paged paths.

``generate`` runs greedy or temperature sampling with a lax.scan'd decode
loop inside one jit — per-token Python overhead is zero; batching is the
(B, ...) leading dim end-to-end.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kvwire, schemes
from repro.kernels import paged_attention as paged_attn
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.models.layers import QuantPolicy, NO_QUANT
from repro.obs import NOOP, Stopwatch
from repro.serve.pool import PagedKVPool


def greedy_sample(logits, key):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def temperature_sample(temperature: float = 1.0, top_k: int | None = None):
    def fn(logits, key):
        lg = logits / max(temperature, 1e-6)
        if top_k is not None:
            kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
            lg = jnp.where(lg < kth, -1e9, lg)
        return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)
    return fn


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_len: int = 2048
    kv_bits: int | None = None           # None = fp cache
    kv_group: int = 64
    weight_scheme: str | None = None     # e.g. "lq4w"; None = fp weights
    a_bits: int | None = None            # runtime activation quantization
    plan: object = None                  # QuantPlan: per-layer mixed precision
    backend: str = "auto"
    temperature: float = 0.0             # 0 => greedy
    top_k: int | None = None
    # paged decode through the fused flash-decode kernel
    # (kernels/paged_attention.py): wire pages stream through VMEM and
    # dequantize in-register instead of gather -> fp pool view -> attend.
    # Compiled on TPU, interpret-mode elsewhere; off the TPU a missing
    # Pallas falls back to the XLA gather path (reported, see
    # attention_mode), on a TPU it is an error.
    fused_attention: bool = False


class Engine:
    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig, *,
                 obs=None):
        self.cfg, self.ecfg = cfg, ecfg
        # repro.obs.Observability; NOOP records nothing at ~zero cost.
        # Host-side only: instrumentation never enters a jitted function,
        # so enabling it cannot add a retrace.
        self.obs = obs or NOOP
        self.obs_metric_labels: dict = {}  # e.g. {"engine": "draft"}
        if ecfg.plan is not None:
            if ecfg.weight_scheme is not None:
                raise ValueError("pass either a uniform weight_scheme or a "
                                 "plan, not both")
            if ecfg.a_bits is not None:
                raise ValueError("a_bits is per-layer under a plan — set it "
                                 "in the plan's QuantConfigs instead")
            if transformer.is_quantized_params(params):
                # pre-packed by the caller (leaf-cache sharing across
                # engines: repro.spec draft/verifier, repro.fleet tenants)
                self.params = params
            else:
                self.params = transformer.quantize_params(params, cfg,
                                                          ecfg.plan)
            self.policy = ecfg.plan.policy(cfg, mode="serve",
                                           backend=ecfg.backend)
        elif ecfg.weight_scheme is not None:
            qcfg = schemes.get(ecfg.weight_scheme)
            if ecfg.a_bits is not None:
                qcfg = dataclasses.replace(qcfg, a_bits=ecfg.a_bits)
            self.params = transformer.quantize_params(params, cfg, qcfg)
            self.policy = QuantPolicy.serve(qcfg, backend=ecfg.backend)
        else:
            self.params = params
            self.policy = NO_QUANT
        self._kv_layout = self._resolve_kv_layout()
        self._sample = (greedy_sample if ecfg.temperature == 0.0 else
                        temperature_sample(ecfg.temperature, ecfg.top_k))
        self._generate = jax.jit(self._generate_impl,
                                 static_argnames=("steps",))

    # ------------------------------------------------------------------
    def _resolve_kv_layout(self):
        """The engine's cache wire spec: ``(bits, group)`` where ``bits``
        is None (fp), one int (uniform), or the plan's per-layer map."""
        plan = self.ecfg.plan
        if plan is not None and getattr(plan, "has_kv", False):
            if self.ecfg.kv_bits is not None:
                raise ValueError("kv_bits is per-layer under a plan with a "
                                 "kv map — set it in the plan instead")
            return plan.resolve_kv(self.cfg), plan.kv_group
        return self.ecfg.kv_bits, self.ecfg.kv_group

    def _kv_quant(self):
        bits, group = self._kv_layout
        return None if bits is None else (bits, group)

    def init_cache(self, batch: int):
        return transformer.init_cache(self.cfg, batch, self.ecfg.max_len,
                                      kv_quant=self._kv_quant())

    def _generate_impl(self, params, batch, cache, key, *, steps: int):
        logits, cache = transformer.prefill(params, self.cfg, batch, cache,
                                            policy=self.policy)
        first = self._sample(logits[:, -1], key)

        def step(carry, k):
            tok, cache = carry
            logits, cache = transformer.decode_step(
                params, self.cfg, tok[:, None], cache, policy=self.policy)
            nxt = self._sample(logits[:, -1], k)
            return (nxt, cache), nxt

        keys = jax.random.split(key, steps)
        (_, cache), toks = jax.lax.scan(step, (first, cache), keys)
        out = jnp.concatenate([first[:, None], jnp.moveaxis(toks, 0, 1)],
                              axis=1)
        return out, cache

    def generate(self, batch: dict, *, steps: int, seed: int = 0):
        """batch: {'tokens': (B, L)} (+ frontend inputs).  Returns
        (generated (B, steps+1) int32, final cache)."""
        b = batch["tokens"].shape[0]
        cache = self.init_cache(b)
        return self._generate(self.params, batch, cache,
                              jax.random.key(seed), steps=steps)

    # ------------------------------------------------------------------
    def cache_bytes(self, batch: int) -> int:
        """HBM bytes of the decode cache (the kv_bits win, measurable)."""
        return kvwire.cache_nbytes(jax.eval_shape(
            lambda: self.init_cache(batch)))


# ---------------------------------------------------------------------------
# paged engine: prefill/decode against a shared page pool
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PagedConfig:
    """Geometry of the continuous-batching serve cell.

    max_context bounds prompt + generation per request and fixes the static
    shapes: the prefill bucket is max_context tokens and every decode step
    gathers max_context // page_size pages per slot.  n_pages counts
    physical pages including the reserved scratch page 0.
    """
    max_slots: int = 4
    page_size: int = 16
    n_pages: int = 64
    max_context: int = 256

    def __post_init__(self):
        if self.max_context % self.page_size:
            raise ValueError("max_context must be a multiple of page_size")

    @property
    def pages_per_slot(self) -> int:
        return self.max_context // self.page_size


class PagedEngine(Engine):
    """Engine whose prefill/decode operate on gathered page views.

    Prefill runs one request (B=1) through the contiguous path on a
    fixed-size right-padded bucket, then scatters the bucket's wire cache
    into the request's pages — one jit for every prompt length.  Decode
    advances all max_slots slots in a single jit (static shapes; inactive
    slots are padded onto the scratch page and masked), with each layer
    gathering its slot page views from the shared pool.
    """

    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig,
                 pcfg: PagedConfig, *, obs=None):
        super().__init__(cfg, params, ecfg, obs=obs)
        if pcfg.max_context > ecfg.max_len:
            raise ValueError("pcfg.max_context exceeds ecfg.max_len")
        self.pcfg = pcfg
        self._kvq = self._kv_quant()
        # None (XLA gather+dequant) | "pallas" | "interpret"; a static
        # closure value, so toggling it is a different engine, never a
        # retrace of a running one
        self.fused_mode = paged_attn.resolve_mode(ecfg.fused_attention)
        self.fused_fallback = (bool(ecfg.fused_attention)
                               and self.fused_mode is None)
        self._fused_fallback_reported = False
        self.report_attention_mode()
        # a model with MoE layers returns its routing counts (experts hit,
        # largest group; summed over layers) beside the decode step's
        # tokens, fetched only while a trace records (``step_counts``)
        self.counts_experts = any(f == "moe" for _, f in cfg.pattern)
        self.step_counts: dict = {}
        # the pool is donated: each step updates its pages in place, so
        # HBM never holds a second pool.  A caller passes ``pool.pages``
        # and keeps only what the step returns.
        self._prefill_paged = jax.jit(self._prefill_paged_impl,
                                      donate_argnums=2)
        self._step_paged = jax.jit(self._step_paged_impl, donate_argnums=1)
        self._multi_paged = jax.jit(self._multi_paged_impl,
                                    donate_argnums=1)

    def new_pool(self) -> PagedKVPool:
        bits, group = self._kv_layout
        return PagedKVPool(self.cfg, n_pages=self.pcfg.n_pages,
                           page_size=self.pcfg.page_size,
                           kv_bits=bits, kv_group=group, obs=self.obs)

    @property
    def attention_mode(self) -> str:
        """The *resolved* paged-decode path this engine actually runs:
        ``fused-pallas`` / ``fused-interpret`` when the Pallas kernel is
        live, ``xla-fallback`` when fused was requested but unavailable,
        plain ``xla`` when never requested."""
        if self.fused_mode is not None:
            return f"fused-{self.fused_mode}"
        return "xla-fallback" if self.fused_fallback else "xla"

    def report_attention_mode(self, obs=None):
        """One-shot ``fused_fallback`` event + counter for a downgraded
        engine.  Engines are often built with NOOP obs and get the real
        one attached post-warmup (Server.set_obs / FleetRouter._wire), so
        this re-arms until an *enabled* obs actually records it."""
        if not self.fused_fallback or self._fused_fallback_reported:
            return
        self._fused_fallback_reported = paged_attn.report_fallback(
            obs if obs is not None else self.obs)

    # ------------------------------------------------------------- jitted
    def _scatter_bucket(self, pages, cache, page_ids):
        """Scatter a contiguous B=1 prefill cache into pool pages.

        The bucket cache and the pool share one decoder-stack layout
        (both built from the engine's kv spec), so the copy is
        structural: ``scatter_prefill`` tree-maps leaf-for-leaf at
        whatever wire format each layer carries.
        """
        with jax.named_scope("kv_write"):
            return {"super_segments": kvwire.scatter_prefill(
                        pages["super_segments"], cache["super_segments"],
                        page_ids, stacked=True),
                    "tail": kvwire.scatter_prefill(pages["tail"],
                                                   cache["tail"], page_ids)}

    def _sample_last(self, logits, key):
        """Sample the last position's logits with ``fold_in(*key)``.
        ``key`` is the caller's (key, fold count) pair: the step programs
        fold it, so the loop between programs makes no eager JAX call."""
        base, fold = key
        with jax.named_scope("sample"):
            return self._sample(logits[:, -1], jax.random.fold_in(base, fold))

    def _prefill_paged_impl(self, params, tokens, pages, page_ids,
                            logits_pos, key):
        cache = transformer.init_cache(self.cfg, 1, self.pcfg.max_context,
                                       kv_quant=self._kvq, whole=True)
        logits, cache = transformer.prefill(
            params, self.cfg, {"tokens": tokens}, cache, policy=self.policy,
            logits_pos=logits_pos)
        pages = self._scatter_bucket(pages, cache, page_ids)
        return self._sample_last(logits, key), pages

    def _step_paged_impl(self, params, pages, tokens, page_table, pos, key):
        logits, pages, aux = transformer.paged_decode_step(
            params, self.cfg, tokens[:, None], pages, page_table, pos,
            page_size=self.pcfg.page_size, policy=self.policy,
            fused=self.fused_mode)
        toks = self._sample_last(logits, key)
        if self.counts_experts:
            return (toks, jnp.stack([aux["experts_hit"],
                                     aux["expert_rows_max"]])), pages
        return toks, pages

    def _multi_paged_impl(self, params, pages, tokens, page_table, pos):
        logits, pages = transformer.paged_decode_multi(
            params, self.cfg, tokens, pages, page_table, pos,
            page_size=self.pcfg.page_size, policy=self.policy,
            fused=self.fused_mode)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pages

    # --------------------------------------------------------------- host
    def prefill_request(self, pool: PagedKVPool, tokens, page_ids,
                        key, fold: int) -> int:
        """Prefill one request into its pages; returns the first
        continuation token, sampled with ``fold_in(key, fold)``.
        ``tokens`` is the (unpadded) int prompt."""
        bucket = self.pcfg.max_context
        if len(tokens) > bucket:
            raise ValueError(f"prompt len {len(tokens)} > bucket {bucket}")
        obs = self.obs
        sw = Stopwatch(obs.clock) if obs.enabled else None
        with obs.tracer.span("prefill", n_tokens=len(tokens),
                             **self.obs_metric_labels):
            tok = self._prefill_host(pool, tokens, page_ids, key, fold)
            if sw is not None:
                # measured wall clock brackets the compiled step end to
                # end: the scattered pages, not just the token
                jax.block_until_ready(pool.pages)
        if sw is not None:
            obs.metrics.histogram("serve_prefill_ms",
                                  **self.obs_metric_labels).record(
                sw.elapsed_ms())
        return tok

    def _prefill_host(self, pool: PagedKVPool, tokens, page_ids,
                      key, fold: int) -> int:
        padded = np.zeros((1, self.pcfg.max_context), np.int32)
        padded[0, :len(tokens)] = tokens
        ids = np.zeros((self.pcfg.pages_per_slot,), np.int32)
        ids[:len(page_ids)] = page_ids
        tok, pool.pages = self._prefill_paged(
            self.params, padded, pool.pages, ids, np.int32(len(tokens) - 1),
            (key, np.uint32(fold)))
        return int(self._fetch(tok)[0])

    def _fetch(self, toks, counts=None) -> np.ndarray:
        """A program's sampled tokens on the host, called right after its
        dispatch.  While a trace records, the wait splits in two:
        ``fetch.ready`` waits for the program's end, ``fetch.to_host``
        for the copy, queued behind the program before the wait as one
        ``np.asarray`` queues it; ``counts`` (the decode step's routing
        counts) come with the tokens then, into ``step_counts``."""
        tracer = self.obs.tracer
        with tracer.span("fetch"):
            if not tracer.recording:
                return np.asarray(toks)
            toks.copy_to_host_async()
            if counts is not None:
                counts.copy_to_host_async()
            with tracer.span("fetch.ready"):
                toks.block_until_ready()
            with tracer.span("fetch.to_host"):
                if counts is not None:
                    hit, rows = np.asarray(counts).tolist()
                    self.step_counts = {"experts_hit": hit,
                                        "expert_rows_max": rows}
                return np.asarray(toks)

    def decode_step_batch(self, pool: PagedKVPool, tokens, page_table, pos,
                          key, fold: int) -> np.ndarray:
        """Advance every slot one token.  tokens/pos (max_slots,),
        page_table (max_slots, pages_per_slot).  Returns the tokens
        sampled with ``fold_in(key, fold)``."""
        obs = self.obs
        sw = Stopwatch(obs.clock) if obs.enabled else None
        with obs.tracer.span("decode_step"):
            toks, pool.pages = self._step_paged(
                self.params, pool.pages, np.asarray(tokens, np.int32),
                np.asarray(page_table, np.int32), np.asarray(pos, np.int32),
                (key, np.uint32(fold)))
        counts = None
        if self.counts_experts:
            toks, counts = toks
        out = self._fetch(toks, counts)
        if sw is not None:
            jax.block_until_ready(pool.pages)
            obs.metrics.histogram("serve_decode_step_ms",
                                  **self.obs_metric_labels).record(
                sw.elapsed_ms())
        return out

    def decode_multi_batch(self, pool: PagedKVPool, tokens, page_table,
                           pos) -> np.ndarray:
        """Greedy-score a length-L candidate run per slot in ONE compiled
        batched forward (the speculative verify step).  tokens
        (max_slots, L); returns the greedy next token at every position
        (max_slots, L) — all L candidates' K/V are written to the pool, so
        rejected suffixes must be un-written via ``pool.truncate``."""
        toks, pool.pages = self._multi_paged(
            self.params, pool.pages, np.asarray(tokens, np.int32),
            np.asarray(page_table, np.int32), np.asarray(pos, np.int32))
        return np.asarray(toks)

    # ------------------------------------------------------- scheduler API
    @property
    def lookahead_tokens(self) -> int:
        """Cache rows one scheduler step may write per slot at/past its
        position (speculative engines write their whole candidate run)."""
        return 1

    def advance_slots(self, pool: PagedKVPool, tokens, page_table, pos,
                      key, fold: int, budget=None):
        """Scheduler step contract: advance every slot, returning
        ``(emitted, rejected)`` — per-slot lists of emitted tokens and
        per-slot rejected-draft counts.  The plain engine emits exactly
        one token per slot and never rejects; ``budget`` (per-slot max
        tokens to emit) is honored trivially."""
        toks = self.decode_step_batch(pool, tokens, page_table, pos, key,
                                      fold)
        return [[int(t)] for t in toks], [0] * len(toks)

    @property
    def decode_compilations(self) -> int:
        """Distinct decode-step traces (1 == no per-step retrace)."""
        return self._step_paged._cache_size()
