"""Attention: GQA with RoPE / qk-norm, flash-style blocked softmax,
exact local-window and chunked variants, and single-token decode.

Implementations (pure JAX; lax.scan keeps HLO compact and VMEM bounded):

  flash_attention        double-scan (q blocks outer, kv blocks inner) with
                         online max/denominator -- O(q_blk * kv_blk) live
                         memory, differentiable, causal or bidirectional.
  local_attention        exact O(L * window) sliding-window / chunked
                         attention via chunk reshape + previous-chunk concat
                         (RecurrentGemma local layers; Llama-4 chunked layers
                         with lookback=0).
  decode_attention       one query step against a KV cache (+window).

GQA layout: q (B, L, KV, G, D) grouped by kv head -- k/v are never
materialized repeated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import layers
from .layers import QuantPolicy, NO_QUANT
from repro.core import kvwire as kvcache
from repro.distributed.actshard import constrain
from repro.kernels import paged_attention as paged_attn

NEG_INF = -1e30


def _mask(qpos, kpos, *, causal: bool, window: int | None):
    """(Lq, Lk) bool allowed matrix from absolute positions."""
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        m &= kpos[None, :] > (qpos[:, None] - window)
    return m


# ---------------------------------------------------------------------------
# flash-style blocked attention (custom VJP: per-block recompute backward)
# ---------------------------------------------------------------------------
#
# Naive autodiff through the forward scans saves every block's f32
# probability tensor — the full (B, H, Lq, Lk) attention matrix in HBM,
# 584 GB/device/step on the llama3.2-1b train cell (§Perf iteration 3).
# The custom VJP saves only (out, logsumexp) per row and recomputes
# p = exp(s - lse) blockwise in the backward — the standard
# FlashAttention dataflow, expressed as lax.scans.

def _blocks(q, k, v, q_block, kv_block):
    b, lq, kvh, g, d = q.shape
    lk = k.shape[1]
    qb, kb = min(q_block, lq), min(kv_block, lk)
    lq_p, lk_p = -(-lq // qb) * qb, -(-lk // kb) * kb
    if lq_p != lq:
        q = jnp.pad(q, ((0, 0), (0, lq_p - lq), (0, 0), (0, 0), (0, 0)))
    if lk_p != lk:
        k = jnp.pad(k, ((0, 0), (0, lk_p - lk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, lk_p - lk), (0, 0), (0, 0)))
    nq, nk = lq_p // qb, lk_p // kb
    qs = jnp.moveaxis(q.reshape(b, nq, qb, kvh, g, d), 1, 0)
    ks = jnp.moveaxis(k.reshape(b, nk, kb, kvh, d), 1, 0)
    vs = jnp.moveaxis(v.reshape(b, nk, kb, kvh, d), 1, 0)
    return qs, ks, vs, (b, lq, lk, kvh, g, d, qb, kb, nq, nk)


def _fwd_impl(q, k, v, causal, window, q_block, kv_block, q_offset):
    """Returns (out (B,Lq,KV,G,D), lse (B,KV,G,Lq))."""
    qs, ks, vs, (b, lq, lk, kvh, g, d, qb, kb, nq, nk) = _blocks(
        q, k, v, q_block, kv_block)
    scale = d ** -0.5

    def outer(_, qi_qblk):
        qi, qblk = qi_qblk
        qpos = q_offset + qi * qb + jnp.arange(qb)

        def inner(carry, kj_kv):
            acc, m_run, l_run = carry
            kj, kblk, vblk = kj_kv
            kpos = kj * kb + jnp.arange(kb)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qblk.astype(jnp.float32),
                           kblk.astype(jnp.float32)) * scale
            allowed = _mask(qpos, kpos, causal=causal, window=window)
            allowed &= (kpos < lk)[None, :]
            s = jnp.where(allowed[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(-1))               # (b,kv,g,qb)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p, vblk.astype(jnp.float32))
            acc = acc * corr[..., None] + pv
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((b, kvh, g, qb, d), jnp.float32)
        m0 = jnp.full((b, kvh, g, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, qb), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            inner, (acc0, m0, l0), (jnp.arange(nk), ks, vs))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))                # (b,kv,g,qb)
        return None, (jnp.moveaxis(out, 3, 1), lse)

    _, (outs, lses) = jax.lax.scan(outer, None, (jnp.arange(nq), qs))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, nq * qb, kvh, g, d)[:, :lq]
    lse = jnp.moveaxis(lses, 0, 3).reshape(b, kvh, g, nq * qb)[..., :lq]
    return out.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, q_block, kv_block, q_offset):
    out, _ = _fwd_impl(q, k, v, causal, window, q_block, kv_block, q_offset)
    return out


def _flash_fwd(q, k, v, causal, window, q_block, kv_block, q_offset):
    out, lse = _fwd_impl(q, k, v, causal, window, q_block, kv_block,
                         q_offset)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, q_block, kv_block, q_offset, res, dout):
    q, k, v, out, lse = res
    qs, ks, vs, (b, lq, lk, kvh, g, d, qb, kb, nq, nk) = _blocks(
        q, k, v, q_block, kv_block)
    scale = d ** -0.5
    lq_p, lk_p = nq * qb, nk * kb
    dout_p = jnp.pad(dout.astype(jnp.float32),
                     ((0, 0), (0, lq_p - lq), (0, 0), (0, 0), (0, 0)))
    out_p = jnp.pad(out.astype(jnp.float32),
                    ((0, 0), (0, lq_p - lq), (0, 0), (0, 0), (0, 0)))
    lse_p = jnp.pad(lse, ((0, 0), (0, 0), (0, 0), (0, lq_p - lq)))
    dos = jnp.moveaxis(dout_p.reshape(b, nq, qb, kvh, g, d), 1, 0)
    # delta_i = sum_d dout_id * out_id  (per q row)
    delta = jnp.einsum("blkgd,blkgd->bkgl", dout_p, out_p)      # (b,kv,g,Lq)
    deltas = jnp.moveaxis(delta.reshape(b, kvh, g, nq, qb), 3, 0)
    lses = jnp.moveaxis(lse_p.reshape(b, kvh, g, nq, qb), 3, 0)

    def recompute_p(qblk, kblk, qi, kj):
        qpos = q_offset + qi * qb + jnp.arange(qb)
        kpos = kj * kb + jnp.arange(kb)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qblk.astype(jnp.float32),
                       kblk.astype(jnp.float32)) * scale
        allowed = _mask(qpos, kpos, causal=causal, window=window)
        allowed &= (kpos < lk)[None, :]
        return jnp.where(allowed[None, None, None], s, NEG_INF)

    # pass 1: dq — outer over q blocks, inner over kv blocks
    def dq_outer(_, xs):
        qi, qblk, doblk, dlt, lseblk = xs

        def dq_inner(dq_acc, kj_kv):
            kj, kblk, vblk = kj_kv
            s = recompute_p(qblk, kblk, qi, kj)
            p = jnp.exp(s - lseblk[..., None])                  # (b,kv,g,qb,kb)
            dp = jnp.einsum("bqkgd,bskd->bkgqs", doblk,
                            vblk.astype(jnp.float32))
            ds = p * (dp - dlt[..., None]) * scale
            dq_acc = dq_acc + jnp.einsum("bkgqs,bskd->bqkgd", ds,
                                         kblk.astype(jnp.float32))
            return dq_acc, None

        dq0 = jnp.zeros((b, qb, kvh, g, d), jnp.float32)
        dq_blk, _ = jax.lax.scan(dq_inner, dq0, (jnp.arange(nk), ks, vs))
        return None, dq_blk

    _, dq_blocks = jax.lax.scan(dq_outer, None,
                                (jnp.arange(nq), qs, dos, deltas, lses))
    dq = jnp.moveaxis(dq_blocks, 0, 1).reshape(b, lq_p, kvh, g, d)[:, :lq]

    # pass 2: dk/dv — outer over kv blocks, inner over q blocks
    def dkv_outer(_, xs):
        kj, kblk, vblk = xs

        def dkv_inner(carry, qxs):
            dk_acc, dv_acc = carry
            qi, qblk, doblk, dlt, lseblk = qxs
            s = recompute_p(qblk, kblk, qi, kj)
            p = jnp.exp(s - lseblk[..., None])
            # dv_j = sum_i p_ij do_i  (sum over q rows and groups)
            dv_acc = dv_acc + jnp.einsum("bkgqs,bqkgd->bskd", p, doblk)
            dp = jnp.einsum("bqkgd,bskd->bkgqs", doblk,
                            vblk.astype(jnp.float32))
            ds = p * (dp - dlt[..., None]) * scale
            dk_acc = dk_acc + jnp.einsum("bkgqs,bqkgd->bskd", ds,
                                         qblk.astype(jnp.float32))
            return (dk_acc, dv_acc), None

        z = jnp.zeros((b, kb, kvh, d), jnp.float32)
        (dk_blk, dv_blk), _ = jax.lax.scan(
            dkv_inner, (z, z), (jnp.arange(nq), qs, dos, deltas, lses))
        return None, (dk_blk, dv_blk)

    _, (dk_blocks, dv_blocks) = jax.lax.scan(dkv_outer, None,
                                             (jnp.arange(nk), ks, vs))
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(b, lk_p, kvh, d)[:, :lk]
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(b, lk_p, kvh, d)[:, :lk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_block",
                                             "kv_block", "q_offset"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    q_block: int = 512, kv_block: int = 1024,
                    q_offset: int = 0):
    """q (B, Lq, KV, G, D); k, v (B, Lk, KV, D) -> (B, Lq, KV, G, D).

    ``q_offset`` shifts query absolute positions (cached prefill
    continuation).  Blocks are masked, not skipped, in this baseline --
    the causal-pair-list optimization is a recorded perf iteration.
    """
    return _flash(q, k, v, causal, window, q_block, kv_block, q_offset)


# ---------------------------------------------------------------------------
# exact local-window / chunked attention (O(L * window))
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("window", "lookback"))
def local_attention(q, k, v, *, window: int, lookback: int = 1):
    """Causal sliding-window (lookback=1) or within-chunk (lookback=0)
    attention.  q (B, L, KV, G, D); k, v (B, L, KV, D).

    lookback=1: each chunk of size ``window`` attends to itself + previous
    chunk, masked to kpos in (qpos - window, qpos] -- exact sliding window.
    lookback=0: attention is confined to the chunk (Llama-4 chunked layers;
    ``window`` = chunk size).
    """
    b, l, kvh, g, d = q.shape
    c = window
    l_p = -(-l // c) * c
    pad = l_p - l
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = l_p // c
    qc = q.reshape(b, nc, c, kvh, g, d)
    kc = k.reshape(b, nc, c, kvh, d)
    vc = v.reshape(b, nc, c, kvh, d)

    if lookback:
        prev = jnp.pad(kc[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
        kcat = jnp.concatenate([prev, kc], axis=2)             # (b,nc,2c,..)
        pv = jnp.pad(vc[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
        vcat = jnp.concatenate([pv, vc], axis=2)
        kpos_rel = jnp.arange(2 * c) - c                       # vs chunk start
    else:
        kcat, vcat = kc, vc
        kpos_rel = jnp.arange(c)

    qpos_rel = jnp.arange(c)
    allowed = (kpos_rel[None, :] <= qpos_rel[:, None])
    allowed &= kpos_rel[None, :] > (qpos_rel[:, None] - window)
    # chunk 0 has no previous chunk: mask kpos_rel < 0 there
    chunk_ids = jnp.arange(nc)
    valid_prev = (chunk_ids[:, None, None] > 0) | (kpos_rel >= 0)[None, None]
    allowed = allowed[None] & valid_prev                       # (nc, c, 2c)

    s = jnp.einsum("bnckgd,bnskd->bnkgcs", qc.astype(jnp.float32),
                   kcat.astype(jnp.float32)) * (d ** -0.5)
    s = jnp.where(allowed[None, :, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bnkgcs,bnskd->bnckgd", p, vcat.astype(jnp.float32))
    out = out.reshape(b, l_p, kvh, g, d)[:, :l]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# decode: one token against a cache
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, pos, *, window: int | None = None,
                     chunk: int | None = None, key_positions=None):
    """q (B, Lq, KV, G, D); caches (B, S, KV, D); pos int — scalar, or (B,)
    for continuous batching where every slot sits at its own position.
    ``Lq`` is usually 1 (plain decode); the speculative verify path sends
    a length-Lq run whose query i sits at absolute position ``pos + i``
    and attends causally over cache slots ``<= pos + i`` (the run's own
    K/V having been written to the cache first).  ``key_positions`` (S,)
    gives each cache slot's absolute position (ring buffers); default slot
    s holds position s.  ``window`` restricts to a sliding window;
    ``chunk`` to the current chunk (Llama-4).
    """
    b, lq, kvh, g, d = q.shape
    s_len = k_cache.shape[1]
    spos = jnp.arange(s_len) if key_positions is None else key_positions
    posb = jnp.broadcast_to(jnp.asarray(pos), (b,))
    qpos = posb[:, None] + jnp.arange(lq)                       # (B, Lq)
    valid = (spos[None, None, :] <= qpos[..., None]) & (spos >= 0)
    if window is not None:
        valid &= spos[None, None, :] > (qpos[..., None] - window)
    if chunk is not None:
        valid &= spos[None, None, :] >= (qpos[..., None] // chunk) * chunk
    # keep caches in their storage dtype: preferred_element_type gives the
    # f32 accumulation without materializing an upcast (B, S, KV, D) copy
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k_cache,
                   preferred_element_type=jnp.float32) * (d ** -0.5)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block (projections + dispatch)
# ---------------------------------------------------------------------------

def attn_init(key, *, d_model: int, n_heads: int, n_kv: int, head_dim: int,
              qk_norm: bool = False, bias: bool = False, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(ks[0], d_model, n_heads * head_dim,
                                dtype=dtype, bias=bias),
        "wk": layers.dense_init(ks[1], d_model, n_kv * head_dim,
                                dtype=dtype, bias=bias),
        "wv": layers.dense_init(ks[2], d_model, n_kv * head_dim,
                                dtype=dtype, bias=bias),
        "wo": layers.dense_init(ks[3], n_heads * head_dim, d_model,
                                dtype=dtype, bias=bias),
    }
    if qk_norm:
        p["q_norm"] = layers.rmsnorm_init(head_dim, dtype)
        p["k_norm"] = layers.rmsnorm_init(head_dim, dtype)
    return p


def _project_qkv(p, x, kv_src, *, n_heads, n_kv, head_dim, qk_norm, rope,
                 positions, rope_theta, policy: QuantPolicy, yarn=()):
    b, l = x.shape[:2]
    g = n_heads // n_kv
    q = layers.dense_apply(p["wq"], x, policy).reshape(b, l, n_kv, g, head_dim)
    lk = kv_src.shape[1]
    k = layers.dense_apply(p["wk"], kv_src, policy).reshape(b, lk, n_kv,
                                                            head_dim)
    v = layers.dense_apply(p["wv"], kv_src, policy).reshape(b, lk, n_kv,
                                                            head_dim)
    if qk_norm:
        q = layers.rmsnorm_apply(p["q_norm"], q)
        k = layers.rmsnorm_apply(p["k_norm"], k)
    if rope:
        q = layers.apply_rope(q.reshape(b, l, n_kv * g, head_dim),
                              positions, rope_theta, yarn).reshape(q.shape)
        k = layers.apply_rope(k, positions, rope_theta, yarn)
    return q, k, v


def attn_apply(p, x, *, n_heads: int, n_kv: int, head_dim: int,
               kind: str = "full", causal: bool = True,
               window: int | None = None, qk_norm: bool = False,
               rope: bool = True, rope_theta: float = 1e4, yarn: tuple = (),
               positions=None, kv_src=None, cache=None, cache_pos=None,
               page_table=None, page_size: int | None = None, layer=None,
               fused: str | None = None,
               policy: QuantPolicy = NO_QUANT):
    """One attention block.

    kind: 'full' | 'local' (sliding window) | 'chunked' (within-chunk) |
          'cross' (kv from kv_src, no causal, no rope on q/k).
    cache: None (train/prefill-no-cache) or dict(k=(B,S,KV,D), v=...) --
      * decode: x has L==1, cache_pos is this token's position scalar;
      * prefill-into-cache: L>1 writes [0:L) and attends within x.
    page_table: (B, P) int32 physical page ids — paged decode.  cache leaves
      then carry a shared pool in the stored page layout
      (``core/kvwire.py``) instead of a per-request (B, S, KV, ...) buffer,
      cache_pos is a (B,) per-slot position vector, and the step writes
      this token's K/V into its page before attending over the slot's
      pages (kinds 'full' and 'local'; a local layer's pages hold its whole
      context, and its attention reads only the last ``window`` positions
      of it).
    page_size: with page_table, the pool's tokens per page.
    layer: with page_table, the traced index of this layer in a
      scan-stacked pool leaf the cache carries whole; None for a leaf of
      one layer.
    fused: None (XLA gather+dequant path) or 'pallas'/'interpret' — run the
      paged branch through the fused flash-decode kernel
      (``kernels/paged_attention.py``), which streams wire pages through
      VMEM and dequantizes in-register instead of materializing the pool.
    yarn: YaRN's (factor, original positions, beta_fast, beta_slow,
      attention_factor) for this layer's rotary embedding; () = plain.
    Returns (out, new_cache).
    """
    b, l, _ = x.shape
    g = n_heads // n_kv
    if positions is None:
        base = 0 if cache_pos is None else cache_pos
        positions = base + jnp.arange(l)[None]
    src = x if kind != "cross" else kv_src
    # layer-kind scopes (HLO metadata only): qkv, kv_write, attention,
    # attn_out; block_apply adds norm and ffn
    with jax.named_scope("qkv"):
        q, k, v = _project_qkv(p, x, src, n_heads=n_heads, n_kv=n_kv,
                               head_dim=head_dim, qk_norm=qk_norm,
                               rope=rope and kind != "cross",
                               positions=positions, rope_theta=rope_theta,
                               policy=policy, yarn=yarn)
        if cache is None and kind != "cross" and \
                getattr(policy, "kv_fq", None) is not None:
            # cache-free forward under a kv-quantized policy: round K/V
            # through the wire format so sensitivity profiling sees
            # exactly the decode numerics (post-rope, per-position local
            # regions along head_dim)
            fq_bits, fq_group = policy.kv_fq
            k = kvcache.dequantize_kv(
                kvcache.quantize_kv(k, fq_bits, fq_group), head_dim, k.dtype)
            v = kvcache.dequantize_kv(
                kvcache.quantize_kv(v, fq_bits, fq_group), head_dim, v.dtype)

    new_cache = cache
    if cache is not None and kind != "cross" and page_table is not None:
        if kind not in ("full", "local"):
            raise ValueError("paged cache supports decode of 'full' and "
                             "'local' attention only")
        out, new_cache = _paged_attend(q, k, v, cache, page_table,
                                       page_size, cache_pos, fused,
                                       window if kind == "local" else None,
                                       layer)
    elif cache is not None and kind != "cross":
        out, new_cache = _cached_attend(q, k, v, cache, cache_pos, kind,
                                        causal, window)
    else:
        with jax.named_scope("attention"):
            out = _dispatch(q, k, v, kind, causal, window)

    with jax.named_scope("attn_out"):
        out = out.reshape(b, l, n_heads * head_dim)
        return layers.dense_apply(p["wo"], out, policy), new_cache


def _quant_spec(cache, head_dim):
    """(bits, group) of a quantized cache's wire format, else None."""
    if not kvcache.is_quant_kv(cache.get("k")):
        return None
    return kvcache._infer(cache["k"]["packed"].shape[-1], head_dim,
                          cache["k"]["scale"].shape[-1])


def _paged_attend(q, k, v, cache, page_table, page_size, cache_pos, fused,
                  window=None, layer=None):
    """Paged decode: write this step's K/V into the slots' pages, in
    place (layer ``layer`` of a stacked pool leaf), then attend over them
    (fused kernel reading the pages as stored, or gather + dequant +
    attend); a ``window`` keeps query i to keys in
    ``(pos + i - window, pos + i]``."""
    l, kvh, head_dim = q.shape[1], q.shape[2], q.shape[-1]
    spec = kvcache.page_spec(cache["k"], head_dim, page_size * kvh)
    with jax.named_scope("kv_write"):
        wpos = cache_pos[:, None] + jnp.arange(l)           # (B, L) absolute
        # positions beyond the slot's table (a speculative run tailing past
        # max_context) write the scratch page instead of clamping onto the
        # slot's own last page, where they would corrupt live rows
        limit = page_table.shape[1] * page_size
        page_idx = jnp.take_along_axis(
            page_table, jnp.minimum(wpos // page_size,
                                    page_table.shape[1] - 1), axis=1)
        page_idx = jnp.where(wpos < limit, page_idx, 0)
        row = wpos % page_size
        kw = dict(bits=spec[0], group_size=spec[1]) if spec else {}
        qk = kvcache.scatter_tokens(cache["k"], k, page_idx, row,
                                    page_size=page_size, layer=layer, **kw)
        qv = kvcache.scatter_tokens(cache["v"], v, page_idx, row,
                                    page_size=page_size, layer=layer, **kw)
    with jax.named_scope("attention"):
        if fused is not None:
            out = paged_attn.paged_attention(
                q, qk, qv, page_table, cache_pos, page_size=page_size,
                layer=layer, window=window, interpret=fused == "interpret")
        else:
            k_cache = kvcache.gather_pages(qk, page_table, page_size, kvh,
                                           layer)
            v_cache = kvcache.gather_pages(qv, page_table, page_size, kvh,
                                           layer)
            if spec:
                k_cache = kvcache.dequantize_kv(k_cache, head_dim, q.dtype)
                v_cache = kvcache.dequantize_kv(v_cache, head_dim, q.dtype)
            out = decode_attention(q, k_cache, v_cache, cache_pos,
                                   window=window)
    return out, {"k": qk, "v": qv}


def _cached_attend(q, k, v, cache, cache_pos, kind, causal, window):
    """Contiguous cache: a decode step writes its slot and attends over
    the cache; a prefill writes [0:L) and attends within the prefix."""
    l, head_dim = q.shape[1], q.shape[-1]
    ring = kind in ("local", "chunked")   # fixed-size rotating cache
    spec = _quant_spec(cache, head_dim)
    s_len = (cache["k"]["packed"] if spec else cache["k"]).shape[1]
    if l == 1:  # decode step
        slot = cache_pos % s_len if ring else cache_pos
        with jax.named_scope("kv_write"):
            if spec:
                # LQ-quantized cache (core/kvwire.py): write the new slot
                # in wire format, attend over the dequantized view.  HBM
                # holds only packed codes + per-region affine.
                new_cache = {
                    "k": kvcache.update_quant_kv(cache["k"], k, slot, axis=1,
                                                 bits=spec[0],
                                                 group_size=spec[1]),
                    "v": kvcache.update_quant_kv(cache["v"], v, slot, axis=1,
                                                 bits=spec[0],
                                                 group_size=spec[1])}
            else:
                new_cache = {
                    "k": jax.lax.dynamic_update_slice_in_dim(
                        cache["k"], k.astype(cache["k"].dtype), slot, axis=1),
                    "v": jax.lax.dynamic_update_slice_in_dim(
                        cache["v"], v.astype(cache["v"].dtype), slot, axis=1)}
        with jax.named_scope("attention"):
            if spec:
                k_cache = kvcache.dequantize_kv(new_cache["k"], head_dim,
                                                q.dtype)
                v_cache = kvcache.dequantize_kv(new_cache["v"], head_dim,
                                                q.dtype)
            else:
                k_cache, v_cache = new_cache["k"], new_cache["v"]
            key_pos = None
            if ring:  # slot s holds absolute position pos - ((pos - s) % S)
                key_pos = cache_pos - ((cache_pos - jnp.arange(s_len))
                                       % s_len)
            out = decode_attention(
                q, k_cache, v_cache, cache_pos,
                window=window if kind == "local" else None,
                chunk=window if kind == "chunked" else None,
                key_positions=key_pos)
        return out, new_cache
    # prefill: write cache, attend within the prefix
    with jax.named_scope("kv_write"):
        if spec:
            if ring and l >= s_len:
                idx = (jnp.arange(s_len) - l) % s_len
                keep_k, keep_v = k[:, l - s_len:][:, idx], \
                    v[:, l - s_len:][:, idx]
                new_cache = {
                    "k": kvcache.quantize_kv(keep_k, *spec),
                    "v": kvcache.quantize_kv(keep_v, *spec)}
            else:
                new_cache = {
                    "k": kvcache.update_quant_kv(
                        cache["k"], k, 0, axis=1, bits=spec[0],
                        group_size=spec[1]),
                    "v": kvcache.update_quant_kv(
                        cache["v"], v, 0, axis=1, bits=spec[0],
                        group_size=spec[1])}
        else:
            kc = k.astype(cache["k"].dtype)
            vc = v.astype(cache["v"].dtype)
            if ring and l >= s_len:
                # keep the last s_len tokens at slots (t % s_len)
                idx = (jnp.arange(s_len) - l) % s_len
                new_cache = {"k": kc[:, l - s_len:][:, idx],
                             "v": vc[:, l - s_len:][:, idx]}
            else:
                new_cache = {
                    "k": jax.lax.dynamic_update_slice_in_dim(
                        cache["k"], kc, 0, axis=1),
                    "v": jax.lax.dynamic_update_slice_in_dim(
                        cache["v"], vc, 0, axis=1)}
    with jax.named_scope("attention"):
        out = _dispatch(q, k, v, kind, causal, window)
    return out, new_cache


def _dispatch(q, k, v, kind, causal, window):
    # Shard the full-sequence attention on the kv-head dim ("kv_heads" ->
    # "model" in the launcher's rules).  Without this GSPMD replicates the
    # (B, KV, G, L, L)-blocked score tensors across the model axis — the
    # llama3.2-1b train cell paid 7.4 TB/device of HBM traffic (§Perf
    # iteration 2).  Decode keeps its KV-sequence sharding instead.
    q = constrain(q, "batch", None, "kv_heads", None, None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    if kind == "full":
        return flash_attention(q, k, v, causal=causal)
    if kind == "cross":
        return flash_attention(q, k, v, causal=False)
    if kind == "local":
        return local_attention(q, k, v, window=window, lookback=1)
    if kind == "chunked":
        return local_attention(q, k, v, window=window, lookback=0)
    raise ValueError(f"unknown attention kind {kind!r}")
