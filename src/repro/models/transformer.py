"""Transformer LM assembly: scan-stacked blocks, enc-dec, caches, quantized
serving.

Layer stacking: the repeating block ``pattern`` (P positions) is scan-stacked
-- params for pattern position j are stacked (S, ...) over S = n_layers // P
superblocks and iterated with ``lax.scan`` (compact HLO at 94-layer scale);
the n_layers % P remainder is an unscanned tail.  Caches and pools stack
the same way, one (S_run, ...) run per stretch of superblocks sharing a kv
wire format (:func:`stack_runs`).

Public surface:
  init_params(cfg, key[, qcfg])          -> params (packed when qcfg given)
  forward(params, cfg, batch, policy)    -> (logits, aux)      [train path]
  init_cache(cfg, batch, max_len)        -> cache
  prefill(params, cfg, batch, cache,
          policy)                        -> (logits, cache)
  decode_step(params, cfg, tokens, cache,
              policy)                    -> (logits, cache)
  paged_decode_step(params, cfg, tokens, pages,
                    page_table, pos, policy) -> (logits, pages)
  quantize_params(params, cfg, qcfg)     -> params with QWeight leaves
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import attention, layers, mamba2, mlp, moe, rglru
from .config import ModelConfig
from .layers import PlanPolicy, QuantPolicy, NO_QUANT
from repro.core import kvwire, schemes
from repro.distributed.actshard import constrain
from repro.kernels import ops as kops


def _base_policy(policy):
    """Collapse a per-layer PlanPolicy to its uniform base (encoder/embed)."""
    if isinstance(policy, PlanPolicy):
        return QuantPolicy(policy.mode, policy.base_cfg, policy.backend)
    return policy


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def _norm_init(cfg, dtype):
    if cfg.norm_kind == "layer":
        return layers.layernorm_init(cfg.d_model, dtype)
    return layers.rmsnorm_init(cfg.d_model, dtype)


def _norm_apply(cfg, p, x):
    if cfg.norm_kind == "layer":
        return layers.layernorm_apply(p, x)
    return layers.rmsnorm_apply(p, x)


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig, spec, *, cross: bool = False,
               dtype=jnp.float32):
    mixer, ffn = spec
    ks = jax.random.split(key, 4)
    p = {"norm1": _norm_init(cfg, dtype)}
    if mixer.startswith("attn"):
        p["mixer"] = attention.attn_init(
            ks[0], d_model=cfg.d_model, n_heads=cfg.n_heads,
            n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim, qk_norm=cfg.qk_norm,
            bias=cfg.attn_bias, dtype=dtype)
    elif mixer == "mamba2":
        p["mixer"] = mamba2.mamba2_init(
            ks[0], d_model=cfg.d_model, d_state=cfg.ssm_state,
            head_dim=cfg.ssm_head_dim, expand=cfg.ssm_expand,
            n_groups=cfg.ssm_groups, conv_kernel=cfg.conv_kernel, dtype=dtype)
    elif mixer == "rglru":
        p["mixer"] = rglru.rglru_init(
            ks[0], d_model=cfg.d_model, width=cfg.lru_width,
            conv_kernel=cfg.conv_kernel, dtype=dtype)
    else:
        raise ValueError(f"unknown mixer {mixer!r}")

    if cross:
        p["norm_cross"] = _norm_init(cfg, dtype)
        p["cross"] = attention.attn_init(
            ks[1], d_model=cfg.d_model, n_heads=cfg.n_heads,
            n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            bias=cfg.attn_bias, dtype=dtype)

    if ffn != "none":
        p["norm2"] = _norm_init(cfg, dtype)
        if ffn == "moe":
            p["ffn"] = moe.moe_init(ks[2], d_model=cfg.d_model,
                                    d_ff=cfg.moe_d_ff,
                                    n_experts=cfg.n_experts,
                                    n_shared_ff=cfg.shared_ff, dtype=dtype)
        else:
            p["ffn"] = mlp.ffn_init(ks[2], ffn, cfg.d_model, cfg.d_ff, dtype)
    return p


def _attn_kind(mixer: str):
    return {"attn": ("full", True, None),
            "attn_nc": ("full", False, None),
            "attn_local": ("local", True, "window"),
            "attn_chunked": ("chunked", True, "chunk")}[mixer]


def _aux_zero(cfg: ModelConfig) -> dict:
    """A block's auxiliary outputs, summed over the stack: the MoE
    load-balancing ``loss``, and in a model with MoE layers the routing
    counts ``experts_hit`` (experts given a row) and ``expert_rows_max``
    (the most rows one expert was given)."""
    aux = {"loss": jnp.zeros((), jnp.float32)}
    if any(f == "moe" for _, f in cfg.pattern):
        aux["experts_hit"] = jnp.zeros((), jnp.int32)
        aux["expert_rows_max"] = jnp.zeros((), jnp.int32)
    return aux


def _aux_add(a: dict, b: dict) -> dict:
    return jax.tree.map(jnp.add, a, b)


def block_apply(p, x, spec, cfg: ModelConfig, *, policy: QuantPolicy,
                cache=None, cache_pos=None, enc_out=None, positions=None,
                page_table=None, page_size=None, layer=None, fused=None):
    """Returns (x, new_cache, aux) with ``aux`` as :func:`_aux_zero`.
    ``layer``: this block's index in the scan-stacked paged pool leaves
    ``cache`` carries whole (``attention.attn_apply``)."""
    mixer, ffn = spec
    aux = _aux_zero(cfg)
    new_cache = dict(cache) if cache is not None else None

    # layer-kind scopes (norm / ffn here; qkv / kv_write / attention /
    # attn_out in attention.attn_apply) are HLO metadata only: a device
    # trace attributes each op to the kind of work it does
    with jax.named_scope("norm"):
        h = _norm_apply(cfg, p["norm1"], x)
    if mixer.startswith("attn"):
        kind, causal, wattr = _attn_kind(mixer)
        window = getattr(cfg, wattr) if wattr else None
        self_cache = cache.get("self") if cache else None
        out, sc = attention.attn_apply(
            p["mixer"], h, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.head_dim, kind=kind, causal=causal, window=window,
            qk_norm=cfg.qk_norm, rope=cfg.rope, rope_theta=cfg.rope_theta,
            yarn=cfg.yarn if mixer == "attn" else (),
            positions=positions, cache=self_cache, cache_pos=cache_pos,
            page_table=page_table, page_size=page_size, layer=layer,
            fused=fused, policy=policy)
        if cache is not None:
            new_cache["self"] = sc
    elif mixer == "mamba2":
        out, sc = mamba2.mamba2_apply(
            p["mixer"], h, d_state=cfg.ssm_state, head_dim=cfg.ssm_head_dim,
            expand=cfg.ssm_expand, n_groups=cfg.ssm_groups,
            conv_kernel=cfg.conv_kernel, chunk=cfg.ssd_chunk,
            cache=cache.get("self") if cache else None, policy=policy)
        if cache is not None:
            new_cache["self"] = sc
    else:  # rglru
        out, sc = rglru.rglru_apply(
            p["mixer"], h, conv_kernel=cfg.conv_kernel,
            cache=cache.get("self") if cache else None, policy=policy)
        if cache is not None:
            new_cache["self"] = sc
    x = x + out

    if "cross" in p:
        with jax.named_scope("norm"):
            h = _norm_apply(cfg, p["norm_cross"], x)
        ccache = cache.get("cross") if cache else None
        if ccache is not None and enc_out is None:
            # decode: attend over precomputed encoder K/V
            b, l, _ = h.shape
            g = cfg.n_heads // cfg.n_kv_heads
            q = layers.dense_apply(p["cross"]["wq"], h, policy).reshape(
                b, l, cfg.n_kv_heads, g, cfg.head_dim)
            out = attention.decode_attention(
                q, ccache["k"], ccache["v"], ccache["k"].shape[1] - 1)
            out = out.reshape(b, l, cfg.n_heads * cfg.head_dim)
            out = layers.dense_apply(p["cross"]["wo"], out, policy)
        else:
            out, _ = attention.attn_apply(
                p["cross"], h, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                head_dim=cfg.head_dim, kind="cross", kv_src=enc_out,
                rope=False, policy=policy)
            if cache is not None:
                # prefill: persist encoder K/V for decode
                b = enc_out.shape[0]
                lk = enc_out.shape[1]
                k = layers.dense_apply(p["cross"]["wk"], enc_out, policy
                                       ).reshape(b, lk, cfg.n_kv_heads,
                                                 cfg.head_dim)
                v = layers.dense_apply(p["cross"]["wv"], enc_out, policy
                                       ).reshape(b, lk, cfg.n_kv_heads,
                                                 cfg.head_dim)
                new_cache["cross"] = {"k": k.astype(ccache["k"].dtype),
                                      "v": v.astype(ccache["v"].dtype)}
        x = x + out

    if ffn != "none":
        with jax.named_scope("norm"):
            h = _norm_apply(cfg, p["norm2"], x)
        with jax.named_scope("ffn"):
            if ffn == "moe":
                out, aux = moe.moe_apply(
                    p["ffn"], h, n_experts=cfg.n_experts, top_k=cfg.top_k,
                    capacity_factor=cfg.capacity_factor, policy=policy)
            else:
                out = mlp.ffn_apply(p["ffn"], h, ffn, policy)
        x = x + out
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# block cache construction
# ---------------------------------------------------------------------------

def _block_cache(cfg: ModelConfig, spec, batch: int, max_len: int,
                 cross: bool, dtype, kv_quant=None, whole: bool = False):
    mixer, _ = spec
    c = {}
    if mixer.startswith("attn"):
        if mixer == "attn_local" and not whole:
            s = min(max_len, cfg.window)
        elif mixer == "attn_chunked":
            s = min(max_len, cfg.chunk)
        else:
            s = max_len
        kv = (batch, s, cfg.n_kv_heads, cfg.head_dim)
        if kv_quant is not None:
            # LQ-quantized KV cache (paper's runtime input quantization
            # mapped to serving; core/kvwire.py wire format)
            bits, gs = kv_quant
            c["self"] = {"k": kvwire.make_quant_kv(kv, bits, gs),
                         "v": kvwire.make_quant_kv(kv, bits, gs)}
        else:
            c["self"] = {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
    elif mixer == "mamba2":
        c["self"] = mamba2.mamba2_init_cache(
            batch, d_model=cfg.d_model, d_state=cfg.ssm_state,
            head_dim=cfg.ssm_head_dim, expand=cfg.ssm_expand,
            n_groups=cfg.ssm_groups, conv_kernel=cfg.conv_kernel, dtype=dtype,
            state_quant=kv_quant)
    else:
        c["self"] = rglru.rglru_init_cache(
            batch, width=cfg.lru_width or cfg.d_model,
            conv_kernel=cfg.conv_kernel, dtype=dtype)
    if cross:
        kv = (batch, cfg.enc_len, cfg.n_kv_heads, cfg.head_dim)
        c["cross"] = {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
    return c


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------

def _stack_init(key, cfg: ModelConfig, pattern, n_layers: int, *,
                cross: bool, dtype, qcfg=None):
    """Scan-stacked block params.  With ``qcfg`` each layer is drawn and
    packed by one jitted call before the next is drawn, so only one
    layer's fp masters are ever live; the packed layers are stacked after.
    """
    p_len = len(pattern)
    n_super, n_tail = n_layers // p_len, n_layers % p_len
    keys = jax.random.split(key, n_layers + 1)

    def init_fn(spec):
        one = functools.partial(block_init, cfg=cfg, spec=spec, cross=cross,
                                dtype=dtype)
        if qcfg is None:
            return one
        return jax.jit(lambda k: _quantize_tree(one(k), qcfg))

    supers = []
    for j, spec in enumerate(pattern):
        layer_keys = [keys[s * p_len + j] for s in range(n_super)]
        if qcfg is None:
            supers.append(jax.vmap(init_fn(spec))(jnp.stack(layer_keys)))
        else:
            init_one = init_fn(spec)
            packed = [init_one(k) for k in layer_keys]
            supers.append(jax.tree.map(lambda *a: jnp.stack(a), *packed))
    tail = [init_fn(pattern[(n_super * p_len + t) % p_len])(
                keys[n_super * p_len + t])
            for t in range(n_tail)]
    return {"super": tuple(supers), "tail": tail}


def _maybe_remat(fn, cfg: ModelConfig, training: bool):
    if not training or cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def _scan_run(blk_params, x, aux, cfg: ModelConfig, pattern, policies, *,
              caches=None, pool=None, layer0: int = 0, training=False,
              **kw):
    """One ``lax.scan`` over a run of scan-stacked superblocks: pattern
    position j runs ``blk_params[j]`` under ``policies[j]``.

    ``caches`` (stacked like the params) are sliced by the scan and
    stacked back as its outputs.  A paged ``pool`` rides in the carry
    whole instead: superblock i writes and reads layer ``layer0 + i`` of
    each stacked page leaf in place, so no layer's pages are sliced out
    of the stack, relaid, or stacked back.  Returns (x, aux, the new
    caches or pool).
    """
    def body(carry, xs):
        xx, aux_acc, carried = carry
        blk_p, blk_c, layer = xs
        cs = carried if carried is not None else blk_c
        new = []
        for j, spec in enumerate(pattern):
            xx, nc, a = block_apply(blk_p[j], xx, spec, cfg,
                                    policy=policies[j],
                                    cache=cs[j] if cs is not None else None,
                                    layer=layer, **kw)
            xx = constrain(xx, "batch", "seq", "embed")
            aux_acc = _aux_add(aux_acc, a)
            new.append(nc)
        new = tuple(new) if cs is not None else None
        if carried is not None:
            return (xx, aux_acc, new), None
        return (xx, aux_acc, None), new

    body = _maybe_remat(body, cfg, training)
    layers = None
    if pool is not None:
        n = jax.tree.leaves(blk_params)[0].shape[0]
        layers = layer0 + jnp.arange(n, dtype=jnp.int32)
    (x, aux, new_pool), ys = jax.lax.scan(body, (x, aux, pool),
                                          (blk_params, caches, layers))
    return x, aux, new_pool if pool is not None else ys


def plan_segments(configs, p_len: int, n_super: int) -> list:
    """Group consecutive superblocks whose per-position configs match.

    Returns ``[(start_super, size, per_position_cfgs), ...]`` — the
    maximal runs a single scan body can cover, so a mostly-uniform plan
    stays nearly as compact as one scan.  ``configs`` entries may be any
    hashable per-layer key: plain :class:`QuantConfig` for a weight-only
    plan, or ``(QuantConfig, kv_bits)`` pairs when the plan also assigns
    per-layer cache bitwidths — a segment must be uniform in *both* so
    its stacked cache leaves share one wire shape.
    """
    return kvwire.segment_runs(configs, p_len, n_super)


def stack_runs(tree) -> list:
    """The runs of scan-stacked superblocks of a decoder-stack tree, in
    layer order: each a tuple of per-pattern-position subtrees whose
    leaves stack the run's superblocks first.

    Uniform params spell their one run ``{"super": run}``; plan-packed
    params, and every cache and pool, list theirs under
    ``"super_segments"`` (a uniform cache or pool is one run).
    """
    if "super" in tree:
        return [tree["super"]]
    return list(tree["super_segments"])


def _run_sizes(runs) -> list:
    return [jax.tree.leaves(run)[0].shape[0] for run in runs]


def _run_of(sizes, start: int, size: int, what: str):
    """The run holding superblocks [start, start+size), and start's
    offset in it."""
    run_start = 0
    for r, n in enumerate(sizes):
        if run_start <= start and start + size <= run_start + n:
            return r, start - run_start
        run_start += n
    raise ValueError(
        f"walker segment [{start}, {start + size}) straddles the {what}'s "
        f"runs of {sizes} superblocks — plan/{what} mismatch")


def _rows(run, off: int, size: int, whole: int):
    if size == whole:
        return run
    return jax.tree.map(lambda a: a[off:off + size], run)


def _concat(parts):
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *leaves: jnp.concatenate(leaves, axis=0),
                        *parts)


def _stack_apply(params, x, cfg: ModelConfig, pattern, *, policy,
                 caches=None, cache_pos=None, enc_out=None, positions=None,
                 page_table=None, page_size=None, fused=None,
                 training=False):
    """Run the scan-stacked superblocks and the tail.  Returns (x, caches,
    aux).

    One ``lax.scan`` per segment — a run of superblocks alike in weight
    config and cache bits — so a uniform :class:`QuantPolicy` is one scan
    and a per-layer :class:`PlanPolicy` one per segment.  A segment reads
    the param run and the cache run that hold it (:func:`stack_runs`),
    sliced only where it is shorter than the run.  A paged pool
    (``page_table`` given) is never sliced: the segment carries the whole
    run from its first layer (:func:`_scan_run`).
    """
    p_len = len(pattern)
    param_runs = stack_runs(params)
    param_sizes = _run_sizes(param_runs)
    n_super = sum(param_sizes)
    n_layers = n_super * p_len + len(params["tail"])
    if isinstance(policy, PlanPolicy):
        if policy.n_layers != n_layers:
            raise ValueError(f"policy covers {policy.n_layers} layers but "
                             f"params carry {n_layers}")
        per_layer = [policy.layer(i) for i in range(n_layers)]
    else:
        per_layer = [policy] * n_layers
    kv = getattr(policy, "kv_bits", ()) or (None,) * n_layers
    segs = plan_segments([(pol.cfg, kv[i]) for i, pol in
                          enumerate(per_layer)], p_len, n_super)
    if len(param_runs) > 1 and len(segs) != len(param_runs):
        raise ValueError(
            f"policy implies {len(segs)} segments but params carry "
            f"{len(param_runs)} — plan/params mismatch")

    kw = dict(cache_pos=cache_pos, enc_out=enc_out, positions=positions,
              page_table=page_table, page_size=page_size, fused=fused)
    paged = page_table is not None
    aux_total = _aux_zero(cfg)
    cache_runs = stack_runs(caches) if caches is not None else []
    cache_sizes = _run_sizes(cache_runs)
    new_parts = [[] for _ in cache_runs]
    for start, size, _ in segs:
        r, off = _run_of(param_sizes, start, size, "params")
        seg_params = _rows(param_runs[r], off, size, param_sizes[r])
        seg_caches = pool = None
        c = c_off = 0
        if caches is not None:
            c, c_off = _run_of(cache_sizes, start, size, "cache")
            if paged:
                pool = cache_runs[c]
            else:
                seg_caches = _rows(cache_runs[c], c_off, size, cache_sizes[c])
        # the scan's own ops (slicing each layer's weights, and a
        # contiguous cache, out of the stack; stacking the new cache) sit
        # under layer_scan; the body's ops under their layer kind's scope
        with jax.named_scope("layer_scan"):
            x, aux_total, new = _scan_run(
                seg_params, x, aux_total, cfg, pattern,
                tuple(per_layer[start * p_len:(start + 1) * p_len]),
                caches=seg_caches, pool=pool, layer0=c_off,
                training=training, **kw)
        if pool is not None:
            cache_runs[c] = new
        elif caches is not None:
            new_parts[c].append(new)
    if caches is not None and not paged:
        cache_runs = [_concat(parts) if parts else cache_runs[c]
                      for c, parts in enumerate(new_parts)]

    new_tail = []
    for t, tp in enumerate(params["tail"]):
        ct = caches["tail"][t] if caches is not None else None
        x, nc, aux = block_apply(tp, x, pattern[t % p_len], cfg,
                                 policy=per_layer[n_super * p_len + t],
                                 cache=ct, **kw)
        aux_total = _aux_add(aux_total, aux)
        new_tail.append(nc)

    new_caches = None
    if caches is not None:
        new_caches = {"super_segments": cache_runs, "tail": new_tail}
    return x, new_caches, aux_total


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key, qcfg=None) -> dict:
    """fp32 master params.  ``qcfg`` (a :class:`QuantConfig`) returns
    ``quantize_params(init_params(cfg, key), cfg, qcfg)`` instead (equal
    up to float rounding of scale/zmin), built one decoder layer at a
    time: a published-width model then never holds its fp32 masters next
    to the packed copy (granite-3-2b: ~10 GB of masters against ~1.4 GB
    packed at 4 bits on a 16 GB chip)."""
    dtype = jnp.float32  # master params; compute casts to cfg.dtype
    ks = jax.random.split(key, 8)
    p = {"embed": layers.embed_init(ks[0], cfg.padded_vocab, cfg.d_model,
                                    dtype),
         "final_norm": _norm_init(cfg, dtype)}
    cross = cfg.n_enc_layers > 0
    p["decoder"] = _stack_init(ks[1], cfg, cfg.pattern, cfg.n_layers,
                               cross=cross, dtype=dtype, qcfg=qcfg)
    if not cfg.tie_embeddings:
        p["lm_head"] = layers.dense_init(ks[2], cfg.d_model,
                                         cfg.padded_vocab, dtype=dtype)
    if cfg.pos_embed == "learned":
        p["pos"] = layers.posembed_init(ks[3], cfg.max_seq, cfg.d_model,
                                        dtype)
    if cross:
        enc_pattern = (("attn_nc", cfg.ffn_kind),)
        p["encoder"] = _stack_init(ks[4], cfg, enc_pattern, cfg.n_enc_layers,
                                   cross=False, dtype=dtype)
        p["enc_norm"] = _norm_init(cfg, dtype)
        p["enc_pos"] = layers.posembed_init(ks[5], cfg.enc_len, cfg.d_model,
                                            dtype)
    if cfg.frontend != "none":
        fdim = cfg.frontend_dim or cfg.d_model
        p["frontend"] = layers.dense_init(ks[6], fdim, cfg.d_model,
                                          dtype=dtype)
    if qcfg is not None:
        p = {k: v if k == "decoder" else _quantize_tree(v, qcfg)
             for k, v in p.items()}
    return p


def encode(params, cfg: ModelConfig, frames, *, policy=NO_QUANT,
           training=False):
    """Whisper-style encoder: frames (B, enc_len, frontend_dim) -> states."""
    policy = _base_policy(policy)      # plans cover the decoder stack only
    x = layers.dense_apply(params["frontend"], frames, policy)
    x = layers.posembed_apply(params["enc_pos"], x)
    x = x.astype(cfg.activation_dtype)
    enc_pattern = (("attn_nc", cfg.ffn_kind),)
    x, _, _ = _stack_apply(params["encoder"], x, cfg, enc_pattern,
                           policy=policy, training=training)
    return _norm_apply(cfg, params["enc_norm"], x)


def _embed_inputs(params, cfg: ModelConfig, batch, policy):
    """Token embedding (+ VLM patch prefix).  Returns (x, n_prefix)."""
    x = layers.embed_apply(params["embed"], batch["tokens"])
    n_prefix = 0
    if cfg.frontend == "patch_stub":
        patches = layers.dense_apply(params["frontend"], batch["patches"],
                                     policy)
        x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
        n_prefix = patches.shape[1]
    return x, n_prefix


def forward(params, cfg: ModelConfig, batch, *, policy: QuantPolicy = NO_QUANT,
            training: bool = True):
    """Full-sequence forward (training / eval).  Returns (logits, aux).

    batch: {'tokens': (B, L) int32} + optional 'frames' (audio) /
    'patches' (VLM).
    """
    enc_out = None
    if cfg.n_enc_layers:
        enc_out = encode(params, cfg, batch["frames"], policy=policy,
                         training=training)
    x, _ = _embed_inputs(params, cfg, batch, policy)
    if cfg.pos_embed == "learned":
        x = layers.posembed_apply(params["pos"], x)
    x = constrain(x.astype(cfg.activation_dtype), "batch", "seq", "embed")
    x, _, aux = _stack_apply(params["decoder"], x, cfg, cfg.pattern,
                             policy=policy, enc_out=enc_out,
                             training=training)
    x = _norm_apply(cfg, params["final_norm"], x)
    logits = _logits(params, cfg, x, policy)
    return logits, aux["loss"]


def _logits(params, cfg: ModelConfig, x, policy):
    if cfg.tie_embeddings:
        logits = layers.embed_logits(params["embed"], x, cfg.vocab_size)
    else:
        logits = layers.dense_apply(params["lm_head"], x, policy)
        if cfg.vocab_size < cfg.padded_vocab:
            logits = logits.at[..., cfg.vocab_size:].set(-1e9)
    # vocab dim sharded over "model": a replicated (B, L, V) fp32 buffer is
    # ~34 GiB/device at train_4k scale (dry-run iteration 1, §Perf)
    return constrain(logits.astype(jnp.float32), "batch", "seq", "vocab")


def normalize_kv_quant(cfg: ModelConfig, kv_quant) -> tuple:
    """The per-layer form of a cache-quantization spec.

    ``kv_quant`` is ``None`` (fp), ``(bits, group_size)`` (every layer),
    or ``(per_layer_bits, group_size)`` with a length-``n_layers``
    sequence of ``bits | None`` entries.  Returns ``(per_layer_bits,
    group_size)`` with ``None`` for an fp layer.
    """
    bits, gs = kv_quant if kv_quant is not None else (None, None)
    if not isinstance(bits, (tuple, list)):
        bits = (bits,) * cfg.n_layers
    elif len(bits) != cfg.n_layers:
        raise ValueError(f"per-layer kv_bits has {len(bits)} entries "
                         f"for {cfg.n_layers} layers")
    for b in bits:
        kvwire.check_kv_bits(b)
    return tuple(bits), gs


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None, kv_quant=None, whole: bool = False) -> dict:
    """Decode cache ``{"super_segments": [run, ...], "tail": [...],
    "pos": ()}``.  ``kv_quant=(bits, group_size)`` stores attention K/V
    in the LQ wire format (bits in {8,4,2,1}; group_size divides
    head_dim); ``bits`` may be a per-layer sequence (see
    :func:`normalize_kv_quant`).  Superblocks stack per run of identical
    kv bits — packed wire shapes differ across bitwidths — so a uniform
    spec is one run.  ``whole`` gives sliding-window layers every one of
    ``max_len`` positions instead of a ``window``-long ring (the paged
    prefill's bucket, scattered page for page into the pool).
    """
    dtype = dtype or cfg.activation_dtype
    cross = cfg.n_enc_layers > 0
    bits, gs = normalize_kv_quant(cfg, kv_quant)
    p_len = len(cfg.pattern)

    def block(spec, b, stack=None):
        one = _block_cache(cfg, spec, batch, max_len, cross, dtype,
                           None if b is None else (b, gs), whole)
        if stack is None:
            return one
        return jax.tree.map(
            lambda a: jnp.zeros((stack,) + a.shape, a.dtype), one)

    n0 = cfg.n_super * p_len
    tail = [block(cfg.pattern[(n0 + t) % p_len], bits[n0 + t])
            for t in range(cfg.n_tail)]
    out = {"tail": tail, "pos": jnp.zeros((), jnp.int32)}
    out["super_segments"] = [
        tuple(block(spec, key[j], size) for j, spec in enumerate(cfg.pattern))
        for _, size, key in plan_segments(list(bits), p_len, cfg.n_super)]
    return out


def prefill(params, cfg: ModelConfig, batch, cache, *,
            policy: QuantPolicy = NO_QUANT, logits_pos=None):
    """Process the prompt, filling the cache.  Returns (logits_last, cache).

    ``logits_pos`` (traced scalar) selects which position's logits to
    return instead of the last — right-padded prompts (continuous-batching
    prefill buckets) read logits at their true last token; causal masking
    makes positions < logits_pos independent of the pad tail.
    """
    enc_out = None
    if cfg.n_enc_layers:
        enc_out = encode(params, cfg, batch["frames"], policy=policy)
    x, _ = _embed_inputs(params, cfg, batch, policy)
    if cfg.pos_embed == "learned":
        x = layers.posembed_apply(params["pos"], x)
    x = x.astype(cfg.activation_dtype)
    l = x.shape[1]
    # named scopes are HLO metadata only (no numerics / retrace impact):
    # they label phases in xprof captures (repro.obs.profile)
    with jax.named_scope("prefill"):
        x, new_caches, _ = _stack_apply(
            params["decoder"], x, cfg, cfg.pattern, policy=policy,
            caches=cache,
            cache_pos=None, enc_out=enc_out, positions=None)
    if logits_pos is None:
        x = x[:, -1:]
    else:
        x = jax.lax.dynamic_slice_in_dim(x, logits_pos, 1, axis=1)
    with jax.named_scope("lm_head"):
        x = _norm_apply(cfg, params["final_norm"], x)
        logits = _logits(params, cfg, x, policy)
    new_caches["pos"] = jnp.asarray(l, jnp.int32)
    return logits, new_caches


def decode_step(params, cfg: ModelConfig, tokens, cache, *,
                policy: QuantPolicy = NO_QUANT):
    """One decode step.  tokens (B, 1) int32.  Returns (logits, cache)."""
    pos = cache["pos"]
    x = layers.embed_apply(params["embed"], tokens)
    if cfg.pos_embed == "learned":
        x = layers.posembed_apply(params["pos"], x, offset=pos)
    x = x.astype(cfg.activation_dtype)
    with jax.named_scope("decode_step"):
        x, new_caches, _ = _stack_apply(
            params["decoder"], x, cfg, cfg.pattern, policy=policy,
            caches=cache,
            cache_pos=pos, enc_out=None, positions=None)
    with jax.named_scope("lm_head"):
        x = _norm_apply(cfg, params["final_norm"], x)
        logits = _logits(params, cfg, x, policy)
    new_caches["pos"] = pos + 1
    return logits, new_caches


def paged_decode_step(params, cfg: ModelConfig, tokens, pages, page_table,
                      pos, *, page_size: int, policy: QuantPolicy = NO_QUANT,
                      fused=None):
    """One continuous-batching decode step over a paged KV pool.

    tokens (B, 1) int32; pages {'super_segments': [run, ...], 'tail': ...}
    with shared page leaves in the stored layout (``core/kvwire.py``:
    (n_pages, ...) per layer, a run's superblocks stacked ahead of that),
    updated in place; page_table (B, P) int32 physical page ids per slot
    (scratch page 0 pads unused entries); pos (B,) int32 — the absolute
    position each slot's token is written at; ``page_size`` the pool's
    tokens per page.  Inactive slots point at the scratch page and are
    masked by the caller.  ``fused`` ('pallas' | 'interpret' | None)
    routes every layer's attention through the fused paged kernel
    instead of gather+dequant.
    Returns (logits (B, 1, V), new pages, aux): ``aux`` as
    :func:`_aux_zero`, summed over the layers.
    """
    if cfg.pos_embed == "learned":
        raise ValueError("paged decode needs per-slot positions; learned "
                         "positional embeddings are not supported")
    x = layers.embed_apply(params["embed"], tokens)
    x = x.astype(cfg.activation_dtype)
    with jax.named_scope("paged_decode_step"):
        x, new_pages, aux = _stack_apply(
            params["decoder"], x, cfg, cfg.pattern, policy=policy,
            caches=pages,
            cache_pos=pos, enc_out=None, positions=pos[:, None],
            page_table=page_table, page_size=page_size, fused=fused)
    with jax.named_scope("lm_head"):
        x = _norm_apply(cfg, params["final_norm"], x)
        logits = _logits(params, cfg, x, policy)
    return logits, new_pages, aux


def paged_decode_multi(params, cfg: ModelConfig, tokens, pages, page_table,
                       pos, *, page_size: int, policy: QuantPolicy = NO_QUANT,
                       fused=None):
    """Length-L batched decode over the paged pool — the speculative
    verify step (one compiled forward scores all L candidate tokens).

    tokens (B, L) int32 — slot b's candidate run, whose token i sits at
    absolute position ``pos[b] + i``; pages / page_table / pos as in
    :func:`paged_decode_step`.  Every layer scatters all L tokens' K/V
    into the slot's pages, then attends causally (query i over cache
    positions ``<= pos + i``, which includes candidates 0..i).  Returns
    (logits (B, L, V), new pages) — logits at *every* position, so the
    caller can greedy-score the whole run and accept the longest matching
    prefix.  L == 1 reduces exactly to :func:`paged_decode_step`.
    """
    if cfg.pos_embed == "learned":
        raise ValueError("paged decode needs per-slot positions; learned "
                         "positional embeddings are not supported")
    l = tokens.shape[1]
    x = layers.embed_apply(params["embed"], tokens)
    x = x.astype(cfg.activation_dtype)
    positions = pos[:, None] + jnp.arange(l)[None]
    with jax.named_scope("paged_decode_multi"):
        x, new_pages, _ = _stack_apply(
            params["decoder"], x, cfg, cfg.pattern, policy=policy,
            caches=pages,
            cache_pos=pos, enc_out=None, positions=positions,
            page_table=page_table, page_size=page_size, fused=fused)
    with jax.named_scope("lm_head"):
        x = _norm_apply(cfg, params["final_norm"], x)
        logits = _logits(params, cfg, x, policy)
    return logits, new_pages


# ---------------------------------------------------------------------------
# quantized serving params (the paper's technique as deployment format)
# ---------------------------------------------------------------------------

_EXCLUDE_KEYS = {"router"}          # fp32-sensitive leaves


def _quantize_tree(tree, qcfg: schemes.QuantConfig):
    """Pack every Dense weight in ``tree`` under one QuantConfig."""
    if qcfg.w_bits is None:
        return tree
    bits, gs = qcfg.w_bits, qcfg.group_size

    def quant_w(w):
        if w.ndim == 2:
            return kops.quantize_weight(w, bits, gs)
        # stacked: (S, K, N) or (S, E, K, N) or (E, K, N)
        from repro.kernels import ref as kref
        flat = w.reshape((-1,) + w.shape[-2:])
        packed, scale, zmin = jax.vmap(
            lambda ww: kref.quantize_weight(ww, bits, gs))(flat)
        lead = w.shape[:-2]
        return kops.QWeight(
            packed=packed.reshape(lead + packed.shape[1:]),
            scale=scale.reshape(lead + scale.shape[1:]),
            zmin=zmin.reshape(lead + zmin.shape[1:]),
            bits=bits, group_size=gs, k=w.shape[-2], n=w.shape[-1])

    def walk(tree, path=()):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                if k in _EXCLUDE_KEYS:
                    out[k] = v
                elif k == "w" and hasattr(v, "ndim") and v.ndim >= 2 \
                        and v.shape[-2] % gs == 0:
                    out[k] = quant_w(v)
                elif k in ("wi_gate", "wi_up", "wo") and hasattr(v, "ndim") \
                        and not isinstance(v, dict) and v.ndim >= 3 \
                        and v.shape[-2] % gs == 0:
                    out[k] = quant_w(v)       # MoE expert stacks
                else:
                    out[k] = walk(v, path + (k,))
            return out
        if isinstance(tree, (list, tuple)):
            t = [walk(v, path + (i,)) for i, v in enumerate(tree)]
            return type(tree)(t) if isinstance(tree, tuple) else t
        return tree

    return walk(tree)


def quantize_params(params, cfg: ModelConfig, qcfg, *,
                    leaf_cache: dict | None = None) -> dict:
    """Replace Dense weights with packed :class:`QWeight` (local quantization
    regions along the contraction axis).  Stacked (scan) and expert weights
    are quantized with vmap; norms / router / conv / scalar leaves stay fp.

    ``qcfg`` is either one :class:`QuantConfig` applied uniformly to the
    whole tree, or a :class:`repro.plan.QuantPlan` (anything exposing
    ``resolve(cfg)``): decoder layers are packed per the plan, with
    consecutive identically-configured superblocks re-stacked into
    ``super_segments`` so the stack walker keeps one compiled body per
    segment; non-decoder leaves (embed / lm_head / encoder) stay fp.

    ``leaf_cache`` dedups packed leaves across plans over ONE shared base
    checkpoint: segment subtrees are keyed on ``(start, size, position,
    QuantConfig)`` and re-used (same device buffers) when another plan
    produced the identical segment — the mechanism behind draft/verifier
    weight sharing in ``repro.spec`` and cross-tenant sharing in
    ``repro.fleet``.  Callers must pass one cache per base checkpoint;
    keys do not capture the fp params' identity.
    """
    if hasattr(qcfg, "resolve"):               # QuantPlan (duck-typed)
        return _quantize_params_plan(params, cfg, qcfg,
                                     leaf_cache=leaf_cache)
    return _quantize_tree(params, qcfg)


def is_quantized_params(params) -> bool:
    """Whether ``params`` already carry plan-packed decoder segments."""
    dec = params.get("decoder", {}) if isinstance(params, dict) else {}
    return "super_segments" in dec


def plan_leaf_keys(cfg: ModelConfig, plan) -> list:
    """The ``leaf_cache`` keys ``quantize_params(plan)`` reads/writes.

    One key per (segment, pattern position) stacked subtree plus one per
    tail layer; two plans share a packed leaf exactly when they produce
    the same key (same superblock range, position, and weight config) —
    kv bitwidths shape the segment *boundaries* but not the packed
    contents, so they appear only through the ranges.  This is how
    ``repro.spec`` counts draft/verifier sharing and ``repro.fleet``
    prices deduped tenants.
    """
    configs = plan.resolve(cfg)
    kv = (plan.resolve_kv(cfg) if hasattr(plan, "resolve_kv")
          else (None,) * cfg.n_layers)
    p_len = len(cfg.pattern)
    segs = plan_segments(list(zip(configs, kv)), p_len, cfg.n_super)
    keys = [("super", start, size, j, seg_key[j][0])
            for start, size, seg_key in segs for j in range(p_len)]
    keys += [("tail", t, configs[cfg.n_super * p_len + t])
             for t in range(cfg.n_tail)]
    return keys


def _quantize_params_plan(params, cfg: ModelConfig, plan, *,
                          leaf_cache: dict | None = None) -> dict:
    configs = plan.resolve(cfg)
    kv = (plan.resolve_kv(cfg) if hasattr(plan, "resolve_kv")
          else (None,) * cfg.n_layers)
    p_len = len(cfg.pattern)
    dec = params["decoder"]

    def cached(key, make):
        if leaf_cache is None:
            return make()
        if key not in leaf_cache:
            leaf_cache[key] = make()
        return leaf_cache[key]

    # segment on the combined (weight, kv) key so param segments line up
    # with the stack walker's — a kv boundary splits the scan even when
    # the weight scheme is unchanged across it
    segs = plan_segments(list(zip(configs, kv)), p_len, cfg.n_super)
    seg_trees = []
    for start, size, seg_key in segs:
        pos_trees = []
        for j in range(p_len):
            def make(start=start, size=size, j=j, qc=seg_key[j][0]):
                sub = jax.tree.map(lambda a: a[start:start + size],
                                   dec["super"][j])
                return _quantize_tree(sub, qc)
            pos_trees.append(cached(("super", start, size, j,
                                     seg_key[j][0]), make))
        seg_trees.append(tuple(pos_trees))
    tail = [cached(("tail", t, configs[cfg.n_super * p_len + t]),
                   lambda t=t, blk=blk, qc=configs[cfg.n_super * p_len + t]:
                   _quantize_tree(blk, qc))
            for t, blk in enumerate(dec["tail"])]
    out = dict(params)
    out["decoder"] = {"super_segments": seg_trees, "tail": tail}
    return out
