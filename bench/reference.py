"""The plain reference: the served model's forward pass in float32.

Straight ``jax.numpy`` at ``Precision.HIGHEST``, no kernels, no cache, no
batching, and nothing imported from the program.  It reads the weights
that ``bench/weights.py`` draws from the seed and follows the
configuration as it is run (``bench/configs/<config>.json``):

  * pre-norm decoder blocks, RMSNorm (``rms_norm_eps``) with a learned
    scale; SwiGLU feed-forward;
  * grouped-query attention, query head ``h`` reading key/value head
    ``h // (heads / kv_heads)``; optional per-head RMSNorm of q and k
    (``qk_norm``) before rotary embedding; rotary embedding over the two
    halves of each head (``rope_theta``); scores scaled by ``head_dim**-0.5``;
  * 4-bit local-region weights (``bench/weights.py``) dequantized to f32;
  * the 4-bit key/value cache the configuration serves with: keys (after
    rotary embedding) and values are rounded per token and head through
    ``kv_group``-wide regions, ``q = round((x - min) / s)``,
    ``s = (max - min) / 15``.  A prompt is processed in one pass that
    attends to its own unrounded keys and values; every later token
    attends to the rounded cache, its own entry included;
  * the output head: the tied embedding, or the packed ``lm_head``.

``gaps`` runs the forward once over a prompt and the tokens served after
it, and reads, at each served position, how far the served token's logit
lies below the reference's best logit there.  The control
(``control=True``) runs the same forward with every matrix product's
inputs rounded to float8 (e4m3), the precision below the configuration's
bfloat16, and reads the same gap for the token that float8 puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn


def dequant(p: dict) -> jnp.ndarray:
    """Packed (K/2, N) codes + (K/128, N) scale/zmin -> f32 (K, N)."""
    packed = p["packed"].astype(jnp.int32)
    kh, n = packed.shape
    codes = jnp.stack([packed & 15, packed >> 4], axis=1).reshape(2 * kh, n)
    g = p["scale"].shape[0]
    w = (codes.astype(jnp.float32).reshape(g, -1, n) * p["scale"][:, None]
         + p["zmin"][:, None])
    return w.reshape(2 * kh, n)


def kv_round(x: jnp.ndarray, bits: int, group: int) -> jnp.ndarray:
    """Round (..., D) through ``group``-wide affine regions of ``bits``."""
    d = x.shape[-1]
    g = x.reshape(*x.shape[:-1], d // group, group)
    lo, hi = g.min(-1, keepdims=True), g.max(-1, keepdims=True)
    levels = (1 << bits) - 1
    s = jnp.where(hi > lo, (hi - lo) / levels, 1.0)
    q = jnp.clip(jnp.round((g - lo) / s), 0, levels)
    return (q * s + lo).reshape(x.shape)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (S, H, D); rotary embedding over the two halves of D."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _forward(w, m: dict, tokens, n_prompt, rows, *, kv_bits: int,
             kv_group: int, fp8: bool):
    """Logits (R, vocab) at positions ``rows`` (R,) of ``tokens`` (S,)."""
    def cast(a):
        return a.astype(F8).astype(jnp.float32) if fp8 else a

    def mm(a, b):
        return jnp.matmul(cast(a), cast(b), precision=HIGHEST)

    def ein(spec, a, b):
        return jnp.einsum(spec, cast(a), cast(b), precision=HIGHEST)

    s_len = tokens.shape[0]
    heads, kvh, hd, eps = m["heads"], m["kv"], m["hd"], m["eps"]
    grp = heads // kvh
    pos = jnp.arange(s_len)
    prompt_row = (pos < n_prompt)[:, None]                    # (S, 1)
    causal = pos[None, :] <= pos[:, None]                     # (S, S)

    def layer(x, lw):
        h = _rms(x, lw["norm1"], eps)
        q = mm(h, dequant(lw["wq"])).reshape(s_len, heads, hd)
        k = mm(h, dequant(lw["wk"])).reshape(s_len, kvh, hd)
        v = mm(h, dequant(lw["wv"])).reshape(s_len, kvh, hd)
        if m["qk_norm"]:
            q = _rms(q, lw["q_norm"], eps)
            k = _rms(k, lw["k_norm"], eps)
        q = _rope(q, pos, m["rope_theta"]).reshape(s_len, kvh, grp, hd)
        k = _rope(k, pos, m["rope_theta"])
        kq, vq = kv_round(k, kv_bits, kv_group), kv_round(v, kv_bits, kv_group)
        scale = hd ** -0.5
        s = jnp.where(prompt_row[None, None],
                      ein("skgd,tkd->kgst", q, k),
                      ein("skgd,tkd->kgst", q, kq)) * scale
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.where(prompt_row[:, None, None],
                      ein("kgst,tkd->skgd", p, v),
                      ein("kgst,tkd->skgd", p, vq))
        x = x + mm(o.reshape(s_len, heads * hd), dequant(lw["wo"]))
        h = _rms(x, lw["norm2"], eps)
        f = jax.nn.silu(mm(h, dequant(lw["wi_gate"]))) * mm(
            h, dequant(lw["wi_up"]))
        return x + mm(f, dequant(lw["wo_ffn"])), None

    x = w["embed"][tokens]
    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _rms(x[rows], w["final_norm"], eps)
    head = w["embed"].T if m["tied"] else dequant(w["lm_head"])
    return mm(x, head)[:, :m["vocab"]]


@functools.lru_cache(maxsize=None)
def _gap_fn(m_items: tuple, kv_bits: int, kv_group: int, control: bool):
    m = dict(m_items)

    @jax.jit
    def fn(w, tokens, n_prompt, rows, targets):
        kw = dict(kv_bits=kv_bits, kv_group=kv_group)
        ref = _forward(w, m, tokens, n_prompt, rows, fp8=False, **kw)
        best = ref.max(-1)
        ok = (targets >= 0) & (targets < m["vocab"])
        at = jnp.take_along_axis(ref, jnp.clip(targets, 0, m["vocab"] - 1)
                                 [:, None], axis=1)[:, 0]
        gap = jnp.where(ok, best - at, jnp.inf)
        if not control:
            return gap, gap
        low = _forward(w, m, tokens, n_prompt, rows, fp8=True, **kw)
        pick = jnp.argmax(low, -1)
        return gap, best - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]

    return fn


def gaps(w, m: dict, prompt, served, *, bucket: int, kv_bits: int,
         kv_group: int, control: bool = False):
    """Per served token: (program gap, control gap) in logits.

    ``prompt`` and ``served`` are int sequences; the forward runs over
    ``prompt + served[:-1]`` right-padded to ``bucket`` positions, and the
    head over ``bucket // 2`` rows (one compiled program for every
    request).  ``served[j]`` was produced at position
    ``len(prompt) - 1 + j``.
    """
    prompt, served = list(map(int, prompt)), list(map(int, served))
    seq = prompt + served[:-1]
    n_rows = bucket // 2
    if len(seq) > bucket or len(served) > n_rows:
        raise ValueError(f"{len(seq)} positions or {len(served)} served "
                         f"tokens exceed the bucket {bucket}")
    tokens = np.zeros((bucket,), np.int32)
    tokens[:len(seq)] = seq
    first = len(prompt) - 1
    rows = np.zeros((n_rows,), np.int32)
    rows[:len(served)] = np.arange(first, first + len(served))
    targets = np.full((n_rows,), -1, np.int32)
    targets[:len(served)] = served
    fn = _gap_fn(tuple(sorted(m.items())), kv_bits, kv_group, control)
    with jax.default_matmul_precision("highest"):
        g, c = fn(w, jnp.asarray(tokens), jnp.asarray(len(prompt), jnp.int32),
                  jnp.asarray(rows), jnp.asarray(targets))
    n = len(served)
    return np.asarray(g)[:n], np.asarray(c)[:n]
