"""The plain reference: the served model's forward pass in float32.

Straight ``jax.numpy`` at ``Precision.HIGHEST``, no kernels, no cache, no
batching, and nothing imported from the program.  The forward itself is
the configuration's family's (``forward`` in
``bench/families/<family>/plain.py``, which states what it follows); it
reads the weights that the family's ``draw`` makes from the seed, and
builds on what this module keeps for every family:

  * ``dequant``: 4-bit local-region weights (``bench/weights.py``) to f32;
  * ``kv_round``: the 4-bit key/value cache the configuration serves
    with, each token and head rounded through ``kv_group``-wide regions,
    ``q = round((x - min) / s)``, ``s = (max - min) / 15``;
  * ``rms`` (RMSNorm with a learned scale) and ``rope`` (rotary embedding
    over the two halves of each head);
  * ``Ops``: every matrix product, at ``HIGHEST``, or with its inputs
    rounded to float8 for the control.

``gaps`` runs the forward once over a prompt and the tokens served after
it, and reads, at each served position, how far the served token's logit
lies below the reference's best logit there.  The control
(``control=True``) runs the same forward with every matrix product's
inputs rounded to float8 (e4m3), the precision below the configuration's
bfloat16, and reads the same gap for the token that float8 puts first.
"""
from __future__ import annotations

import dataclasses
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


def rms(x, scale, eps):
    """RMSNorm over the last axis, with a learned ``scale``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (S, H, D); rotary embedding over the two halves of D."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@dataclasses.dataclass(frozen=True)
class Ops:
    """A forward's matrix products at ``HIGHEST``; with ``fp8``, each
    product's inputs rounded to float8 (e4m3) first: the control."""
    fp8: bool = False

    def cast(self, a):
        return a.astype(F8).astype(jnp.float32) if self.fp8 else a

    def mm(self, a, b):
        return jnp.matmul(self.cast(a), self.cast(b), precision=HIGHEST)

    def ein(self, spec, a, b):
        return jnp.einsum(spec, self.cast(a), self.cast(b), precision=HIGHEST)


@functools.lru_cache(maxsize=None)
def _gap_fn(forward, m_items: tuple, kv_bits: int, kv_group: int,
            control: bool):
    m = dict(m_items)

    @jax.jit
    def fn(w, tokens, n_prompt, rows, targets):
        kw = dict(kv_bits=kv_bits, kv_group=kv_group)
        ref = forward(w, m, tokens, n_prompt, rows, ops=Ops(), **kw)
        best = ref.max(-1)
        ok = (targets >= 0) & (targets < m["vocab"])
        at = jnp.take_along_axis(ref, jnp.clip(targets, 0, m["vocab"] - 1)
                                 [:, None], axis=1)[:, 0]
        gap = jnp.where(ok, best - at, jnp.inf)
        if not control:
            return gap, gap
        low = forward(w, m, tokens, n_prompt, rows, ops=Ops(fp8=True),
                      **kw)
        pick = jnp.argmax(low, -1)
        return gap, best - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]

    return fn


def gaps(forward, w, m: dict, prompt, served, *, bucket: int, kv_bits: int,
         kv_group: int, control: bool = False):
    """Per served token: (program gap, control gap) in logits, by the
    family's ``forward`` over its weights ``w`` and sizes ``m``.

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
    fn = _gap_fn(forward, tuple(sorted(m.items())), kv_bits, kv_group,
                 control)
    with jax.default_matmul_precision("highest"):
        g, c = fn(w, jnp.asarray(tokens), jnp.asarray(len(prompt), jnp.int32),
                  jnp.asarray(rows), jnp.asarray(targets))
    n = len(served)
    return np.asarray(g)[:n], np.asarray(c)[:n]
