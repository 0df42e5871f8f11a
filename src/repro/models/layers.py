"""Primitive layers: Dense (quantization-aware), norms, embeddings, RoPE.

Functional style: ``*_init(key, ...) -> params`` (nested dicts of arrays),
``*_apply(params, x, ...) -> y``.  Params are plain pytrees so they flow
through jit / pjit / scan and the checkpoint manager unchanged.

Quantization integration (the paper's technique as a first-class feature):
a Dense weight may be

  * a float array                     -- fp / QAT training path,
  * a :class:`repro.kernels.QWeight`  -- packed local-quantization-region
                                         deployment format; the forward pass
                                         dispatches to kernels.quant_matmul.

``QuantPolicy`` carries the scheme + mode through the model without
threading extra arguments everywhere.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core import schemes, qat
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    """How projections behave in the forward pass.

    mode:
      'none'   float weights, float activations
      'qat'    straight-through fake quant on weights (+acts if configured)
      'serve'  weights are QWeight (packed); optional runtime act quant / LUT
    """
    mode: str = "none"
    cfg: schemes.QuantConfig = schemes.FP32
    backend: str = "auto"      # kernel backend: auto | pallas | interpret | ref
    kv_fq: tuple | None = None  # (bits, group): fake-quant K/V when uncached

    @staticmethod
    def train_fp():
        return QuantPolicy("none", schemes.FP32)

    @staticmethod
    def serve(cfg, backend="auto"):
        return QuantPolicy("serve", schemes.get(cfg), backend)

    @staticmethod
    def qat(cfg):
        return QuantPolicy("qat", schemes.get(cfg))


NO_QUANT = QuantPolicy.train_fp()


@dataclasses.dataclass(frozen=True)
class PlanPolicy:
    """Per-layer quantization policy: one :class:`QuantConfig` per decoder
    layer (a resolved :class:`repro.plan.QuantPlan`).

    Quacks like a :class:`QuantPolicy` (mode / cfg / backend) for projections
    outside the planned stack — embedding, lm_head, frontend, encoder — which
    run under ``base_cfg`` (fp by default).  ``layer(i)`` yields the plain
    per-layer policy that ``block_apply`` consumes; the stack walker groups
    consecutive superblocks with identical configs so the scan stays compact.
    """
    mode: str                                   # 'qat' | 'serve'
    configs: tuple                              # per-layer QuantConfig
    backend: str = "auto"
    base_cfg: schemes.QuantConfig = schemes.FP32
    kv_bits: tuple = ()                         # per-layer cache bits | None
    kv_group: int = 64                          # cache local-region size

    @property
    def cfg(self) -> schemes.QuantConfig:
        return self.base_cfg

    def layer_kv(self, i: int) -> int | None:
        """Cache bitwidth of decoder layer ``i`` (None = fp cache)."""
        return self.kv_bits[i] if self.kv_bits else None

    def layer(self, i: int) -> QuantPolicy:
        kv = self.layer_kv(i)
        return QuantPolicy(self.mode, self.configs[i], self.backend,
                           kv_fq=None if kv is None else (kv, self.kv_group))

    @property
    def n_layers(self) -> int:
        return len(self.configs)


def policy_for_layer(policy, i: int) -> QuantPolicy:
    """Resolve a (possibly per-layer) policy for decoder/conv layer ``i``."""
    if isinstance(policy, PlanPolicy):
        return policy.layer(i)
    return policy


# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, *, dtype=jnp.float32,
               bias: bool = False, scale: float | None = None):
    w_scale = scale if scale is not None else in_dim ** -0.5
    p = {"w": (jax.random.normal(key, (in_dim, out_dim), jnp.float32)
               * w_scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def dense_apply(p, x, policy: QuantPolicy = NO_QUANT):
    w = p["w"]
    if isinstance(w, kops.QWeight):
        cfg = policy.cfg
        y = kops.quant_dense(x, w, a_bits=cfg.a_bits, lut=cfg.lut,
                             backend=policy.backend)
    elif policy.mode == "qat" and policy.cfg.quantized:
        y = qat.qat_dense_apply(w.astype(jnp.float32),
                                x.astype(jnp.float32), policy.cfg)
        y = y.astype(x.dtype)
    else:
        y = x @ w.astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def quantize_dense(p, cfg: schemes.QuantConfig):
    """Convert a Dense param dict to the packed serving format."""
    if cfg.w_bits is None:
        return p
    w = p["w"].astype(jnp.float32)
    out = dict(p)
    out["w"] = kops.quantize_weight(w, cfg.w_bits, cfg.group_size)
    return out


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm_apply(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm_apply(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def embed_init(key, vocab: int, dim: int, dtype=jnp.float32):
    return {"table": (jax.random.normal(key, (vocab, dim), jnp.float32)
                      * dim ** -0.5).astype(dtype)}


def embed_apply(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def embed_logits(p, x, true_vocab: int | None = None):
    """Tied read-out: x @ table^T, padded vocab rows masked to -inf."""
    table = p["table"].astype(x.dtype)
    logits = x @ table.T
    if true_vocab is not None and true_vocab < table.shape[0]:
        pad = table.shape[0] - true_vocab
        neg = jnp.full((pad,), -1e9, logits.dtype)
        logits = logits.at[..., true_vocab:].set(neg)
    return logits


def posembed_init(key, max_len: int, dim: int, dtype=jnp.float32):
    return {"pos": (jax.random.normal(key, (max_len, dim), jnp.float32)
                    * 0.02).astype(dtype)}


def posembed_apply(p, x, offset=0):
    L = x.shape[-2]
    pos = jax.lax.dynamic_slice_in_dim(p["pos"], offset, L, axis=0)
    return x + pos.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 1e4, yarn: tuple = ()):
    """Rotary inverse frequencies (D/2,); with ``yarn`` = (factor,
    original positions, beta_fast, beta_slow, attention_factor), YaRN's
    blend of them (Hugging Face ``_compute_yarn_parameters``): pair i
    keeps ``1/theta^(2i/D)`` below ``low``, takes it over ``factor`` above
    ``high``, and ramps linearly between, where ``low``/``high`` are the
    pairs that turn ``beta_fast``/``beta_slow`` times over the original
    context, floored/ceiled and clamped to [0, D-1]."""
    base = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32)
                            / head_dim))
    if not yarn:
        return base
    factor, orig, beta_fast, beta_slow, _ = yarn

    def pair(rot):      # the pair index whose wavelength is orig / rot
        return (head_dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return base / factor * ramp + base * (1.0 - ramp)


def apply_rope(x, positions, theta: float = 1e4, yarn: tuple = ()):
    """x (..., L, H, D), positions (..., L) int32 -> same shape.  With
    ``yarn`` the frequencies are YaRN's and cos/sin are scaled by its
    attention factor (the fifth entry)."""
    freqs = rope_freqs(x.shape[-1], theta, yarn)               # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs     # (..., L, D/2)
    cos = jnp.cos(ang)[..., None, :]                           # (..., L, 1, D/2)
    sin = jnp.sin(ang)[..., None, :]
    if yarn:
        cos, sin = cos * yarn[4], sin * yarn[4]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
