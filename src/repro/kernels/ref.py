"""Pure-jnp oracles for every Pallas kernel in this package.

Layout conventions shared by kernels and oracles (kernel wire format):

  quant matmul weights ("QWeight"):
    packed : uint8 (K // codes_per_byte, N)   codes packed along K
    scale  : f32   (G, N)   G = K // group_size    (per-region step s_lk)
    zmin   : f32   (G, N)                          (per-region x^lk_min)

  activation quant ("QAct"):
    packed : uint8 (M, K // codes_per_byte)   codes packed along K
    scale  : f32   (M, G)
    zmin   : f32   (M, G)

Regions run along the contraction axis K in both cases — exactly the
paper's Fig. 4 picture with the weight rows split into local regions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import packing


# ---------------------------------------------------------------------------
# weight quantization into the kernel wire format
# ---------------------------------------------------------------------------

def quantize_weight(w: jnp.ndarray, bits: int, group_size: int):
    """f32 (K, N) -> (packed (K/cpb, N), scale (G, N), zmin (G, N))."""
    k, n = w.shape
    if k % group_size:
        raise ValueError(f"K={k} not divisible by group_size={group_size}")
    g = k // group_size
    wf = w.astype(jnp.float32).reshape(g, group_size, n)
    xmin = wf.min(axis=1)                                  # (G, N)
    xmax = wf.max(axis=1)
    levels = (1 << bits) - 1
    rng = xmax - xmin
    scale = jnp.where(rng > 0, rng / levels, jnp.ones_like(rng))
    codes = jnp.clip(jnp.round((wf - xmin[:, None]) / scale[:, None]),
                     0, levels).astype(jnp.uint8).reshape(k, n)
    packed = packing.pack(codes.T, bits).T                 # pack along K
    return packed, scale, zmin_cast(xmin)


def zmin_cast(x):
    return x.astype(jnp.float32)


def dequantize_weight(packed, scale, zmin, bits: int, group_size: int,
                      dtype=jnp.float32):
    """Inverse of :func:`quantize_weight` -> f32 (K, N)."""
    kp, n = packed.shape
    codes = packing.unpack(packed.T, bits).T.astype(jnp.float32)  # (K, N)
    k = codes.shape[0]
    g = k // group_size
    wf = (codes.reshape(g, group_size, n) * scale[:, None]
          + zmin[:, None]).reshape(k, n)
    return wf.astype(dtype)


# ---------------------------------------------------------------------------
# oracles
# ---------------------------------------------------------------------------

def quant_matmul(x, packed, scale, zmin, *, bits: int, group_size: int):
    """Oracle for kernels.quant_matmul: x @ dequant(w)."""
    w = dequantize_weight(packed, scale, zmin, bits, group_size)
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


def expert_matmul(x, packed, scale, zmin, tile_expert, n_live, *,
                  bits: int, group_size: int, tm: int):
    """Oracle for kernels.expert_matmul: each ``tm``-row tile of x times
    its expert's dequantized weights; tiles past ``n_live`` give zeros."""
    r, k = x.shape
    w = jax.vmap(lambda p, s, z: dequantize_weight(p, s, z, bits,
                                                   group_size))(
        packed, scale, zmin)                                   # (E, K, N)
    xt = x.astype(jnp.float32).reshape(r // tm, tm, k)
    out = jnp.einsum("tmk,tkn->tmn", xt, w[tile_expert])
    live = jnp.arange(r // tm) < n_live
    out = jnp.where(live[:, None, None], out, 0.0)
    return out.reshape(r, -1).astype(x.dtype)


def act_quant(x, *, bits: int, group_size: int):
    """Oracle for kernels.act_quant: runtime per-region activation quant.

    x: (M, K) float -> (packed (M, K/cpb), scale (M, G), zmin (M, G)).
    """
    m, k = x.shape
    if k % group_size:
        raise ValueError(f"K={k} not divisible by group_size={group_size}")
    g = k // group_size
    xf = x.astype(jnp.float32).reshape(m, g, group_size)
    xmin = xf.min(axis=-1)
    xmax = xf.max(axis=-1)
    levels = (1 << bits) - 1
    rng = xmax - xmin
    scale = jnp.where(rng > 0, rng / levels, jnp.ones_like(rng))
    codes = jnp.clip(jnp.round((xf - xmin[..., None]) / scale[..., None]),
                     0, levels).astype(jnp.uint8).reshape(m, k)
    return packing.pack(codes, bits), scale, xmin.astype(jnp.float32)


def act_dequant(packed, scale, zmin, *, bits: int, group_size: int):
    codes = packing.unpack(packed, bits).astype(jnp.float32)     # (M, K)
    m, k = codes.shape
    g = k // group_size
    return (codes.reshape(m, g, group_size) * scale[..., None]
            + zmin[..., None]).reshape(m, k)


def lut_matmul(a_packed, a_scale, a_zmin, w, *, bits: int, group_size: int):
    """Oracle for kernels.lut_matmul: dequant(a) @ w via explicit dequant.

    The kernel computes the identical quantity through the one-hot
    partial-sum dataflow (paper section V); numerically both equal
    dequant(a) @ w up to float association.
    """
    a = act_dequant(a_packed, a_scale, a_zmin, bits=bits,
                    group_size=group_size)
    return a @ w.astype(jnp.float32)
