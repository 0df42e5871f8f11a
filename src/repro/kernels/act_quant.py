"""Fused runtime activation quantization Pallas kernel.

The paper quantizes layer inputs *at runtime* (section IV: "the inputs have
to be converted into fixed point in runtime").  This kernel fuses the whole
pipeline over each local quantization region in one VMEM pass:

    per-region min / max  ->  scale s_lk, zero x^lk_min (eq. 5)
    round((x - min)/s)    ->  n-bit codes               (eq. 3)
    bit-pack codes into uint8 lanes

Block: (bm, K) rows -- a row's regions are contiguous along K, so one block
holds whole regions and the reductions stay in-registers.  Outputs:
packed (M, K/cpb) uint8, scale (M, G) f32, zmin (M, G) f32.

Packing runs on the MXU: ``cpb`` adjacent codes fold into one byte as
``sum_i code_i * 2^(i*bits)``, a matmul with a 0/power-of-two matrix over
each ``128*cpb``-lane chunk (the last one may be shorter).  Codes and weights are exact in bf16 and the
sums (< 256) exact in the f32 accumulator, so the bytes match
``core/packing.pack`` bit for bit with no lane interleave in the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import packing


def _kernel(x_ref, p_ref, s_ref, z_ref, *, bits: int, group_size: int):
    x = x_ref[...].astype(jnp.float32)                  # (bm, K)
    bm, k = x.shape
    g = k // group_size
    xg = x.reshape(bm, g, group_size)
    xmin = xg.min(axis=-1)                              # (bm, G)
    xmax = xg.max(axis=-1)
    levels = (1 << bits) - 1
    rng = xmax - xmin
    scale = jnp.where(rng > 0, rng / levels, jnp.ones_like(rng))
    codes = jnp.clip(jnp.round((xg - xmin[..., None]) / scale[..., None]),
                     0, levels).reshape(bm, k)
    cpb = packing.codes_per_byte(bits)
    if cpb == 1:
        p_ref[...] = codes.astype(jnp.int32).astype(jnp.uint8)
    else:
        cw = 128 * cpb                                  # codes per chunk
        for lo in range(0, k, cw):                      # last may be short
            w = min(cw, k - lo)
            lane = jax.lax.broadcasted_iota(jnp.int32, (w, w // cpb), 0)
            byte = jax.lax.broadcasted_iota(jnp.int32, (w, w // cpb), 1)
            fold = jnp.where(lane // cpb == byte,
                             jnp.left_shift(1, (lane % cpb) * bits), 0)
            packed = jax.lax.dot_general(
                codes[:, lo:lo + w].astype(jnp.bfloat16),
                fold.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            p_ref[:, lo // cpb:(lo + w) // cpb] = \
                packed.astype(jnp.int32).astype(jnp.uint8)
    s_ref[...] = scale
    z_ref[...] = xmin


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "bm",
                                             "interpret"))
def act_quant(x, *, bits: int, group_size: int, bm: int = 256,
              interpret: bool = False):
    """x (M, K) -> (packed (M, K/cpb) uint8, scale (M, G), zmin (M, G))."""
    m, k = x.shape
    if k % group_size:
        raise ValueError(f"K={k} not divisible by group_size={group_size}")
    g = k // group_size
    cpb = packing.codes_per_byte(bits)
    bm = min(bm, _round_up(m, 8))
    mp = _round_up(m, bm)
    x_p = jnp.pad(x, ((0, mp - m), (0, 0))) if mp != m else x

    packed, scale, zmin = pl.pallas_call(
        functools.partial(_kernel, bits=bits, group_size=group_size),
        grid=(mp // bm,),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm, k // cpb), lambda i: (i, 0)),
            pl.BlockSpec((bm, g), lambda i: (i, 0)),
            pl.BlockSpec((bm, g), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, k // cpb), jnp.uint8),
            jax.ShapeDtypeStruct((mp, g), jnp.float32),
            jax.ShapeDtypeStruct((mp, g), jnp.float32),
        ],
        interpret=interpret,
        name=f"act_quant_b{bits}g{group_size}",
    )(x_p)
    return packed[:m], scale[:m], zmin[:m]


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult
