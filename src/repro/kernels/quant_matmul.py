"""Fused dequantize-matmul Pallas TPU kernel.

Computes  ``x @ dequant(W)``  where W is stored as packed low-bit codes with
per-local-region affine params (paper section IV.C) -- the TPU deployment of
the paper's scheme (DESIGN.md section 5.1):

  * HBM->VMEM traffic moves the *packed* codes (bits/8 bytes per weight plus
    per-region scale/zmin), which is where the speedup lives on TPU: decode /
    small-batch GEMM is memory-bound, so bytes ~ bits/16 of bf16 is a direct
    roofline win.
  * Codes are unpacked and dequantized **in VMEM, per block, right before
    the MXU dot** -- never materialized in HBM.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary") with an f32 VMEM
accumulator; bk is a multiple of the local-region (group) size so each block
sees whole regions, and a multiple of 8 regions (or all of K) so the
scale/zmin blocks are sublane-aligned.

Unpacking without an interleave: packed row r of a K block holds the codes
of rows ``cpb*r + i`` (i < cpb) at shift ``i*bits``.  Shift-and-mask gives
``cpb`` planes, plane i holding rows ``i, cpb+i, 2cpb+i, ...``; instead of
interleaving the planes back along sublanes, the wrapper permutes the
columns of x the same way, so block ``kk`` computes
``sum_i x_plane_i @ dequant(plane_i)``.  Every row of plane i in local
region g lies in rows ``[g*gs/cpb, (g+1)*gs/cpb)``, so one scale row per
region still covers a contiguous run of plane rows.

Block shapes:
  x      (bm, bk)            float32 / bfloat16 (columns plane-permuted)
  packed (bk // cpb, bn)     uint8, codes packed along K
  scale  (bk // gs, bn)      f32
  zmin   (bk // gs, bn)      f32
  out    (bm, bn)            same dtype as x
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing


def _kernel(x_ref, p_ref, s_ref, z_ref, o_ref, acc_ref, **kw):
    _tile(x_ref, p_ref, s_ref, z_ref, o_ref, acc_ref, pl.program_id(2), **kw)


def _tile(x_ref, p_ref, s_ref, z_ref, o_ref, acc_ref, kk, *,
          bits: int, group_size: int, bk: int, k_steps: int):
    """One (bm, bn) output tile's K step ``kk``: zero the accumulator at
    the first, add ``x @ dequant(w)`` over the block's code planes, write
    the tile at the last (``expert_matmul`` runs it per expert tile)."""
    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cpb = packing.codes_per_byte(bits)
    rows = bk // cpb                                       # packed rows
    g = bk // group_size
    packed = p_ref[...].astype(jnp.int32)                  # (rows, bn)
    s = s_ref[...][:, None, :]                             # (g, 1, bn)
    z = z_ref[...][:, None, :]
    for i in range(cpb):
        codes = packed if cpb == 1 else \
            (packed >> (i * bits)) & ((1 << bits) - 1)
        w = (codes.astype(jnp.float32).reshape(g, rows // g, -1) * s
             + z).reshape(rows, -1)                        # dequant in VMEM
        acc_ref[...] += jax.lax.dot_general(
            x_ref[:, i * rows:(i + 1) * rows], w.astype(x_ref.dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kk == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pick_bk(k: int, group_size: int, target: int = 1024) -> int:
    """All of K when it fits ``target``; else the largest multiple of
    8 regions that divides K and fits (scale blocks need 8 sublanes)."""
    step = 8 * group_size
    best = k
    for bk in range(step, min(k, target) + 1, step):
        if k % bk == 0:
            best = bk
    return best


def _plane_permute(x, bk: int, cpb: int):
    """Columns of each bk block reordered to match the unpacked planes."""
    if cpb == 1:
        return x
    m, k = x.shape
    return (x.reshape(m, k // bk, bk // cpb, cpb)
            .transpose(0, 1, 3, 2).reshape(m, k))


@functools.partial(jax.jit, static_argnames=(
    "bits", "group_size", "bm", "bn", "bk", "interpret"))
def quant_matmul(x, packed, scale, zmin, *, bits: int, group_size: int,
                 bm: int = 128, bn: int = 128, bk: int | None = None,
                 interpret: bool = False):
    """x (M, K) @ dequant(packed/scale/zmin) (K, N) -> (M, N).

    M, N need not be tile-aligned (padded here); K must be divisible by the
    chosen bk (a multiple of group_size).
    """
    m, k = x.shape
    cpb = packing.codes_per_byte(bits)
    n = packed.shape[1]
    if k % group_size:
        # same hazard as lut_matmul: the K grid walks whole local regions,
        # so a ragged tail region would silently vanish from the product
        raise ValueError(
            f"K={k} is not a multiple of group_size={group_size}: the "
            f"trailing {k % group_size}-wide partial local region has no "
            f"grid step and would be dropped from the matmul")
    if bk is None:
        bk = _pick_bk(k, group_size)
    if k % bk or bk % group_size:
        raise ValueError(f"K={k} bk={bk} group_size={group_size} misaligned")

    bm = min(bm, _round_up(m, 8))
    bn = min(bn, _round_up(n, 128))
    mp, np_ = _round_up(m, bm), _round_up(n, bn)
    x_p = jnp.pad(x, ((0, mp - m), (0, 0))) if mp != m else x
    x_p = _plane_permute(x_p, bk, cpb)
    if np_ != n:
        packed = jnp.pad(packed, ((0, 0), (0, np_ - n)))
        scale = jnp.pad(scale, ((0, 0), (0, np_ - n)))
        zmin = jnp.pad(zmin, ((0, 0), (0, np_ - n)))

    k_steps = k // bk
    grid = (mp // bm, np_ // bn, k_steps)
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, group_size=group_size,
                          bk=bk, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // cpb, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // group_size, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // group_size, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"quant_matmul_b{bits}g{group_size}",
    )(x_p, packed, scale, zmin)
    return out[:m, :n]


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult
