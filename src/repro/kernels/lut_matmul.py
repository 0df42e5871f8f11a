"""Look-up-table matmul Pallas kernel (paper section V, TPU adaptation).

The paper replaces multiply-accumulates with table lookups: with n-bit
activations there are only 2^n distinct codes, so each local region's inner
product is  s * sum_v v*T[v] + zmin * sum_j w_j  with the "table"
T[v] = sum_{j: code_j == v} w_j  built by adds alone.

TPU has no scatter-accumulate into VMEM tables, but the *identical dataflow*
is a sequence of **binary masked matmuls** (DESIGN.md section 5.2): for each
code value v the mask (codes == v) is a {0,1} matrix and

    T_v = mask_v @ W                (the table build, one per code value)
    out += (v * s) . T_v            (the table read / combine)

The kernel loops v = 0..2^n-1 (unrolled -- 4 iterations at 2-bit), which is
the one-hot partial-sum matmul.  This is the fidelity implementation used
for paper-Table-3 accounting; the packed path (quant_matmul.py) is the
throughput deployment.

Grid: (M/bm, N/bn, G) with G = K / group_size -- one local region per K step.

Block shapes:
  codes (bm, group_size) uint8 (unpacked codes)
  scale (1, bm, 1) f32 ; zmin (1, bm, 1) f32   (this region's affine, per
        row; the wrapper lays them out (G, M, 1) so a region's column is a
        full-lane block rather than a 1-lane slice of (M, G))
  w     (group_size, bn)
  out   (bm, bn)  f32 accumulation across regions
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing


def _kernel(c_ref, s_ref, z_ref, w_ref, o_ref, acc_ref, *,
            bits: int, g_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = c_ref[...].astype(jnp.int32)            # (bm, gs)
    w = w_ref[...].astype(jnp.float32)              # (gs, bn)
    s = s_ref[0]                                    # (bm, 1)
    z = z_ref[0]

    # table build + combine: sum_v v * (mask_v @ W), v = 1 .. 2^bits-1
    # (v = 0 contributes nothing -- the paper's same skip, section V.C)
    code_dot = jnp.zeros_like(acc_ref)
    for v in range(1, 1 << bits):
        mask_v = (codes == v).astype(w.dtype)       # binary {0,1}
        t_v = jax.lax.dot_general(mask_v, w, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        code_dot += jnp.float32(v) * t_v
    # region affine: s * code_dot + zmin * sum_j w_j
    wsum = w.sum(axis=0, keepdims=True)             # (1, bn)
    acc_ref[...] += s * code_dot + z * wsum

    @pl.when(pl.program_id(2) == g_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "bm",
                                             "bn", "interpret"))
def lut_matmul(a_packed, a_scale, a_zmin, w, *, bits: int, group_size: int,
               bm: int = 128, bn: int = 128, interpret: bool = False):
    """dequant(a) @ w via the LUT dataflow.

    a_packed (M, K/cpb) uint8, a_scale/a_zmin (M, G), w (K, N) float.
    Returns f32 (M, N).
    """
    if bits > 4:
        raise ValueError("LUT path needs activation bits <= 4 (section V.A)")
    m = a_packed.shape[0]
    k, n = w.shape
    if k % group_size:
        # the grid covers K // group_size full regions; a ragged tail
        # would be silently dropped from the product, not just misrounded
        raise ValueError(
            f"K={k} is not a multiple of group_size={group_size}: the "
            f"trailing {k % group_size}-wide partial local region has no "
            f"grid step and would be dropped from the matmul")
    g = k // group_size
    codes = packing.unpack(a_packed, bits, k)            # (M, K) uint8

    bm = min(bm, _round_up(m, 8))
    bn = min(bn, _round_up(n, 128))
    mp, np_ = _round_up(m, bm), _round_up(n, bn)
    if mp != m:
        codes = jnp.pad(codes, ((0, mp - m), (0, 0)))
        a_scale = jnp.pad(a_scale, ((0, mp - m), (0, 0)))
        a_zmin = jnp.pad(a_zmin, ((0, mp - m), (0, 0)))
    w_p = jnp.pad(w, ((0, 0), (0, np_ - n))) if np_ != n else w
    a_scale, a_zmin = (a.T[:, :, None] for a in (a_scale, a_zmin))

    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, g_steps=g),
        grid=(mp // bm, np_ // bn, g),
        in_specs=[
            pl.BlockSpec((bm, group_size), lambda i, j, r: (i, r)),
            pl.BlockSpec((1, bm, 1), lambda i, j, r: (r, i, 0)),
            pl.BlockSpec((1, bm, 1), lambda i, j, r: (r, i, 0)),
            pl.BlockSpec((group_size, bn), lambda i, j, r: (r, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, r: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"lut_matmul_b{bits}g{group_size}",
    )(codes, a_scale, a_zmin, w_p)
    return out[:m, :n]


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult
