"""Grouped dequantize-matmul over packed experts (drop-free MoE).

``x (R, K)`` holds the rows routed to the experts, sorted by expert and
each expert's group padded to the row tile ``tm``, so row tile ``i``
belongs to one expert, ``tile_expert[i]``.  The kernel computes, for every
live tile, ``x[tile] @ dequant(W[tile_expert[tile]])``, with ``W`` a
stack of packed experts (``(E, K/cpb, N)`` codes, ``(E, K/gs, N)``
scale/zmin: ``QWeight`` leaves with an expert axis).

Grid ``(row tiles, N/bn, K/bk)``, K innermost.  The tile -> expert map is
a scalar-prefetch operand, so the packed-weight, scale and zmin
``BlockSpec`` index maps fetch the tile's own expert: each hit expert's
weights stream through VMEM once for each of its row tiles, and an
expert no row chose is never read.  The body is ``quant_matmul``'s
(``_tile``: dequantize in VMEM per block, one MXU dot per code plane;
the wrapper permutes x's columns into plane order the same way).

The number of tiles is static (``R = T*k + E*(tm-1)`` rounded up to
``tm`` bounds every routing); only the first ``n_live`` hold rows.  Past
them every index map repeats the last live step's block (row tile
``n_live - 1``, the last N and K block, its expert), so the pipeline
fetches nothing, and the body is skipped; their output rows are never
written and never read (the combine gathers live rows only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing
from . import quant_matmul as _qm


def row_tile(rows: int, n_experts: int) -> int:
    """The row tile for ``rows`` routed rows over ``n_experts``: four
    times the mean group, as a power of two in [16, 128], so that most
    experts fit one tile (read once) and a 2048-row prefill fills the
    MXU: 16 at a 32-slot top-8 decode over 64 experts, 128 at prefill."""
    mean = -(-rows // n_experts)
    return min(128, max(16, 1 << (4 * mean - 1).bit_length()))


def _pick_bn(n: int) -> int:
    """The widest of 512/256/128 lanes dividing N: padding N would copy
    every expert's weights."""
    for bn in (512, 256, 128):
        if n % bn == 0:
            return bn
    raise ValueError(f"N={n} is not a multiple of 128")


def _kernel(te_ref, nl_ref, *refs, **kw):
    kk = pl.program_id(2)

    @pl.when(pl.program_id(0) < nl_ref[0])
    def _():
        _qm._tile(*refs, kk, **kw)


@functools.partial(jax.jit, static_argnames=(
    "bits", "group_size", "tm", "interpret"))
def expert_matmul(x, packed, scale, zmin, tile_expert, n_live, *, bits: int,
                  group_size: int, tm: int, interpret: bool = False):
    """x (R, K) rows grouped by expert in ``tm``-row tiles ->
    (R, N): tile i times expert ``tile_expert[i]``'s dequantized weights,
    for the first ``n_live`` tiles (the rest of the output is undefined).

    packed (E, K/cpb, N) uint8, scale/zmin (E, K/group_size, N) f32;
    tile_expert (R // tm,) int32 (entries past ``n_live`` are ignored);
    n_live a traced int32 scalar in [1, R // tm].
    """
    r, k = x.shape
    e, _, n = packed.shape
    cpb = packing.codes_per_byte(bits)
    if r % tm:
        raise ValueError(f"R={r} is not a multiple of tm={tm}")
    if k % group_size:
        raise ValueError(f"K={k} is not a multiple of group_size="
                         f"{group_size}")
    bk = _qm._pick_bk(k, group_size)
    bn = _pick_bn(n)
    nt, nj, nk = r // tm, n // bn, k // bk
    nl = jnp.clip(jnp.asarray(n_live, jnp.int32), 1, nt).reshape(1)
    last = nl[0] - 1
    te = tile_expert.astype(jnp.int32)
    te = te[jnp.minimum(jnp.arange(nt), last)]      # dead tiles: last live

    def dead(i, nl_ref):
        return i >= nl_ref[0]

    def x_map(i, j, kk, te_ref, nl_ref):
        d = dead(i, nl_ref)
        return (jnp.where(d, nl_ref[0] - 1, i), jnp.where(d, nk - 1, kk))

    def w_map(i, j, kk, te_ref, nl_ref):
        d = dead(i, nl_ref)
        return (te_ref[i], jnp.where(d, nk - 1, kk), jnp.where(d, nj - 1, j))

    def o_map(i, j, kk, te_ref, nl_ref):
        d = dead(i, nl_ref)
        return (jnp.where(d, nl_ref[0] - 1, i), jnp.where(d, nj - 1, j))

    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, group_size=group_size, bk=bk,
                          k_steps=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt, nj, nk),
            in_specs=[
                pl.BlockSpec((tm, bk), x_map),
                pl.BlockSpec((None, bk // cpb, bn), w_map),
                pl.BlockSpec((None, bk // group_size, bn), w_map),
                pl.BlockSpec((None, bk // group_size, bn), w_map),
            ],
            out_specs=pl.BlockSpec((tm, bn), o_map),
            scratch_shapes=[pltpu.VMEM((tm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((r, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"expert_matmul_b{bits}g{group_size}",
    )(te, nl, _qm._plane_permute(x, bk, cpb), packed, scale, zmin)
