"""Public jit'd wrappers around the Pallas kernels.

Backend policy (``backend=`` argument, default "auto"):

  * "pallas"     -- compile the Pallas TPU kernel (requires TPU).
  * "interpret"  -- Pallas interpret mode: the kernel body runs in Python on
                    CPU.  Used by tests to validate the exact kernel against
                    the pure-jnp oracle.
  * "ref"        -- the pure-jnp oracle itself (fast on CPU, identical math;
                    XLA fuses the dequant into the matmul).  Used on non-TPU
                    backends, including the dry-run host compile.
  * "auto"       -- "pallas" on TPU else "ref".

The :class:`QWeight` pytree is the deployment weight format -- packed codes
plus per-local-region affine -- and flows through jit / pjit / scan.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import packing
from . import ref as _ref
from . import quant_matmul as _qm
from . import act_quant as _aq
from . import expert_matmul as _em
from . import lut_matmul as _lm


def resolve_backend(backend: str) -> str:
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return backend


# ---------------------------------------------------------------------------
# QWeight: deployment weight format
# ---------------------------------------------------------------------------

@partial(jax.tree_util.register_dataclass,
         data_fields=("packed", "scale", "zmin"),
         meta_fields=("bits", "group_size", "k", "n"))
@dataclasses.dataclass(frozen=True)
class QWeight:
    packed: jnp.ndarray   # uint8 (K/cpb, N) codes packed along K
    scale: jnp.ndarray    # f32 (G, N)
    zmin: jnp.ndarray     # f32 (G, N)
    bits: int
    group_size: int
    k: int
    n: int

    @property
    def shape(self):
        return (self.k, self.n)

    def nbytes(self) -> int:
        return (self.packed.size * self.packed.dtype.itemsize
                + self.scale.size * 4 + self.zmin.size * 4)


def quantize_weight(w, bits: int, group_size: int) -> QWeight:
    """Offline weight quantization into the kernel wire format."""
    k, n = w.shape
    packed, scale, zmin = _ref.quantize_weight(w, bits, group_size)
    return QWeight(packed=packed, scale=scale, zmin=zmin, bits=bits,
                   group_size=group_size, k=k, n=n)


def dequantize_weight(qw: QWeight, dtype=jnp.float32):
    return _ref.dequantize_weight(qw.packed, qw.scale, qw.zmin, qw.bits,
                                  qw.group_size, dtype)


# ---------------------------------------------------------------------------
# ops
# ---------------------------------------------------------------------------

def quant_matmul(x, qw: QWeight, *, backend: str = "auto", **block_kw):
    """x (..., K) @ dequant(qw) -> (..., N).  Leading dims are flattened."""
    b = resolve_backend(backend)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, qw.k)
    if b == "ref":
        out = _ref.quant_matmul(x2, qw.packed, qw.scale, qw.zmin,
                                bits=qw.bits, group_size=qw.group_size)
    else:
        out = _qm.quant_matmul(x2, qw.packed, qw.scale, qw.zmin,
                               bits=qw.bits, group_size=qw.group_size,
                               interpret=(b == "interpret"), **block_kw)
    return out.reshape(*lead, qw.n)


expert_row_tile = _em.row_tile


def expert_matmul(x, qw: QWeight, tile_expert, n_live, *, tm: int,
                  backend: str = "auto"):
    """Grouped x (R, K) @ dequant(qw[e]) over experts: row tile i (``tm``
    rows) uses expert ``tile_expert[i]`` of the stacked ``qw`` (E, K, N);
    tiles from ``n_live`` on are not computed.  -> (R, N)."""
    b = resolve_backend(backend)
    kw = dict(bits=qw.bits, group_size=qw.group_size, tm=tm)
    if b == "ref":
        return _ref.expert_matmul(x, qw.packed, qw.scale, qw.zmin,
                                  tile_expert, n_live, **kw)
    return _em.expert_matmul(x, qw.packed, qw.scale, qw.zmin, tile_expert,
                             n_live, interpret=(b == "interpret"), **kw)


def act_quant(x, *, bits: int, group_size: int, backend: str = "auto",
              **block_kw):
    """Runtime activation quantization (paper: inputs quantized online)."""
    b = resolve_backend(backend)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if b == "ref":
        packed, scale, zmin = _ref.act_quant(x2, bits=bits,
                                             group_size=group_size)
    else:
        packed, scale, zmin = _aq.act_quant(x2, bits=bits,
                                            group_size=group_size,
                                            interpret=(b == "interpret"),
                                            **block_kw)
    g = x.shape[-1] // group_size
    return (packed.reshape(*lead, -1), scale.reshape(*lead, g),
            zmin.reshape(*lead, g))


def lut_matmul(a_packed, a_scale, a_zmin, w, *, bits: int, group_size: int,
               backend: str = "auto", **block_kw):
    """Paper section-V LUT forward.  a_* in QAct wire format; w float (K, N)."""
    b = resolve_backend(backend)
    if b == "ref":
        return _ref.lut_matmul(a_packed, a_scale, a_zmin, w, bits=bits,
                               group_size=group_size)
    return _lm.lut_matmul(a_packed, a_scale, a_zmin, w, bits=bits,
                          group_size=group_size,
                          interpret=(b == "interpret"), **block_kw)


def quant_dense(x, qw: QWeight, *, a_bits: int | None = None,
                lut: bool = False, backend: str = "auto"):
    """Full paper forward for one projection: optional runtime activation
    quant (a_bits), then packed-weight matmul -- or the LUT path when
    ``lut=True`` (activations quantized, weights float-reconstructed).
    """
    if lut:
        if a_bits is None:
            raise ValueError("LUT path requires a_bits")
        lead = x.shape[:-1]
        x2 = x.reshape(-1, qw.k)
        ap, asc, azm = act_quant(x2, bits=a_bits, group_size=qw.group_size,
                                 backend=backend)
        w = dequantize_weight(qw)
        out = lut_matmul(ap, asc, azm, w, bits=a_bits,
                         group_size=qw.group_size, backend=backend)
        return out.reshape(*lead, qw.n).astype(x.dtype)
    if a_bits is not None:
        lead = x.shape[:-1]
        x2 = x.reshape(-1, qw.k)
        ap, asc, azm = act_quant(x2, bits=a_bits, group_size=qw.group_size,
                                 backend=backend)
        xq = _ref.act_dequant(ap, asc, azm, bits=a_bits,
                              group_size=qw.group_size).astype(x.dtype)
        x = xq.reshape(*lead, qw.k)
    return quant_matmul(x, qw, backend=backend)
