"""Fused paged-attention Pallas kernel: flash-decode over wire-format pages.

The XLA paged-decode path pays three HBM round-trips on exactly the data
the LQ format compressed: gather wire pages into a logical view, dequantize
that view to a full fp pool copy, then attend over it
(``models/attention.py`` paged branch).  This kernel fuses all three — the
page table is a scalar-prefetch operand, so each grid step's BlockSpec
``index_map`` streams ONE physical page of packed codes (+ per-region
scale/zmin) straight into VMEM, dequantizes in-register, and folds the page
into an online-softmax accumulator (the flash-decode recurrence).  HBM
traffic is the wire bytes, once.

Dequant paths per page (``dequant=``):

  "affine"  unpack codes and apply ``k = codes * scale + zmin`` per local
            region by algebra: one matmul on the codes per region, the
            scale and zmin applied to its result — the throughput path,
            any bits.
  "lut"     bits <= 4: the paper's Table-Lookup trick (section V) applied
            to attention, reusing the ``core/lut.py`` /
            ``kernels/lut_matmul.py`` masked-matmul dataflow.  With n-bit
            codes there are only 2^n distinct values, so per local region

                q . k      = scale * sum_v v * (q @ mask_v) + zmin * sum_j q_j
                p . v_col  = sum_v v * (p*scale @ mask_v)   + (p @ zmin)

            with ``mask_v = (codes == v)`` a {0,1} matrix — table build and
            read are adds + binary matmuls, never a materialized fp page.
  "auto"    "lut" when the pool is quantized at bits <= 4, else "affine"
            ("fp" pools skip dequant entirely).

Grid ``(B, P)`` — batch parallel, the page axis sequential ("arbitrary")
so the m/l/acc VMEM scratch carries the running softmax state across
pages.  Each step reads one page as ``(page_size*KV, X)`` rows (a free
reshape of the pool leaf), so every block is full-width in its last two
dims.  Queries arrive as (B, Lq, KV, G, D); kv heads, GQA groups and the
multi-query run (Lq = k+1, the speculative verify) flatten onto one
(KV*Lq*G, D) row block, scored against every page row at once, with a
head mask keeping each query row on its own kv head.  Masking matches
``decode_attention`` over the gathered view: key position
``p*page_size + t`` is visible to query row i iff it is ``<= pos[b] + i``
— an all-masked page contributes nothing because masked probabilities
are forced to zero *after* the running-max update.

The grid stays static, but each slot stops at its last live table entry
``last[b] = (pos[b] + Lq - 1) // page_size``: the wrapper repeats that
entry over the rest of the slot's table row, so the page ``index_map``
repeats its block (the pipeline fetches nothing), and the per-page body
runs under ``p <= last[b]`` (a third scalar-prefetch operand).  Those
entries are masked for every query row, so skipping them changes no bit
of the output, and whatever the table holds there (scratch padding or a
stale page) is never read.  The clamp lives in the wrapper, not the
``index_map``: six index maps recomputing it each grid step cost ~0.2 us
a live step on a v5e.

A sliding-window layer (static ``window``) also starts late: query row i
sees only keys in ``(pos[b] + i - window, pos[b] + i]``, so every entry
before ``first[b] = max(pos[b] - window + 1, 0) // page_size`` is masked
for all rows.  The wrapper repeats entry ``first[b]`` over the entries
before it (no fetch), the body runs under ``first[b] <= p <= last[b]``
(``first`` a fourth scalar-prefetch operand), and the in-page mask drops
keys at or below ``pos[b] + i - window``.  ``window=None`` traces to the
kernel above, unchanged.

Quantized pages unpack into ``cpb`` code planes by shift and mask (plane i
holds feature ``cpb*j + i`` at lane j).  The wrapper hands the kernel its
queries in the same plane order, stacked once per local region with the
other regions' lanes zeroed, and restores feature order on the output, so
the kernel never interleaves lanes.

``interpret=True`` runs the same kernel on CPU (CI parity tests);
``tests/test_chip_compile.py`` compiles it for a v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

try:  # pallas is optional at import time: gate, don't crash (ROADMAP env)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _PALLAS_ERR = None
except Exception as e:  # pragma: no cover - exercised on pallas-less hosts
    pl = pltpu = None
    _PALLAS_ERR = e

NEG_INF = -1e30
DEQUANT_MODES = ("auto", "affine", "lut")


def available() -> bool:
    """Whether the Pallas toolchain imported (kernel or interpret mode)."""
    return pl is not None


def default_mode() -> str:
    """Execution mode for this host: compiled on TPU, interpret elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


def resolve_mode(fused: bool, *, obs=None) -> str | None:
    """Map an engine flag to a kernel mode.  Off the TPU, a missing Pallas
    toolchain falls back to the XLA gather+dequant path (``None``); on a
    TPU it is an error, never a quiet switch away from the kernel.

    A downgrade (fused requested, Pallas missing) is an SLO-relevant
    silent failure: when an enabled ``obs`` is passed, it is reported via
    :func:`report_fallback` so the run's trace/metrics carry the truth.
    """
    if not fused:
        return None
    if not available():
        if jax.default_backend() == "tpu":
            raise RuntimeError("fused attention requested on a TPU but "
                               f"Pallas failed to import: {_PALLAS_ERR!r}")
        report_fallback(obs)
        return None
    return default_mode()


def report_fallback(obs) -> bool:
    """Emit the one-shot ``fused_fallback`` trace event + counter.

    Returns True when something was recorded (engines use this to latch
    their own once-per-engine guard across late obs attachment)."""
    if obs is None or not getattr(obs, "enabled", False):
        return False
    obs.event("fused_fallback", backend=jax.default_backend(),
              error=repr(_PALLAS_ERR) if _PALLAS_ERR is not None else "")
    obs.metrics.counter("fused_fallback_total").inc()
    return True


def _infer_bits(packed_d: int, d: int) -> int:
    return {1: 8, 2: 4, 4: 2, 8: 1}[d // packed_d]


def dequant_path(bits: int | None, dequant: str = "auto") -> str:
    """The per-page dequant dataflow a pool format lowers to:
    ``"fp"`` (no dequant), ``"affine"``, or ``"lut"`` — the ``auto``
    policy picks LUT whenever the table fits (bits <= 4)."""
    if dequant not in DEQUANT_MODES:
        raise ValueError(f"dequant must be one of {DEQUANT_MODES}, "
                         f"got {dequant!r}")
    if bits is None:
        return "fp"
    lut = dequant == "lut" or (dequant == "auto" and bits <= 4)
    if lut and bits > 4:
        raise ValueError("LUT dequant needs kv bits <= 4 (section V.A)")
    return "lut" if lut else "affine"


def _unpack_planes(pk, bits: int) -> list:
    """In-register unpack of uint8 lanes -> ``cpb`` int32 code planes.

    Plane i holds code i of every byte (shift ``i * bits``, matching
    ``core/packing.pack``), i.e. features ``d = cpb*j + i``.  The planes
    are never interleaved back along lanes: the wrapper hands the kernel
    queries in the same plane order and restores feature order on the
    output, so the kernel needs no lane shuffle.
    """
    p = pk.astype(jnp.int32)
    if bits == 8:
        return [p]
    mask = (1 << bits) - 1
    return [(p >> (i * bits)) & mask for i in range(8 // bits)]


def _dot_t(a, b):
    """a (M, K) @ b (N, K)^T, f32 accumulation.  At the default precision
    Mosaic feeds f32 operands to the MXU as one bf16 pass, as XLA does."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _allowed(nq: int, rows: int, *, lqg: int, gq: int, kvh: int,
             page_size: int, pos_b, p, window: int | None = None):
    """(nq, rows) mask: query row ``h*lqg + l*gq + g`` may see page row
    ``t*kvh + h'`` iff it is the same kv head and key pos <= query pos
    (and, with a ``window``, key pos > query pos - window)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (nq, rows), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (nq, rows), 1)
    qpos = pos_b + (row % lqg) // gq
    kpos = p * page_size + col // kvh
    ok = (row // lqg == col % kvh) & (kpos <= qpos)
    if window is not None:
        ok &= kpos > qpos - window
    return ok


def _last_page(pos_b, *, lq: int, page_size: int, n_tbl: int):
    """Slot's last live table entry: query row i sees keys <= pos_b + i,
    so every entry past ``(pos_b + lq - 1) // page_size`` is masked for
    all rows (clamped to the table for a run tailing past it)."""
    return jnp.minimum((pos_b + lq - 1) // page_size, n_tbl - 1)


def _first_page(pos_b, *, window: int, page_size: int):
    """Slot's first live table entry under a sliding window: the earliest
    query row (row 0) sees keys > pos_b - window, so every entry before
    ``(pos_b - window + 1) // page_size`` is masked for all rows."""
    return jnp.maximum(pos_b - window + 1, 0) // page_size


def _live(p, bounds):
    """Whether grid step ``p`` of slot ``program_id(0)`` holds a live page:
    ``bounds`` is ``(last_ref,)`` or, under a window, ``(last_ref,
    first_ref)``."""
    b = pl.program_id(0)
    live = p <= bounds[0][b]
    if len(bounds) > 1:
        live &= p >= bounds[1][b]
    return live


def _online_step(s, allowed, m_ref, l_ref):
    """One flash-decode page update of the running max / denominator;
    returns (probabilities, accumulator correction).

    Masked probabilities are zeroed AFTER the max update: an all-masked
    page has m == NEG_INF and exp(s - m) == 1 there, which would otherwise
    poison l with phantom mass.
    """
    s = jnp.where(allowed, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    pmat = jnp.where(allowed, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + pmat.sum(axis=-1, keepdims=True)
    m_ref[...] = m_new
    return pmat, corr


def _init(p, acc_ref, m_ref, l_ref):
    @pl.when(p == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)


def _flush(p, p_steps, o_ref, acc_ref, l_ref):
    @pl.when(p == p_steps - 1)
    def _():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _kernel_fp(tbl_ref, pos_ref, *refs, page_size: int, gq: int, lqg: int,
               kvh: int, p_steps: int, sm_scale: float,
               window: int | None = None):
    n_bounds = 1 if window is None else 2
    bounds = refs[:n_bounds]
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs[n_bounds:]
    p = pl.program_id(1)
    pos_b = pos_ref[pl.program_id(0)]
    _init(p, acc_ref, m_ref, l_ref)

    @pl.when(_live(p, bounds))
    def _():
        q = q_ref[0, 0]                                        # (nq, D)
        k = k_ref[0].astype(jnp.float32)                       # (R, D)
        v = v_ref[0].astype(jnp.float32)
        s = _dot_t(q, k) * sm_scale
        allowed = _allowed(q.shape[0], k.shape[0], lqg=lqg, gq=gq, kvh=kvh,
                           page_size=page_size, pos_b=pos_b, p=p,
                           window=window)
        pmat, corr = _online_step(s, allowed, m_ref, l_ref)
        acc_ref[0] = acc_ref[0] * corr + _dot(pmat, v)

    _flush(p, p_steps, o_ref, acc_ref, l_ref)


def _kernel_quant(tbl_ref, pos_ref, *refs,
                  bits: int, gr: int, lut: bool, page_size: int, gq: int,
                  lqg: int, kvh: int, p_steps: int, sm_scale: float,
                  window: int | None = None):
    n_bounds = 1 if window is None else 2
    bounds = refs[:n_bounds]
    (q_ref, qsum_ref, kp_ref, ks_ref, kz_ref, vp_ref, vs_ref, vz_ref, o_ref,
     acc_ref, m_ref, l_ref) = refs[n_bounds:]
    p = pl.program_id(1)
    pos_b = pos_ref[pl.program_id(0)]
    _init(p, acc_ref, m_ref, l_ref)
    nq = m_ref.shape[0]
    codes_v = range(1, 1 << bits)                              # v=0 adds 0

    def code_matmul(mm, a, codes):
        """``mm(a, codes)`` directly (affine), or as the section-V table:
        sum_v v * mm(a, codes == v) — adds over binary matmuls."""
        if not lut:
            return mm(a, codes.astype(jnp.float32))
        out = None
        for vcode in codes_v:
            t = jnp.float32(vcode) * mm(a, (codes == vcode).astype(
                jnp.float32))
            out = t if out is None else out + t
        return out

    @pl.when(_live(p, bounds))
    def _():
        # scores: per region r, scale_r * (q_r . codes) + zmin_r * sum(q_r).
        # q_ref stacks the region-masked query rows (row r*nq + i holds query
        # row i restricted to region r), so one matmul per plane gives every
        # region's code dot at once.
        k_sc_t = ks_ref[0].T                                   # (Gr, R)
        cd = None
        for i, codes in enumerate(_unpack_planes(kp_ref[0], bits)):
            t = code_matmul(_dot_t, q_ref[0, i], codes)        # (Gr*nq, R)
            cd = t if cd is None else cd + t
        s = _dot_t(qsum_ref[0], kz_ref[0])                     # zmin terms
        for r in range(gr):
            s = s + cd[r * nq:(r + 1) * nq] * k_sc_t[r:r + 1]
        s = s * sm_scale
        allowed = _allowed(nq, s.shape[1], lqg=lqg, gq=gq, kvh=kvh,
                           page_size=page_size, pos_b=pos_b, p=p,
                           window=window)
        pmat, corr = _online_step(s, allowed, m_ref, l_ref)

        # values: out[:, d in r] = sum_t p_t * (scale_tr * code_td + zmin_tr);
        # the scale folds into p (one stacked row block per region), the zmin
        # term is a per-region row sum, and a lane mask keeps region r's block
        # on region r's lanes.
        v_sc_t = vs_ref[0].T
        v_zm_t = vz_ref[0].T
        p_sc = jnp.concatenate([pmat * v_sc_t[r:r + 1] for r in range(gr)],
                               axis=0)                         # (Gr*nq, R)
        zsum = jnp.concatenate(
            [(pmat * v_zm_t[r:r + 1]).sum(axis=-1, keepdims=True)
             for r in range(gr)], axis=0)                      # (Gr*nq, 1)
        w = acc_ref.shape[-1]
        region = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1) // (w // gr)
        for i, codes in enumerate(_unpack_planes(vp_ref[0], bits)):
            pv = code_matmul(_dot, p_sc, codes) + zsum         # (Gr*nq, W)
            out = jnp.zeros((nq, w), jnp.float32)
            for r in range(gr):
                out = out + jnp.where(region == r,
                                      pv[r * nq:(r + 1) * nq], 0.0)
            acc_ref[i] = acc_ref[i] * corr + out

    _flush(p, p_steps, o_ref, acc_ref, l_ref)


@functools.partial(jax.jit, static_argnames=("window", "dequant",
                                             "interpret"))
def paged_attention(q, k_pages, v_pages, page_table, pos, *,
                    window: int | None = None, dequant: str = "auto",
                    interpret: bool = False):
    """Fused flash-decode over a paged pool, wire format and all.

    q (B, Lq, KV, G, D); ``k_pages``/``v_pages`` are one pool leaf — fp
    (n_pages, page_size, KV, D) arrays or LQ wire dicts with
    (n_pages, page_size, KV, D/cpb) packed codes (``core/kvwire.py``);
    page_table (B, P) int32 physical page ids, in table order (position t
    lives at table entry t // page_size); pos (B,) int32 — the absolute
    position of each slot's FIRST query row (query i attends ``<= pos+i``);
    ``window`` (static) a sliding window: query i attends only keys in
    ``(pos+i-window, pos+i]``.
    Returns (B, Lq, KV, G, D) in q's dtype.  Token parity with
    ``gather_pages -> dequantize_kv -> decode_attention`` is the contract
    (tests/test_paged_attention.py); bit-identity is not, since the online
    softmax re-associates the reduction.
    """
    if pl is None:
        raise RuntimeError(f"Pallas unavailable: {_PALLAS_ERR!r}; use the "
                           "XLA gather+dequant path instead")
    b, lq, kvh, gq, d = q.shape
    lqg = lq * gq
    nq = kvh * lqg
    n_tbl = page_table.shape[1]
    quant = isinstance(k_pages, dict)
    page_size = (k_pages["packed"] if quant else k_pages).shape[1]
    sm_scale = d ** -0.5

    # query rows (h, l, g) = h*lqg + l*gq + g; page rows (t, h') = t*KV + h'
    qm = q.transpose(0, 2, 1, 3, 4).reshape(b, nq, d).astype(jnp.float32)
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    # each slot's row repeats its last live entry from there on (and,
    # under a window, its first live entry before it)
    last = _last_page(posb, lq=lq, page_size=page_size, n_tbl=n_tbl)
    entry = jnp.minimum(jnp.arange(n_tbl)[None], last[:, None])
    bounds = (last,)
    if window is not None:
        first = _first_page(posb, window=window, page_size=page_size)
        entry = jnp.maximum(entry, first[:, None])
        bounds = (last, first)
    tbl = jnp.take_along_axis(page_table.astype(jnp.int32), entry, axis=1)

    def fixed(bi, p, *_):
        return (bi, 0, 0, 0)

    def page_map(bi, p, tbl_ref, *_):
        return (tbl_ref[bi, p], 0, 0)

    def rows(a):                       # (n_pages, ps, KV, X) -> (n, ps*KV, X)
        return a.reshape(a.shape[0], a.shape[1] * a.shape[2], a.shape[3])

    def page_spec(a):
        return pl.BlockSpec((1,) + a.shape[1:], page_map)

    statics = dict(page_size=page_size, gq=gq, lqg=lqg, kvh=kvh,
                   p_steps=n_tbl, sm_scale=sm_scale)
    if window is not None:
        statics["window"] = window
    if quant:
        packed_d = k_pages["packed"].shape[-1]
        gr = k_pages["scale"].shape[-1]
        bits = _infer_bits(packed_d, d)
        cpb, w = d // packed_d, packed_d
        lut = dequant_path(bits, dequant) == "lut"
        kernel = functools.partial(_kernel_quant, bits=bits, gr=gr, lut=lut,
                                   **statics)
        # query planes (feature cpb*j + i -> plane i, lane j), stacked per
        # region: row r*nq + n holds query row n masked to region r's lanes
        qp = qm.reshape(b, nq, w, cpb).transpose(0, 3, 1, 2)
        onehot = (jnp.arange(w) // (w // gr))[None] == jnp.arange(gr)[:, None]
        qs = jnp.where(onehot[None, None, :, None, :], qp[:, :, None], 0.0)
        qs = qs.reshape(b, cpb, gr * nq, w)
        qsum = qm.reshape(b, nq, gr, d // gr).sum(-1)          # (b, nq, Gr)
        leaves = [rows(t[f]) for t in (k_pages, v_pages)
                  for f in ("packed", "scale", "zmin")]
        in_specs = [pl.BlockSpec((1, cpb, gr * nq, w), fixed),
                    pl.BlockSpec((1, nq, gr),
                                 lambda bi, p, *_: (bi, 0, 0))
                    ] + [page_spec(a) for a in leaves]
        operands = (qs, qsum, *leaves)
        name = f"paged_attention_{'lut' if lut else 'affine'}_b{bits}"
    else:
        dequant_path(None, dequant)            # still validates the mode
        cpb, w = 1, d
        kernel = functools.partial(_kernel_fp, **statics)
        leaves = [rows(k_pages), rows(v_pages)]
        in_specs = [pl.BlockSpec((1, 1, nq, d), fixed)] \
            + [page_spec(a) for a in leaves]
        operands = (qm[:, None], *leaves)
        name = "paged_attention_fp"

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(bounds),
            grid=(b, n_tbl),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, cpb, nq, w), fixed),
            scratch_shapes=[pltpu.VMEM((cpb, nq, w), jnp.float32),
                            pltpu.VMEM((nq, 1), jnp.float32),
                            pltpu.VMEM((nq, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, cpb, nq, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(tbl, posb, *bounds, *operands)
    out = out.transpose(0, 2, 3, 1).reshape(b, kvh, lq, gq, d)
    return out.transpose(0, 2, 1, 3, 4).astype(q.dtype)
