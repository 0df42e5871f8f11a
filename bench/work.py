"""Operations and bytes each kernel and each step needs, from shapes.

Only needed work counts: real rows (the slots that hold a request, the
prompt's own tokens, never the padding of the 2048 bucket), the live pages
of each context (never the pages past it or the scratch page), the true
vocabulary (never its padding).  What implements a kernel does not change
its count, so a faster implementation raises its roofline share, and no
reading of a share can pass 100% unless the time is short of the work.

Bytes of a packed projection are its wire bytes: ``K * N * bits / 8``
codes plus an f32 scale and zmin per ``group``-row region and column,
equal to ``QWeight.nbytes()`` (checked by ``tests/bench``).  Bytes of a
page are ``page_size * kv_heads * (head_dim * bits / 8 + 8 * head_dim /
kv_group)`` per key or value leaf, equal to the pool's own page bytes.
"""
from __future__ import annotations

import math

from bench import weights

ACT_BYTES = 2          # bfloat16 activations in and out of a kernel


def packed_bytes(k: int, n: int, *, bits: int = 4, group: int = 128) -> int:
    return k * n * bits // 8 + 2 * 4 * (k // group) * n


def matmul(m: int, k: int, n: int, *, bits: int = 4,
           group: int = 128) -> tuple[float, float]:
    """(FLOPs, bytes) of x (m, k) @ packed w (k, n)."""
    return (2.0 * m * k * n,
            packed_bytes(k, n, bits=bits, group=group)
            + ACT_BYTES * m * (k + n))


def page_bytes(md: dict, serving: dict) -> int:
    """Bytes of one page of one layer's key and value leaves together."""
    hd, g = md["hd"], serving["kv_group"]
    per_head = hd * serving["kv_bits"] // 8 + 8 * (hd // g)
    return 2 * serving["page_size"] * md["kv"] * per_head


def projections(md: dict) -> list[tuple[int, int]]:
    """(K, N) of the packed projections of one decoder layer."""
    return list(weights.projections(md).values())


def quant_matmul_calls(md: dict, rows: int,
                       head_rows: int) -> list[tuple[float, float]]:
    """(FLOPs, bytes) of every ``quant_matmul`` call of one forward pass
    over ``rows`` real rows: each layer's projections, then an untied,
    packed output head over ``head_rows`` (a prefill reads one position;
    a tied head is an XLA matmul with the embedding, not this kernel)."""
    calls = [matmul(rows, k, n) for k, n in projections(md)] * md["layers"]
    if not md["tied"]:
        calls.append(matmul(head_rows, md["d"], md["vocab"]))
    return calls


def attention(md: dict, contexts: list[int],
              serving: dict) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's paged attention at decode: each slot's
    query against its live context (QK and PV), over its live pages."""
    hq, hd = md["heads"], md["hd"]
    flops = sum(4.0 * hq * hd * c for c in contexts)
    pages = sum(math.ceil(c / serving["page_size"]) for c in contexts)
    io = ACT_BYTES * 2 * len(contexts) * hq * hd
    return flops, pages * page_bytes(md, serving) + io


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline bound of one call, in seconds."""
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])


def decode_flops(md: dict, contexts: list[int]) -> float:
    """Model FLOPs one decode step needs for its real slots."""
    rows = len(contexts)
    proj = sum(2.0 * rows * k * n for k, n in projections(md))
    attn = sum(4.0 * md["heads"] * md["hd"] * c for c in contexts)
    return md["layers"] * (proj + attn) + 2.0 * rows * md["d"] * md["vocab"]


def prefill_flops(md: dict, length: int) -> float:
    """Model FLOPs a prefill of ``length`` real tokens needs: every
    projection over the prompt, causal attention, one row of the head."""
    proj = sum(2.0 * length * k * n for k, n in projections(md))
    attn = 2.0 * md["heads"] * md["hd"] * length * (length + 1)
    return md["layers"] * (proj + attn) + 2.0 * md["d"] * md["vocab"]
