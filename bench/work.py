"""Operations and bytes of one kernel call, from shapes, and its least time.

A configuration's family (``bench/families/<family>/plain.py``) counts,
with these, the calls one step of its architecture makes
(``quant_matmul_calls``, ``attention_calls``) and the model FLOPs of a
decode step and of a prefill; the per-layer readers in ``bench/metrics/``
take those counts.

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
    """Bytes of one page of one layer's key and value leaves together
    (``md``: a family's sizes with the head size ``hd`` and the number
    of key/value heads ``kv``)."""
    hd, g = md["hd"], serving["kv_group"]
    per_head = hd * serving["kv_bits"] // 8 + 8 * (hd // g)
    return 2 * serving["page_size"] * md["kv"] * per_head


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline bound of one call, in seconds."""
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
