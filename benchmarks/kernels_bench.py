"""Per-kernel microbenchmark: ref-path wall time + bytes accounting.

Wall-times on this CPU host are indicative only (the kernels target TPU;
interpret mode is a correctness harness, ~1000x slower than compiled),
so the table reports the REF path (XLA-compiled jnp) plus the
bytes-moved model that determines TPU performance.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import kvwire
from repro.kernels import ops
from repro.kernels import paged_attention as paged_attn
from repro.models import attention
from repro.obs import time_fn


def _time(fn, reps=3):
    return time_fn(fn, reps=reps)


def run_fused(verbose: bool = True) -> dict:
    """Fused paged-attention vs the gather+dequant+attention baseline.

    One decode step over the wire-format paged pool, kv bits
    {fp, 8, 4, 2} x context length: the triple round-trip the fused
    kernel eliminates, timed against the XLA fallback on identical
    pages.  On a CPU host the fused column runs the interpreter (a
    correctness harness, orders of magnitude slower than a compiled
    TPU kernel) — the regress gate compares same-backend history only,
    so the columns are self-consistent, never cross-backend.
    """
    b, kvh, g, d, gs = 2, 2, 2, 64, 16
    page_size = 16
    mode = paged_attn.default_mode() if paged_attn.available() else None
    key = jax.random.key(0)
    rows = {}
    for ctx in (128, 512):
        pps = ctx // page_size
        n_pages = b * pps + 1                     # page 0 = scratch
        kf = jax.random.normal(key, (n_pages, page_size, kvh, d),
                               jnp.float32)
        vf = jax.random.normal(jax.random.fold_in(key, 1), kf.shape,
                               jnp.float32)
        q = jax.random.normal(jax.random.fold_in(key, 2),
                              (b, 1, kvh, g, d), jnp.float32)
        table = (1 + jnp.arange(b * pps, dtype=jnp.int32)).reshape(b, pps)
        pos = jnp.full((b,), ctx - 1, jnp.int32)
        for bits in (None, 8, 4, 2):
            if bits is None:
                k_pg, v_pg = kf, vf
            else:
                k_pg = kvwire.quantize_kv(kf, bits, gs)
                v_pg = kvwire.quantize_kv(vf, bits, gs)
            k_pg, v_pg = kvwire.stored_pages(k_pg), kvwire.stored_pages(v_pg)
            label = "fp" if bits is None else f"kv{bits}"

            def baseline(k_pg=k_pg, v_pg=v_pg, bits=bits):
                kk = kvwire.gather_pages(k_pg, table, page_size, kvh)
                vv = kvwire.gather_pages(v_pg, table, page_size, kvh)
                if bits is not None:
                    kk = kvwire.dequantize_kv(kk, d)
                    vv = kvwire.dequantize_kv(vv, d)
                return attention.decode_attention(q, kk, vv, pos)

            t = _time(jax.jit(baseline), reps=2)
            rows[f"paged_attn_{label}_ctx{ctx}_baseline_ms"] = t * 1e3
            if mode is None:
                continue                          # no Pallas: XLA-only row

            def fused(k_pg=k_pg, v_pg=v_pg):
                return paged_attn.paged_attention(
                    q, k_pg, v_pg, table, pos, page_size=page_size,
                    interpret=mode == "interpret")

            t = _time(fused, reps=2)
            rows[f"paged_attn_{label}_ctx{ctx}_fused_ms"] = t * 1e3

    if verbose:
        print(f"\n== fused paged-attention vs gather+dequant baseline "
              f"(fused mode: {mode or 'unavailable'}) ==")
        for ctx in (128, 512):
            for label in ("fp", "kv8", "kv4", "kv2"):
                base = rows[f"paged_attn_{label}_ctx{ctx}_baseline_ms"]
                fkey = f"paged_attn_{label}_ctx{ctx}_fused_ms"
                fstr = f"{rows[fkey]:8.2f} ms fused" if fkey in rows \
                    else "     n/a fused"
                print(f"  {label:>4} ctx {ctx:4d}: {base:8.2f} ms baseline"
                      f"  {fstr}")
    return rows


def run(verbose: bool = True) -> dict:
    m, k, n, gs = 256, 4096, 4096, 128
    key = jax.random.key(0)
    x = jax.random.normal(key, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n))
    rows = {}

    t_fp = _time(jax.jit(lambda: x @ w.astype(x.dtype)))
    rows["fp32_matmul_ms"] = t_fp * 1e3
    for bits in (8, 4, 2):
        qw = ops.quantize_weight(w, bits, gs)
        t = _time(jax.jit(lambda qw=qw: ops.quant_matmul(
            x, qw, backend="ref")))
        rows[f"quant_matmul_b{bits}_ms"] = t * 1e3
        rows[f"quant_matmul_b{bits}_bytes"] = qw.nbytes()
    t_aq = _time(jax.jit(lambda: ops.act_quant(
        x, bits=4, group_size=gs, backend="ref")[0]))
    rows["act_quant_b4_ms"] = t_aq * 1e3

    if verbose:
        print("\n== kernel microbench (ref path on CPU host) ==")
        print(f"  fp32 matmul {m}x{k}x{n}: {rows['fp32_matmul_ms']:.1f} ms "
              f"({w.size * 4:,} weight bytes)")
        for bits in (8, 4, 2):
            print(f"  quant_matmul {bits}-bit: "
                  f"{rows[f'quant_matmul_b{bits}_ms']:.1f} ms "
                  f"({rows[f'quant_matmul_b{bits}_bytes']:,} weight bytes)")
        print(f"  act_quant 4-bit: {rows['act_quant_b4_ms']:.1f} ms")
    return rows


if __name__ == "__main__":
    run()
    run_fused()
