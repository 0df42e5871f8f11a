#!/usr/bin/env python3
"""Serve granite-3-2b at full width on one TPU chip and check the result.

Runs the main serving path once, through the same entry code as

    python -m repro.launch.serve --arch granite-3-2b --full --scheme lq4w \
        --kv-bits 4 --kv-group 16 --fused-attention --continuous 4 \
        --prompt-len 128 --steps 15

all 40 layers at published widths with random weights from a fixed seed:
lq4w packed weights through the compiled ``quant_matmul`` kernel, a 4-bit
KV pool, and paged decode through the compiled fused ``paged_attention``
kernel.  Then it checks, failing on the first miss:

  * every request finishes with its token count;
  * one compiled decode step, attention mode ``fused-pallas``;
  * the compiled decode step holds both kernels as ``tpu_custom_call``;
  * ``quant_matmul`` (compiled) against the jnp reference on granite
    projection shapes, at decode M and at the prefill bucket;
  * fused ``paged_attention`` against gather -> dequantize ->
    ``decode_attention`` over the live pool.

Lines starting ``info:`` are informational (times, peak bytes, tokens/s).
The last line of a passing run is one JSON object naming the device.
Any failure exits non-zero and prints no such line; so does a host where
JAX finds no TPU, and a directory holding this file without the repo.

    python chip_smoke.py        # one chip, one process
"""
from __future__ import annotations

import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
SERVE_ARGV = ["--arch", "granite-3-2b", "--full", "--scheme", "lq4w",
              "--kv-bits", "4", "--kv-group", "16", "--fused-attention",
              "--continuous", "4", "--prompt-len", "128", "--steps", "15",
              "--max-slots", "4"]
# max |kernel - reference| / max |reference|, the references at HIGHEST
# precision.  The kernels' f32 dots run at the compiler's default, one
# bf16 MXU pass (~2^-9 relative per input), as the XLA path's do; on a
# v5e that alone gives attention 4e-3 to 3e-2 as scores sharpen.  A
# wrong plane, region or page shows up at order 1.
MATMUL_TOL = 2e-2
ATTN_TOL = 2e-2


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)
    print(f"pass: {what}")


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def check_decode_hlo(server):
    """The compiled decode step runs both Pallas kernels."""
    engine, pcfg = server.engine, server.engine.pcfg
    zeros = jnp.zeros((pcfg.max_slots,), jnp.int32)
    t = time.perf_counter()
    text = engine._step_paged.lower(
        engine.params, server.pool.pages, zeros,
        jnp.zeros((pcfg.max_slots, pcfg.pages_per_slot), jnp.int32), zeros,
        (jax.random.key(0), np.uint32(0))).compile().as_text()
    print(f"info: decode step lower+compile for HLO: "
          f"{time.perf_counter() - t:.2f}s")
    calls = [line.split("=")[0].strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    print(f"info: decode step custom calls: {calls}")
    check(any("quant_matmul_b4" in c for c in calls),
          "decode HLO has the quant_matmul kernel as tpu_custom_call")
    check(any("paged_attention_" in c for c in calls),
          "decode HLO has the paged_attention kernel as tpu_custom_call")


def check_live_pool_attention(server, args):
    """Fused kernel vs gather -> dequantize -> decode_attention on the
    pool as live requests left it, first and last layer."""
    from repro.core import kvwire
    from repro.kernels import paged_attention as paged_attn
    from repro.models import attention, transformer
    from repro.serve import RequestParams

    cfg, pcfg = server.engine.cfg, server.engine.pcfg
    rng = np.random.default_rng(11)
    rids = [server.submit(list(map(int, rng.integers(0, cfg.vocab_size,
                                                     args.prompt_len))),
                          RequestParams(max_new_tokens=8))
            for _ in range(pcfg.max_slots)]
    for _ in range(4 * pcfg.max_slots):
        live = [r for r in server.scheduler.active_requests()
                if r.rid in rids and len(r.generated) >= 2]
        if len(live) == len(rids):
            break
        server.step()
    check(len(live) == len(rids), f"{len(rids)} requests live in the pool")
    table = np.stack([server.pool.table_array(r.rid, pcfg.pages_per_slot)
                      for r in live])
    # last cache row each request has written
    pos = np.asarray([len(r.prompt) + len(r.generated) - 2 for r in live],
                     np.int32)
    q = jax.random.normal(jax.random.key(5), (len(live), 1, cfg.n_kv_heads,
                          cfg.n_heads // cfg.n_kv_heads, cfg.head_dim),
                          jnp.float32)
    (run,) = transformer.stack_runs(server.pool.pages)   # uniform 4-bit
    stack = run[0]["self"]
    ps, kvh = pcfg.page_size, cfg.n_kv_heads
    for layer in (0, cfg.n_layers - 1):
        k, v = stack["k"], stack["v"]
        got = paged_attn.paged_attention(q, k, v, table, pos, page_size=ps,
                                         layer=layer)
        with jax.default_matmul_precision("highest"):
            want = attention.decode_attention(
                q, kvwire.dequantize_kv(kvwire.gather_pages(
                    k, table, ps, kvh, layer), cfg.head_dim),
                kvwire.dequantize_kv(kvwire.gather_pages(
                    v, table, ps, kvh, layer), cfg.head_dim), pos)
        err = rel_err(got, want)
        print(f"info: fused paged_attention vs XLA reference, layer "
              f"{layer}: rel err {err:.3e}")
        check(err <= ATTN_TOL, f"fused paged_attention layer {layer} "
              f"within {ATTN_TOL} of the reference")
    server.drain()


def check_quant_matmul(params, pcfg):
    """Compiled quant_matmul vs the jnp reference on layer-0 weights."""
    from repro.kernels import ops

    blk = params["decoder"]["super"][0]
    weights = {"wq": blk["mixer"]["wq"]["w"], "wk": blk["mixer"]["wk"]["w"],
               "wi_up": blk["ffn"]["wi_up"]["w"],
               "wo_ffn": blk["ffn"]["wo"]["w"]}
    for name, stacked in weights.items():
        qw = jax.tree.map(lambda a: a[0], stacked)
        for m in (pcfg.max_slots, pcfg.max_context):
            x = jax.random.normal(jax.random.key(m), (m, qw.k), jnp.bfloat16)
            got = ops.quant_matmul(x, qw, backend="pallas")
            with jax.default_matmul_precision("highest"):
                want = ops.quant_matmul(x, qw, backend="ref")
            err = rel_err(got, want)
            print(f"info: quant_matmul {name} {m}x{qw.k}x{qw.n}: rel err "
                  f"{err:.3e}")
            check(err <= MATMUL_TOL, f"quant_matmul {name} M={m} within "
                  f"{MATMUL_TOL} of the reference")


def run() -> dict:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"needs a TPU; JAX found {dev.platform!r}")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch import serve
    except ImportError as e:
        raise SmokeFailure(f"run from a checkout of the repository: {e}")

    cache = serve.enable_compile_cache()
    print(f"info: device {dev.device_kind} x{len(devices)}, compile cache "
          f"{cache}")
    args = serve.build_parser().parse_args(SERVE_ARGV)
    t = time.perf_counter()
    cfg, params, ecfg = serve.build(args)
    jax.block_until_ready(params)
    print(f"info: built {cfg.name} ({cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}) with "
          f"packed {args.scheme} weights in {time.perf_counter() - t:.2f}s")
    check(cfg.name == "granite-3-2b" and cfg.n_layers == 40
          and cfg.d_model == 2048, "granite-3-2b at full width, 40 layers")

    out = serve.run_continuous(cfg, params, ecfg, args)
    server = out["server"]
    for rid in out["rids"]:
        req = server.scheduler.request(rid)
        check(req.state == "complete"
              and len(req.generated) == args.steps + 1,
              f"request {rid} finished with {args.steps + 1} tokens")
    stats = server.stats()
    check(stats["attention_mode"] == "fused-pallas",
          f"attention_mode {stats['attention_mode']}")
    check(stats["decode_compilations"] == 1,
          f"decode_compilations {stats['decode_compilations']}")
    print(f"info: compile (warm-up request) {out['warmup_s']:.2f}s")
    print(f"info: {out['tokens']} tokens in {out['serve_s']:.3f}s -> "
          f"{out['tokens'] / out['serve_s']:.1f} tok/s (4 staggered "
          f"requests, includes prefill)")

    check_live_pool_attention(server, args)
    check(server.engine.decode_compilations == 1,
          "still one compiled decode step after the live-pool phase")
    check_decode_hlo(server)
    check_quant_matmul(server.engine.params, server.engine.pcfg)

    stats = dev.memory_stats() or {}
    print(f"info: peak device bytes in use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def main() -> int:
    try:
        device = run()
    except Exception as e:
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
