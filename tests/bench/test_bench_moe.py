"""The routed-expert family (``bench/families/moe``): the tiny
``mellum2-12b-a2.5b.chat`` cell is correct and the float8 control fails
it, and the family's work counts equal hand arithmetic at the published
sizes."""
from __future__ import annotations

import json

import jax
import pytest

from bench_tiny import ROOT, TINY_LIMIT, run_tiny, tiny_cell

from bench import program, work
from bench import run as R
from repro.core import schemes
from repro.models import transformer

CONFIG = "mellum2-12b-a2.5b"


def _family():
    cfg = json.loads((ROOT / "bench" / "configs" / f"{CONFIG}.json")
                     .read_text())
    fam = R.load_family(cfg)
    return cfg, fam, fam.plain.dims(cfg)


def test_tiny_chat_is_correct():
    res = run_tiny(tiny_cell(CONFIG), 1, seconds=3.0)
    assert res["correct"], res["checks"]
    assert res["checks"]["served_tokens_compared"]["value"] > 20


def test_the_float8_control_fails_the_limit():
    for seed in (1, 2):
        res = run_tiny(tiny_cell(CONFIG), seed, control=True)
        assert not res["correct"], res["checks"]
        assert res["checks"]["widest_logit_gap"]["value"] > TINY_LIMIT
        assert res["program_widest_logit_gap"] <= TINY_LIMIT


def test_dims_at_the_published_sizes():
    _, _, md = _family()
    assert (md["d"], md["heads"], md["kv"], md["hd"], md["layers"]) == (
        2304, 32, 4, 128, 28)
    assert (md["experts"], md["top_k"], md["eff"], md["window"]) == (
        64, 8, 896, 1024)
    assert md["sliding"] == (True, True, True, False)
    assert md["yarn"] == (16.0, 8192.0, 32.0, 1.0, 1.2772588722239782)
    assert md["theta_sliding"] == md["theta_full"] == 500000.0
    assert (md["vocab"], md["vocab_pad"], md["tied"]) == (98304, 98304,
                                                          False)


def test_params_are_the_programs_own_tree():
    """The seeded arrays, handed over as the program's parameter types,
    have the tree, shapes and dtypes of ``init_params(qcfg=lq4w)``."""
    cfg, fam, md = _family()
    mc = program.model_config(cfg, fam)
    ours = program.params(jax.eval_shape(lambda: fam.plain.draw(md, 1)), mc,
                          fam)
    theirs = jax.eval_shape(lambda: transformer.init_params(
        mc, jax.random.key(0), qcfg=schemes.get("lq4w")))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_quant_matmul_calls_by_hand():
    _, fam, md = _family()
    calls = fam.plain.quant_matmul_calls(md, 32, 32)
    assert len(calls) == 4 * 28 + 1
    # wq: x (32, 2304) @ (2304, 4096); 18 regions of 128 rows
    wq = (2 * 32 * 2304 * 4096,
          2304 * 4096 // 2 + 8 * 18 * 4096 + 2 * 32 * (2304 + 4096))
    assert calls[0] == wq
    head = calls[-1]
    assert head == (2 * 32 * 2304 * 98304,
                    2304 * 98304 // 2 + 8 * 18 * 98304
                    + 2 * 32 * (2304 + 98304))


def test_expert_matmul_calls_by_hand():
    _, fam, md = _family()
    calls = fam.plain.expert_matmul_calls(md, 32)
    assert len(calls) == 3 * 28
    hit = 64 * (1 - (56 / 64) ** 32)                  # ~63.1 of 64
    assert fam.plain.experts_hit(md, 32) == pytest.approx(hit)
    assert fam.plain.experts_hit(md, 1) == pytest.approx(8)
    gate_bytes = 2304 * 896 // 2 + 8 * 18 * 896       # 1,161,216
    down_bytes = 896 * 2304 // 2 + 8 * 7 * 2304       # 1,161,216
    assert calls[0] == pytest.approx(
        (2 * 256 * 2304 * 896, hit * gate_bytes + 2 * 256 * (2304 + 896)))
    assert calls[2] == pytest.approx(
        (2 * 256 * 896 * 2304, hit * down_bytes + 2 * 256 * (896 + 2304)))
    assert work.packed_bytes(2304, 896) == gate_bytes


def test_attention_calls_by_hand():
    cfg, fam, md = _family()
    sv = cfg["serving"]
    page = work.page_bytes(md, sv)
    assert page == 2 * 16 * 4 * (128 * 4 // 8 + 8 * 8)
    calls = fam.plain.attention_calls(md, [1500, 1000], sv)
    assert len(calls) == 28
    io = 2 * 2 * 2 * 32 * 128
    # sliding: 1024 keys of 1500 over pages 29..93 (65); 1000 keys, 0..62
    slid = (4.0 * 32 * 128 * (1024 + 1000), (65 + 63) * page + io)
    # full: 1500 keys over 94 pages, 1000 over 63
    full = (4.0 * 32 * 128 * (1500 + 1000), (94 + 63) * page + io)
    assert calls[:4] == [slid, slid, slid, full]
    assert calls[4:] == calls[:4] * 6


def test_flops_by_hand():
    _, fam, md = _family()
    per_row = (2 * (2304 * 4096 * 2 + 2304 * 512 * 2)      # q, o; k, v
               + 2 * 8 * 3 * 2304 * 896                     # top-8 experts
               + 2 * 2304 * 64)                             # router
    assert fam.plain.decode_flops(md, [1500]) == pytest.approx(
        28 * per_row + 4 * 32 * 128 * (21 * 1024 + 7 * 1500)
        + 2 * 2304 * 98304)
    n = 1500
    slid = 1024 * 1025 / 2 + (n - 1024) * 1024
    full = n * (n + 1) / 2
    assert fam.plain.prefill_flops(md, n) == pytest.approx(
        28 * n * per_row + 4 * 32 * 128 * (21 * slid + 7 * full)
        + 2 * 2304 * 98304)
