"""``correct`` on a cell cut to test size: the harness drives the real
serving path on the CPU and compares with the plain reference; with the
timed path broken underneath, or with the float8 control in its place,
``correct`` comes out false."""
from __future__ import annotations

import numpy as np
import pytest

from bench_tiny import TINY_LIMIT, run_tiny, tiny_cell

from bench import reference
import repro.serve.engine as E


@pytest.mark.parametrize("seed", [1, 2])
def test_chat_is_correct(seed):
    # 3 s, so that a loaded test machine still serves more than 20 tokens
    res = run_tiny(tiny_cell(), seed, seconds=3.0)
    assert res["correct"], res["checks"]
    assert res["checks"]["served_tokens_compared"]["value"] > 20
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"setup_s", "output_tokens_per_s",
                                   "itl_p95_ms"}


def test_score_is_correct():
    res = run_tiny(tiny_cell(mix="score"), 5, seconds=2.0)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "ttft_p95_ms"}
    assert res["attempted"] >= 3


def _token_altered(mp):
    orig = E.greedy_sample
    mp.setattr(E, "greedy_sample",
               lambda logits, key: (orig(logits, key) + 1) % 1000)


def _state_unchanged(mp):
    step = E.PagedEngine._step_paged_impl

    def frozen(self, params, pages, tokens, table, pos, key):
        toks, _ = step(self, params, pages, tokens, table, pos, key)
        return toks, pages
    mp.setattr(E.PagedEngine, "_step_paged_impl", frozen)


def _half_the_batch(mp):
    step = E.PagedEngine._step_paged_impl

    def half(self, params, pages, tokens, table, pos, key):
        tokens = tokens.at[tokens.shape[0] // 2:].set(0)
        return step(self, params, pages, tokens, table, pos, key)
    mp.setattr(E.PagedEngine, "_step_paged_impl", half)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_the_batch])
def test_a_broken_decode_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = run_tiny(tiny_cell(), 1)
    assert not res["correct"], res["checks"]


def test_a_broken_prefill_is_not_correct(monkeypatch):
    _token_altered(monkeypatch)
    res = run_tiny(tiny_cell(mix="score"), 5, seconds=2.0)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("mix", ["chat", "score"])
def test_the_float8_control_fails_the_limit(mix):
    """The reference in float8 (the precision below the configuration's
    bfloat16) in the program's place, read on the same served prompts and
    tokens: ``correct`` comes out false on every seed."""
    for seed in (1, 2, 3):
        res = run_tiny(tiny_cell(mix=mix), seed, control=True,
                       seconds=1.5 if mix == "chat" else 2.0)
        ctrl = res["checks"]["widest_logit_gap"]["value"]
        assert not res["correct"], (seed, ctrl)
        assert ctrl > TINY_LIMIT
        assert res["program_widest_logit_gap"] <= TINY_LIMIT


def test_reference_kv_rounding_and_dequant():
    x = np.linspace(-1, 1, 32, dtype=np.float32).reshape(2, 16)
    r = np.asarray(reference.kv_round(x, 4, 16))
    # each 16-wide region rounds to 15 steps of its own range
    for row, got in zip(x, r):
        assert np.abs(got - row).max() <= (row.max() - row.min()) / 30 + 1e-6
    # byte 0x21: row 0 code 1, row 1 code 2; byte 0xF0: row 2 code 0,
    # row 3 code 15; two 128-row regions, K = 256
    p = {"packed": np.tile(np.array([[0x21], [0xF0]], np.uint8), (64, 1)),
         "scale": np.full((2, 1), 0.5, np.float32),
         "zmin": np.full((2, 1), -1.0, np.float32)}
    w = np.asarray(reference.dequant(p))
    assert w.shape == (256, 1)
    assert w[:4, 0].tolist() == [-0.5, 0.0, -1.0, 6.5]
