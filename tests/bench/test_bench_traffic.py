"""The traffic generator: seeded, the same sizes and arrivals for every
seed, within the mix's limits."""
from __future__ import annotations

import json
import numpy as np
import pytest

from bench_tiny import ROOT

from bench import traffic

CHAT = json.loads((ROOT / "bench" / "traffic" / "chat.json").read_text())
SCORE = json.loads((ROOT / "bench" / "traffic" / "score.json").read_text())


def _key(reqs):
    return [(r.prompt.tolist(), r.max_new_tokens, r.due) for r in reqs]


@pytest.mark.parametrize("mix", ["chat", "score"])
def test_same_seed_same_requests(mix):
    m = CHAT if mix == "chat" else SCORE
    a = traffic.make(m, 77, slots=32, vocab=49155, seconds=20)
    b = traffic.make(m, 77, slots=32, vocab=49155, seconds=20)
    assert _key(a) == _key(b)


@pytest.mark.parametrize("mix", ["chat", "score"])
def test_seeds_change_tokens_not_work(mix):
    m = CHAT if mix == "chat" else SCORE
    a = traffic.make(m, 1, slots=32, vocab=49155, seconds=20)
    b = traffic.make(m, 2**31 + 11, slots=32, vocab=49155, seconds=20)
    assert [(len(r.prompt), r.max_new_tokens, r.due) for r in a] == \
        [(len(r.prompt), r.max_new_tokens, r.due) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in b]


def test_closed_backlog_behind_the_slots():
    reqs = traffic.make(CHAT, 5, slots=32, vocab=49155, seconds=20)
    assert len(reqs) == 32 + CHAT["backlog"]
    assert all(r.due is None for r in reqs)
    for r in reqs:
        assert r.max_new_tokens >= 1
        assert len(r.prompt) + r.max_new_tokens <= 1536 + 512
        assert 0 <= r.prompt.min() and r.prompt.max() < 49155
    back = reqs[32:]
    assert min(len(r.prompt) for r in back) >= 32
    assert max(len(r.prompt) for r in back) <= 1536
    assert 16 <= min(r.max_new_tokens for r in back)
    assert max(r.max_new_tokens for r in back) <= 512


def test_closed_in_flight_are_length_biased():
    reqs = traffic.make(CHAT, 5, slots=32, vocab=49155, seconds=20)
    back = np.array([r.max_new_tokens for r in reqs[32:]])
    totals = np.array([len(r.prompt) + r.max_new_tokens for r in reqs[:32]])
    prompts = np.array([len(r.prompt) for r in reqs[32:]])
    # a slot holds a long request for longer: in flight, outputs run
    # longer than the backlog's, so contexts do too
    assert totals.mean() > np.median(prompts) + np.median(back)


def test_open_arrivals_at_the_rate():
    a = traffic.make(SCORE, 3, slots=32, vocab=49155, seconds=40)
    due = np.array([r.due for r in a])
    assert (np.diff(due) > 0).all() and due[0] >= 0 and due[-1] < 40
    rate = SCORE["rate_per_s"]
    assert abs(len(due) - rate * 40) < 4 * np.sqrt(rate * 40)
    assert all(r.max_new_tokens == 1 for r in a)
    assert all(128 <= len(r.prompt) <= 2000 for r in a)


def test_lengths_are_quantiles():
    x = traffic.lengths({"median": 100, "sigma": 0.5, "min": 10,
                         "max": 1000}, 101)
    assert x[50] == 100 and (np.diff(x) >= 0).all()
    assert traffic.lengths({"fixed": 1}, 3).tolist() == [1, 1, 1]


@pytest.mark.parametrize("seed", [0, 2**31 - 1, 2**31 + 5, 2**40 + 3])
def test_any_whole_seed(seed):
    a = traffic.make(CHAT, seed, slots=4, vocab=100, seconds=1)
    assert len(a) == 4 + CHAT["backlog"]


@pytest.mark.parametrize("path", sorted(
    (ROOT / "bench" / "traffic").glob("*.json")), ids=lambda p: p.stem)
def test_every_mix_names_a_public_source(path):
    m = json.loads(path.read_text())
    assert m["source"].startswith("Azure LLM inference trace")
    assert "arXiv" in m["source"] and "\n" not in m["source"]
    assert m["assumed"]["sigma"]
