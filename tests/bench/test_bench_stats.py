"""Exact percentiles, spreads and the end-to-end arithmetic on synthetic
timestamps."""
from __future__ import annotations

import statistics

import pytest

import bench_tiny  # noqa: F401  (puts the repository on the path)

from bench import run as R
from bench import stats


@pytest.mark.parametrize("values,q,want", [
    (list(range(1, 101)), 95, 95),
    (list(range(1, 21)), 95, 19),
    (list(range(20, 0, -1)), 50, 10),
    ([7.5], 95, 7.5),
    ([3, 1, 2], 100, 3),
    ([3, 1, 2], 1, 1),
])
def test_percentile_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


def test_percentile_needs_samples():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_spread_is_quartile_distance_over_median():
    v = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q3 - q1) / statistics.median(v))


def _cell(names):
    return R.Cell(name="x", chips=1, config={}, family=None, mix={},
                  limits={},
                  end_to_end=[{"name": n, "unit": "u"} for n in names],
                  per_layer=[])


def test_tokens_per_s_and_itl_over_the_window():
    book = R.Book()
    # two requests; the window is (10, 20]
    book.recs[0] = R.Rec([1], 9, times=[9.0, 11.0, 13.0, 15.0])
    book.recs[1] = R.Rec([1], 9, times=[12.0, 14.0, 19.0, 21.0])
    out = R.end_to_end(_cell(["output_tokens_per_s", "itl_p95_ms"]),
                       book, 10.0, 20.0)
    # tokens at 11, 13, 15, 12, 14, 19 fall inside
    assert out["output_tokens_per_s"] == pytest.approx(6 / 10.0)
    # gaps inside: 2, 2 (req 0; 9->11 straddles the start), 2, 5 (req 1;
    # 19->21 straddles the end)
    assert out["itl_p95_ms"] == pytest.approx(5000.0)


def test_ttft_counts_the_wait_of_unanswered_requests():
    book = R.Book()
    due = [R.Rec([1], 1, due=10.0 + 0.5 * i) for i in range(20)]
    for i, r in enumerate(due[:19]):
        r.times = [r.due + 0.1 * (i + 1)]
    # the last is still waiting when the window shuts; one more is due
    # after it and does not count
    book.due_recs = due + [R.Rec([1], 1, due=25.0)]
    out = R.end_to_end(_cell(["ttft_p95_ms"]), book, 10.0, 20.0)
    waits = sorted([1e3 * (min(r.times[0], 20.0) - r.due) for r in due[:19]]
                   + [1e3 * (20.0 - due[19].due)])
    assert len(waits) == 20
    assert out["ttft_p95_ms"] == pytest.approx(waits[18])


def test_book_splits_prefill_and_decode_tokens():
    book = R.Book()
    book.recs[3] = R.Rec(list(range(10)), 4)
    book.begin()
    book.on_token(3, 5)             # first token: the prefill's
    book.on_token(99, 1)            # a warm-up request: ignored
    book.end()
    book.begin()
    book.on_token(3, 6)             # decode at position 10: 11 keys
    book.end()
    assert book.steps[0].prefills == [10] and book.steps[0].contexts == []
    assert book.steps[1].contexts == [11]
