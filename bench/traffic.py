"""The one traffic generator: reads a mix from ``bench/traffic/<mix>.json``.

A mix is data.  Two loops are known:

``"loop": "closed"`` -- a saturated backlog.  ``slots`` requests are in
flight when the window opens and ``backlog`` more wait behind them, so the
queue always holds more requests than there are slots.  The requests in
flight start where a long-running closed loop would have them: their total
output lengths are drawn length-biased (a slot holds a request for as long
as it runs) and each is a uniform share of the way through; the part
already generated is part of its prompt and the rest is its budget.

``"loop": "open"`` -- arrivals at ``rate_per_s`` with exponential gaps,
due times measured from the window's start.

The work is the same for every ``--seed``: sizes are quantiles of the
mix's distributions (lognormal with the given median and ``sigma``,
rounded and clipped), put in order by the mix's own ``order_seed``, and
arrivals come from its ``arrival_seed``.  ``--seed`` draws the token ids.
A seed that reorders the work changes it: with the seed ordering the
backlog, which short requests reach a slot inside the window changed
``output_tokens_per_s`` by 2-3% from seed to seed on qwen3-8b, and with
the seed drawing arrivals the 95th percentile of time to first token at
0.8 of capacity swings by a quarter or more.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Request:
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int
    due: float | None = None    # open loop: seconds after the window opens


def lengths(dist: dict, n: int) -> np.ndarray:
    """The ``n`` quantiles ``(i + 0.5) / n`` of a length distribution."""
    if "fixed" in dist:
        return np.full(n, int(dist["fixed"]), np.int64)
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, n, dtype=np.int64).astype(np.int32)


def closed(mix: dict, seed: int, *, slots: int, vocab: int) -> list[Request]:
    """``slots`` requests in flight, then the backlog, in admission order."""
    rng = rng_for(seed, 1)
    order = np.random.default_rng(int(mix["order_seed"]))
    n = int(mix["backlog"])
    out_set = lengths(mix["output_tokens"], n)
    # in flight: length-biased totals, a uniform share already generated
    by_len = np.sort(out_set)
    q = (np.arange(slots) + 0.5) / slots
    total = by_len[np.searchsorted(np.cumsum(by_len) / by_len.sum(), q)]
    done = np.floor(order.permutation(q) * total).astype(np.int64)
    start_prompts = order.permutation(lengths(mix["prompt_tokens"], slots))
    reqs = [Request(_tokens(rng, int(p + a), vocab), int(t - a))
            for p, a, t in zip(start_prompts, done, total)]
    prompts = order.permutation(lengths(mix["prompt_tokens"], n))
    outs = order.permutation(out_set)
    reqs += [Request(_tokens(rng, int(p), vocab), int(o))
             for p, o in zip(prompts, outs)]
    return reqs


def open_loop(mix: dict, seed: int, *, seconds: float,
              vocab: int) -> list[Request]:
    """Requests due in ``[0, seconds)``, in due order."""
    arr = np.random.default_rng(int(mix["arrival_seed"]))
    rate = float(mix["rate_per_s"])
    n = int(math.ceil(rate * seconds * 2 + 20))
    due = np.cumsum(arr.exponential(1.0 / rate, n))
    due = due[due < seconds]
    rng = rng_for(seed, 2)
    prompts = np.random.default_rng(int(mix["order_seed"])).permutation(
        lengths(mix["prompt_tokens"], len(due)))
    outs = lengths(mix["output_tokens"], len(due))
    return [Request(_tokens(rng, int(p), vocab), int(o), float(t))
            for p, o, t in zip(prompts, outs, due)]


def make(mix: dict, seed: int, *, slots: int, vocab: int,
         seconds: float) -> list[Request]:
    if mix["loop"] == "closed":
        return closed(mix, seed, slots=slots, vocab=vocab)
    if mix["loop"] == "open":
        return open_loop(mix, seed, seconds=seconds, vocab=vocab)
    raise ValueError(f"unknown loop {mix['loop']!r}")
