"""Drafting: k greedy tokens per slot from the low-bit planned model.

The draft engine decodes on its own *shadow* pages (the draft half of a
:class:`~repro.spec.engine.PairedKVPool`): same page ids and page tables
as the verifier pool, its own wire format (the draft plan's ``kv_bits``).
Drafting is k calls of the draft engine's single compiled decode step —
the draft pays k sequential low-bit steps so the verifier can score all
k proposals in ONE batched forward.

The draft cache needs no rewind.  After a cycle accepts m of k proposals
the stale rows (the rejected suffix) sit strictly *ahead* of the new
position, and the next cycle overwrites each one before it first becomes
attendable (row ``pos + i`` is written at draft step i, masked until
then) — see ``tests/test_spec.py::test_draft_rows_overwritten_before_read``.
"""
from __future__ import annotations

import numpy as np


def draft_proposals(draft_engine, draft_pool, tokens, page_table, pos,
                    k: int, key, fold: int) -> np.ndarray:
    """Propose ``k`` greedy continuations per slot.

    ``tokens``/``pos`` are (max_slots,) — each slot's pending token and
    the position it will be written at; ``page_table`` is the shared
    (max_slots, pages_per_slot) table.  Returns proposals (max_slots, k)
    int32: column i holds the draft's token for position ``pos + i + 1``.
    Writes rows ``pos .. pos+k-1`` of the draft pool.
    """
    cur = np.asarray(tokens, np.int32)
    pos = np.asarray(pos, np.int32)
    out = np.zeros((cur.shape[0], k), np.int32)
    for i in range(k):
        cur = draft_engine.decode_step_batch(draft_pool, cur, page_table,
                                             pos + i, key, fold)
        out[:, i] = cur
    return out
