"""The fused paged-attention kernel under a sliding window: interpret mode
against the XLA gather + dequant + ``decode_attention(window=)`` path at
the positions where the window's first live page and in-page mask turn
over, with one-row and multi-row (speculative) queries."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kvwire
from repro.kernels import paged_attention as paged_attn
from repro.models import attention

pytestmark = pytest.mark.skipif(
    not paged_attn.available(),
    reason="Pallas unavailable: fused kernel gated off on this host")

KEY = jax.random.key(7)
PAGE, PPS, WINDOW = 4, 8, 10            # max_context 32
MAX_CTX = PAGE * PPS


def _pool(bits, b, kvh=2, d=32, gs=16):
    n_pages = b * PPS + 1
    kf = jax.random.normal(KEY, (n_pages, PAGE, kvh, d), jnp.float32)
    vf = jax.random.normal(jax.random.fold_in(KEY, 1), kf.shape)
    kf = kf.at[0].set(1e4)                     # scratch garbage
    vf = vf.at[0].set(-1e4)
    table = (1 + jnp.arange(b * PPS, dtype=jnp.int32)).reshape(b, PPS)
    if bits is None:
        return kf, vf, table
    return kvwire.quantize_kv(kf, bits, gs), kvwire.quantize_kv(vf, bits,
                                                                gs), table


def _xla(q, k_pg, v_pg, table, pos):
    kk = kvwire.gather_pages(k_pg, table)
    vv = kvwire.gather_pages(v_pg, table)
    if isinstance(kk, dict):
        kk = kvwire.dequantize_kv(kk, q.shape[-1])
        vv = kvwire.dequantize_kv(vv, q.shape[-1])
    return attention.decode_attention(q, kk, vv, pos, window=WINDOW)


@pytest.mark.parametrize("lq", [1, 3])
@pytest.mark.parametrize("bits", [None, 4])
def test_windowed_kernel_matches_the_xla_path(bits, lq):
    # 0, window-1, window, window+page_size-1 and max_context-1 (a run of
    # lq rows ends there), one slot each
    pos = jnp.asarray([0, WINDOW - 1, WINDOW, WINDOW + PAGE - 1,
                       MAX_CTX - lq], jnp.int32)
    b = pos.shape[0]
    k_pg, v_pg, table = _pool(bits, b)
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (b, lq, 2, 2, 32))
    want = _xla(q, k_pg, v_pg, table, pos)
    got = paged_attn.paged_attention(q, k_pg, v_pg, table, pos,
                                     window=WINDOW, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the window matters at these positions: unwindowed differs
    full = paged_attn.paged_attention(q, k_pg, v_pg, table, pos,
                                      interpret=True)
    assert np.abs(np.asarray(full) - np.asarray(want))[2:].max() > 1e-3


def test_entries_before_the_first_live_page_are_never_read():
    """Whatever the table names before a slot's first live page (here:
    the scratch page of garbage) never reaches the output."""
    pos = jnp.asarray([MAX_CTX - 1, WINDOW + PAGE], jnp.int32)
    k_pg, v_pg, table = _pool(4, 2)
    q = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 1, 2, 2, 32))
    want = paged_attn.paged_attention(q, k_pg, v_pg, table, pos,
                                      window=WINDOW, interpret=True)
    first = (np.maximum(np.asarray(pos) - WINDOW + 1, 0) // PAGE)
    poked = np.asarray(table).copy()
    for b, f in enumerate(first):
        poked[b, :f] = 0
    got = paged_attn.paged_attention(q, k_pg, v_pg, jnp.asarray(poked), pos,
                                     window=WINDOW, interpret=True)
    assert first.min() > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
