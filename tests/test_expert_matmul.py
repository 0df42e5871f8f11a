"""The grouped expert kernel (``kernels/expert_matmul.py``) in interpret
mode against a per-expert f32 einsum, and the drop-free MoE layer built on
it: a token's output depends on that token alone."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as kref
from repro.models import layers, moe

em = importlib.import_module("repro.kernels.expert_matmul")

E, GS = 4, 128


def _experts(key, k, n, e=E):
    w = jax.random.normal(key, (e, k, n), jnp.float32) * k ** -0.5
    p, s, z = jax.vmap(lambda ww: kref.quantize_weight(ww, 4, GS))(w)
    return ops.QWeight(packed=p, scale=s, zmin=z, bits=4, group_size=GS,
                       k=k, n=n)


def _dense(qw):
    return jax.vmap(lambda p, s, z: kref.dequantize_weight(
        p, s, z, 4, GS))(qw.packed, qw.scale, qw.zmin)


def _grouped(counts, tm):
    """Tile -> expert map and live tiles of groups of ``counts`` rows,
    each padded to ``tm``, in a buffer of the static bound's size."""
    tiles = [e for e, c in enumerate(counts) for _ in range(-(-c // tm))]
    rows = sum(counts) + len(counts) * (tm - 1)
    n_tiles = -(-rows // tm)
    te = np.zeros((n_tiles,), np.int32)
    te[:len(tiles)] = tiles
    return te, len(tiles), n_tiles * tm


@pytest.mark.parametrize("counts", [
    [5, 0, 17, 3],          # an empty expert; rows not a multiple of tm
    [0, 0, 40, 0],          # every row on one expert
    [16, 16, 16, 16],       # whole tiles
], ids=["ragged", "one_expert", "whole_tiles"])
@pytest.mark.parametrize("k,n", [(256, 128), (128, 256)])
def test_expert_matmul_matches_a_per_expert_einsum(counts, k, n):
    tm = 16
    te, n_live, rows = _grouped(counts, tm)
    qw = _experts(jax.random.key(sum(counts) + k), k, n)
    x = jax.random.normal(jax.random.key(1), (rows, k), jnp.float32)
    got = np.asarray(ops.expert_matmul(x, qw, jnp.asarray(te), n_live, tm=tm,
                                       backend="interpret"))
    ref = np.asarray(ops.expert_matmul(x, qw, jnp.asarray(te), n_live, tm=tm,
                                       backend="ref"))
    w = np.asarray(_dense(qw))
    xs = np.asarray(x)
    for i in range(n_live):
        rs = slice(i * tm, (i + 1) * tm)
        want = np.einsum("mk,kn->mn", xs[rs], w[te[i]])
        np.testing.assert_allclose(got[rs], want, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(ref[rs], want, rtol=1e-4, atol=1e-4)
    # the oracle leaves the tiles past the live ones at zero
    assert not ref[n_live * tm:].any()


def test_row_tile_by_routed_rows():
    assert em.row_tile(32 * 8, 64) == 16          # a 32-slot decode
    assert em.row_tile(2048 * 8, 64) == 128       # a 2048-token prefill
    assert em.row_tile(1, 64) == 16


# ---------------------------------------------------------------------------
# drop-free routing
# ---------------------------------------------------------------------------

D, F, NE, TOP = 128, 128, 8, 2


def _moe_params(seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return {"router": {"w": jax.random.normal(ks[0], (D, NE)) * D ** -0.5},
            "wi_gate": _experts(ks[1], D, F, NE),
            "wi_up": _experts(ks[2], D, F, NE),
            "wo": _experts(ks[3], F, D, NE)}


def _apply(p, x, backend="ref"):
    out, aux = moe.moe_apply(p, x, n_experts=NE, top_k=TOP,
                             policy=layers.QuantPolicy.serve(
                                 "lq4w", backend=backend))
    return np.asarray(out), aux


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_a_tokens_output_depends_on_that_token_alone(backend):
    """One token alone, among 31 others, and ahead of 2047 padding rows
    (the prefill bucket's): the same experts and the same output.  The
    tolerance is float32 rounding: each row's products are its own, and
    the combine sums a token's k rows in top-k order whatever the batch,
    so only the compiler's association of a dot's K blocks can differ
    (measured at most 1.2e-7 on the CPU, an ulp of outputs near 1)."""
    p = _moe_params()
    rng = np.random.default_rng(0)
    tok = rng.standard_normal((1, 1, D)).astype(np.float32)
    batch = rng.standard_normal((1, 32, D)).astype(np.float32)
    batch[0, 17] = tok[0, 0]
    padded = np.zeros((1, 2048, D), np.float32)
    padded[0, 0] = tok[0, 0]
    padded[0, 1:] = rng.standard_normal(D).astype(np.float32)   # pad rows
    alone, _ = _apply(p, jnp.asarray(tok), backend)
    among, _ = _apply(p, jnp.asarray(batch), backend)
    behind, aux = _apply(p, jnp.asarray(padded), "ref")
    np.testing.assert_allclose(among[0, 17], alone[0, 0], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(behind[0, 0], alone[0, 0], rtol=1e-6,
                               atol=1e-6)
    ids = [np.asarray(moe._route(p["router"]["w"],
                                 jnp.asarray(x.reshape(-1, D)), TOP)[2])
           for x in (tok, batch, padded)]
    assert (ids[0][0] == ids[1][17]).all() and (ids[0][0] == ids[2][0]).all()
    # 2047 copies of one padding row all pick its TOP experts: one group
    # takes every one of them, and nothing is dropped
    assert int(aux["expert_rows_max"]) >= 2047
    assert int(aux["experts_hit"]) >= TOP


def test_drop_free_matches_the_capacity_path_without_drops():
    """Packed experts (drop-free, grouped kernel) against the float
    experts' capacity path with room for every assignment: the same
    routing and combine, so the same output up to f32 rounding."""
    p = _moe_params(3)
    x = jax.random.normal(jax.random.key(4), (2, 9, D), jnp.float32)
    got, aux = _apply(p, x)
    dense = dict(p, wi_gate=_dense(p["wi_gate"]), wi_up=_dense(p["wi_up"]),
                 wo=_dense(p["wo"]))
    want, aux2 = moe.moe_apply(dense, x, n_experts=NE, top_k=TOP,
                               capacity_factor=float(NE))
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-5)
    for k in ("loss", "experts_hit", "expert_rows_max"):
        np.testing.assert_allclose(float(aux[k]), float(aux2[k]), rtol=1e-6)
