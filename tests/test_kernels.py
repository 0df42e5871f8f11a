"""Pallas kernels vs ref.py oracles: shape/dtype sweeps in interpret mode.

Every kernel runs its exact TPU body in Python (interpret=True) and must
match the pure-jnp oracle to float tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.key(0)


def _w(k, n, seed=0):
    return 2.0 * jax.random.normal(jax.random.fold_in(KEY, seed), (k, n))


def _x(m, k, dtype=jnp.float32, seed=1):
    return jax.random.normal(jax.random.fold_in(KEY, seed), (m, k)
                             ).astype(dtype)


# ---------------------------------------------------------------------------
# quant_matmul: fused dequant-matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4, 2, 1])
@pytest.mark.parametrize("m,k,n,gs", [
    (8, 256, 128, 64),
    (16, 512, 256, 128),
    (4, 128, 384, 32),
])
def test_quant_matmul_interpret_vs_ref(bits, m, k, n, gs):
    w = _w(k, n, seed=bits)
    qw = ops.quantize_weight(w, bits, gs)
    x = _x(m, k)
    got = ops.quant_matmul(x, qw, backend="interpret")
    want = ops.quant_matmul(x, qw, backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_dtypes(dtype):
    w = _w(256, 128, seed=3)
    qw = ops.quantize_weight(w, 4, 64)
    x = _x(8, 256, dtype)
    got = ops.quant_matmul(x, qw, backend="interpret")
    want = ops.quant_matmul(x, qw, backend="ref")
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-1)


def test_quant_matmul_unaligned_mn():
    """M, N not multiples of the tile: the kernel pads internally."""
    w = _w(256, 100, seed=4)
    qw = ops.quantize_weight(w, 8, 64)
    x = _x(5, 256)
    got = ops.quant_matmul(x, qw, backend="interpret")
    want = ops.quant_matmul(x, qw, backend="ref")
    assert got.shape == (5, 100)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_quant_matmul_vs_float():
    """8-bit quantized matmul approximates the float matmul closely."""
    w = _w(512, 256, seed=5)
    x = _x(16, 512)
    qw = ops.quantize_weight(w, 8, 64)
    got = ops.quant_matmul(x, qw, backend="ref")
    exact = x @ w
    rel = float(jnp.abs(got - exact).max() / jnp.abs(exact).max())
    assert rel < 2e-2


# ---------------------------------------------------------------------------
# act_quant: runtime activation quantization
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4, 2])
@pytest.mark.parametrize("m,k,gs", [(8, 256, 64), (16, 128, 32),
                                    (3, 512, 128),
                                    (4, 896, 128),      # internvl2-1b width
                                    (2, 1280, 128)])    # whisper-large-v3
def test_act_quant_interpret_vs_ref(bits, m, k, gs):
    x = _x(m, k, seed=bits + 20)
    gp, gs_, gz = ops.act_quant(x, bits=bits, group_size=gs,
                                backend="interpret")
    rp, rs, rz = ops.act_quant(x, bits=bits, group_size=gs, backend="ref")
    np.testing.assert_array_equal(np.asarray(gp), np.asarray(rp))
    np.testing.assert_allclose(np.asarray(gs_), np.asarray(rs), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gz), np.asarray(rz), rtol=1e-6)


@pytest.mark.parametrize("bits", [8, 4, 2])
def test_act_quant_reconstruction(bits):
    x = _x(8, 256, seed=bits + 30)
    p, s, z = ops.act_quant(x, bits=bits, group_size=64, backend="ref")
    xr = ref.act_dequant(p, s, z, bits=bits, group_size=64)
    step = np.asarray(s).max()
    assert np.abs(np.asarray(x) - np.asarray(xr)).max() <= step * 0.5 + 1e-6


# ---------------------------------------------------------------------------
# lut_matmul: paper section V
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [4, 2, 1])
@pytest.mark.parametrize("m,k,n,gs", [(8, 256, 128, 64), (4, 128, 96, 32)])
def test_lut_matmul_interpret_vs_ref(bits, m, k, n, gs):
    x = _x(m, k, seed=bits + 40)
    w = _w(k, n, seed=bits + 41)
    ap, asc, azm = ops.act_quant(x, bits=bits, group_size=gs, backend="ref")
    got = ops.lut_matmul(ap, asc, azm, w, bits=bits, group_size=gs,
                         backend="interpret")
    want = ops.lut_matmul(ap, asc, azm, w, bits=bits, group_size=gs,
                          backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("k,gs", [(96, 64), (130, 32), (100, 128)])
def test_lut_matmul_rejects_ragged_tail_region(k, gs):
    """Regression: a K not divisible by group_size used to silently drop
    the trailing partial local region from the product (the K grid walks
    whole regions only).  It must be a loud ValueError instead."""
    from repro.kernels.lut_matmul import lut_matmul as raw_lut
    bits, m, n = 2, 4, 8
    cpb = 8 // bits
    a_packed = jnp.zeros((m, -(-k // cpb)), jnp.uint8)
    a_scale = jnp.ones((m, -(-k // gs)), jnp.float32)
    w = jnp.ones((k, n), jnp.float32)
    with pytest.raises(ValueError, match="dropped"):
        raw_lut(a_packed, a_scale, a_scale, w, bits=bits, group_size=gs)


@pytest.mark.parametrize("k,gs", [(96, 64), (130, 32)])
def test_quant_matmul_rejects_ragged_tail_region(k, gs):
    """Same hazard audit on the dequant-matmul kernel: the ragged tail
    must fail loudly before any grid arithmetic."""
    from repro.kernels.quant_matmul import quant_matmul as raw_qm
    bits, m, n = 8, 4, 8
    x = jnp.ones((m, k), jnp.float32)
    packed = jnp.zeros((k, n), jnp.uint8)
    g = -(-k // gs)
    with pytest.raises(ValueError, match="dropped"):
        raw_qm(x, packed, jnp.ones((g, n)), jnp.zeros((g, n)),
               bits=bits, group_size=gs)


def test_lut_equals_dequant_matmul():
    """LUT forward == dequantized-activation matmul (paper eq. 8)."""
    x = _x(8, 256, seed=50)
    w = _w(256, 64, seed=51)
    ap, asc, azm = ops.act_quant(x, bits=2, group_size=64, backend="ref")
    lut_out = ops.lut_matmul(ap, asc, azm, w, bits=2, group_size=64,
                             backend="ref")
    xq = ref.act_dequant(ap, asc, azm, bits=2, group_size=64)
    np.testing.assert_allclose(np.asarray(lut_out), np.asarray(xq @ w),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# quant_dense: the full paper forward (weights + activations + LUT)
# ---------------------------------------------------------------------------

def test_quant_dense_paths_agree():
    x = _x(8, 256, seed=60)
    w = _w(256, 128, seed=61)
    qw = ops.quantize_weight(w, 8, 64)
    base = ops.quant_dense(x, qw, backend="ref")
    act = ops.quant_dense(x, qw, a_bits=8, backend="ref")
    lut = ops.quant_dense(x, qw, a_bits=2, lut=True, backend="ref")
    exact = x @ w
    for out, tol in [(base, 0.05), (act, 0.05), (lut, 0.6)]:
        rel = float(jnp.abs(out - exact).max() / jnp.abs(exact).max())
        assert rel < tol, rel


def test_qweight_bytes():
    k = n = 1024
    gs = 128
    w = _w(k, n)
    for bits in (8, 4, 2, 1):
        qw = ops.quantize_weight(w, bits, gs)
        expected = k * n * bits // 8 + 2 * (k // gs) * n * 4
        assert qw.nbytes() == expected
        # >= 3.2x smaller than fp32 even at 8-bit (incl. region metadata)
        assert qw.nbytes() <= k * n * 4 * bits / 8 / 0.9
