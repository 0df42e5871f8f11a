"""YaRN rotary frequencies and scale against Hugging Face's closed form
(``_compute_yarn_parameters``), written out here in float64."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers

# Mellum2's full-attention rope_parameters
YARN = (16.0, 8192, 32.0, 1.0, 1.2772588722239782)
THETA = 500_000.0


def closed_form(dim, theta, factor, orig, beta_fast, beta_slow):
    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(corr(beta_fast)), 0), \
        min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extra = 1 - ramp
    return (1 / (factor * pos)) * (1 - extra) + (1 / pos) * extra, low, high


@pytest.mark.parametrize("dim", [16, 64, 128])
def test_yarn_frequencies_match_the_closed_form(dim):
    want, low, high = closed_form(dim, THETA, *YARN[:4])
    got = np.asarray(layers.rope_freqs(dim, THETA, YARN), np.float64)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    plain = np.asarray(layers.rope_freqs(dim, THETA), np.float64)
    # below the ramp the frequencies extrapolate (unchanged), past it
    # they interpolate (divided by the factor)
    np.testing.assert_allclose(got[:low + 1], plain[:low + 1], rtol=1e-6)
    top = int(math.ceil(high))
    np.testing.assert_allclose(got[top:], plain[top:] / YARN[0], rtol=1e-6)


def test_yarn_ramp_ends_at_mellum2_widths():
    _, low, high = closed_form(128, THETA, *YARN[:4])
    assert (low, high) == (18, 35)


@pytest.mark.parametrize("dim", [16, 64, 128])
def test_yarn_scales_cos_and_sin_by_the_attention_factor(dim):
    rng = np.random.default_rng(dim)
    x = jnp.asarray(rng.standard_normal((1, 5, 2, dim)), jnp.float32)
    pos = jnp.arange(5)[None] * 700
    got = np.asarray(layers.apply_rope(x, pos, THETA, YARN))
    freqs, _, _ = closed_form(dim, THETA, *YARN[:4])
    ang = np.asarray(pos)[..., None] * freqs                   # (1, 5, D/2)
    cos = np.cos(ang)[..., None, :] * YARN[4]
    sin = np.sin(ang)[..., None, :] * YARN[4]
    x1, x2 = np.split(np.asarray(x, np.float64), 2, axis=-1)
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    # angles up to 2,800 rad in float32: ~2e-4 rad of rounding
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    # without YaRN: the plain rotation, no scale
    same = np.asarray(layers.apply_rope(x, pos, THETA))
    np.testing.assert_allclose(np.linalg.norm(same, axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
