"""Seeded weights for a benchmark cell, in the layout they are served in.

A configuration's family (``bench/families/<family>/plain.py``) draws
every leaf on the device from ``--seed`` in one jitted call, with these
leaves:

  * packed projections (``packed``): uint8 codes, two 4-bit codes per
    byte along the contraction axis K (byte ``j`` of column ``n`` holds
    row ``2j`` in its low nibble and row ``2j+1`` in its high nibble),
    with one f32 ``scale`` and ``zmin`` per 128-row local region and
    column, so that
    ``w[k, n] = code[k, n] * scale[k // 128, n] + zmin[k // 128, n]``;
  * norm scales (``norm``) and the token embedding in f32.

Uniform random codes with ``scale = sigma / 4.61`` and ``zmin = -7.5 scale``
give weights of standard deviation ``sigma = K ** -0.5`` (a uniform code on
0..15 has standard deviation 4.61), as a ``K ** -0.5`` normal init does.

This module imports nothing of the program: the harness hands these arrays
to the program's public parameter types, and the plain reference
(``bench/reference.py``) reads the same arrays after the program has gone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

GROUP = 128      # local quantization region along K (the lq4w scheme)


def packed(key, lead: tuple, k: int, n: int) -> dict:
    """A packed (K, N) projection, with leading axes ``lead``."""
    kc, ks, kz = jax.random.split(key, 3)
    sigma = k ** -0.5
    g = (*lead, k // GROUP, n)
    scale = (sigma / 4.61) * jnp.exp(0.1 * jax.random.normal(ks, g))
    zmin = -7.5 * scale + 0.05 * sigma * jax.random.normal(kz, g)
    codes = jax.random.bits(kc, (*lead, k // 2, n), jnp.uint8)
    return {"packed": codes, "scale": scale.astype(jnp.float32),
            "zmin": zmin.astype(jnp.float32)}


def norm(key, shape) -> jnp.ndarray:
    """A norm's learned scale, near 1."""
    return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)


def seed_key(seed: int):
    """A key for any whole-number seed, 32 bits or more."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)
