"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS for 512 host
devices *before* any jax call; tests/benches see the single real device.

Topology (TPU v5e target):
  single-pod: (16, 16)    = ("data", "model") — 256 chips
  multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips; the
              "pod" axis is pure data parallelism across the DCN/ICI
              boundary (gradient all-reduce only, optionally LQ-compressed
              via core/gradcomp.py).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def dp_axes(mesh) -> tuple:
    """The data-parallel axis names of a production mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_host_mesh():
    """1x1 mesh on the real local device (CPU tests / examples)."""
    return jax.make_mesh((1, 1), ("data", "model"))
