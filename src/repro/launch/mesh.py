"""Production mesh builders (functions, never module-level constants).

Target: TPU v5e. Single pod = 16×16 = 256 chips, mesh ("data", "model").
Multi-pod = 2 pods = 512 chips, mesh ("pod", "data", "model") — the "pod"
axis carries pure data parallelism across the inter-pod links.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

# v5e hardware constants (per chip) — used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s per link


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules place arrays
    with ``with_sharding_constraint``, which refuses Explicit axes (the
    default of ``jax.make_mesh``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with production axis names (CPU tests)."""
    return make_mesh((1, 1), ("data", "model"))
