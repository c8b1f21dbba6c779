"""Production mesh definitions (TPU v5e pods).

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first init).
"""
from __future__ import annotations


from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh", "HW"]


class HW:
    """TPU v5e hardware constants used by the roofline analysis."""

    PEAK_FLOPS_BF16 = 197e12      # per chip, FLOP/s
    HBM_BW = 819e9                # per chip, B/s
    ICI_BW = 50e9                 # per link, B/s (~50 GB/s/link)
    HBM_BYTES = 16 * 2 ** 30      # 16 GiB per chip
    CHIPS_PER_POD = 256


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes on every dimension.

    ``jax.make_mesh`` defaults to Explicit axes, which
    ``with_sharding_constraint`` (the activation sharder of
    :class:`repro.sharding.specs.ShardingRules`) refuses.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Degenerate mesh over the local device set (CPU tests / examples)."""
    n = len(jax.devices())
    data = max(1, n // model)
    return make_mesh((data, model), ("data", "model"))
