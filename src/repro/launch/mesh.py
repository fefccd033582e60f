"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run launcher must be able to set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before jax init.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: Optional[int] = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = jax.device_count()
    data = data if data is not None else max(1, n // model)
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    # Auto axes: the model layers steer layouts with
    # with_sharding_constraint, which refuses Explicit axes
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
