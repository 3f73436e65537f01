"""Production mesh builders. Functions (not module constants) so importing
never touches jax device state (a caller may set XLA_FLAGS first).

Every mesh is built with ``AxisType.Auto`` axes: the models and the train
step shard through GSPMD (``device_put`` + ``with_sharding_constraint``),
and ``jax.make_mesh`` would otherwise make the axes ``Explicit``."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with GSPMD (``Auto``) axes, over ``devices`` when
    given (default: all of this process's devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per v5e pod; the multi-pod mesh adds a leading
    2-pod data-parallel axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — used by tests."""
    return auto_mesh((data, model), ("data", "model"))
