"""Production meshes. Defined as functions so importing never touches jax
device state (the dry-run must set XLA_FLAGS before any initialisation).

Every ``("data", "model")`` mesh in the repo is built by :func:`make_mesh`,
which pins ``Auto`` axis types: the sharded solve constrains with
``with_sharding_constraint`` and the shard_map trainer gathers with plain
indexing, both of which need GSPMD-style (``Auto``) axes. ``jax.make_mesh``
alone defaults to ``Explicit`` axes, under which both fail.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int],
              axes: Sequence[str] = ("data", "model")):
    """``jax.make_mesh`` with ``Auto`` axis types (see module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (256 chips, one v5e pod) or 2x16x16 (512 chips, two pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 4, n_model: int = 2):
    """Small host-device mesh for tests (requires matching device count)."""
    return make_mesh((n_data, n_model))


def auto_forest_mesh(model_axis_max: int = 8):
    """(data, model) mesh over every visible device for forest training.

    The model axis (ensemble-grid parallelism) gets the largest power of two
    that divides the device count, is at most ``model_axis_max``, and stays
    ≤ the data-axis size — rows usually outnumber ensembles per batch, so
    the data axes keep the majority of the devices. Returns ``None`` on a
    single device (callers fall back to the single-device trainer).
    """
    n = len(jax.devices())
    if n == 1:
        return None
    model = 1
    while (model * 2 <= model_axis_max and (model * 2) ** 2 <= n
           and n % (model * 2) == 0):
        model *= 2
    return make_mesh((n // model, model))
