"""Production meshes.

Single pod: (data=16, model=16) over 256 chips; multi-pod adds a leading
pure-DP ``pod`` axis (2 x 256 = 512 chips).  Functions, not module-level
constants, so importing this module never touches jax device state (the
dry-run must set XLA_FLAGS before any device query).
"""

from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def auto_view(mesh):
    """``mesh`` with every axis ``Auto``, the axis type the model's
    sharding assumes: its hints are ``with_sharding_constraint`` calls
    that steer the partitioner, which on ``Explicit`` axes (the default
    of ``jax.make_mesh`` since JAX 0.9) turn into assertions that the
    operand is already sharded that way."""
    return mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with the model's axis types (:func:`auto_view`)."""
    return auto_view(jax.make_mesh(shape, axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def dp_axes(mesh) -> Tuple[str, ...]:
    """The data-parallel axis bundle for batch sharding on this mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh) -> int:
    out = 1
    for a in dp_axes(mesh):
        out *= mesh.shape[a]
    return out
