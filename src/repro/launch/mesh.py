"""Mesh builders: the one place this repo makes a ``jax.sharding.Mesh``.

Functions, not module-level constants, so importing this module never touches
jax device state.  Every axis is ``AxisType.Auto``: the model code places
arrays with ``with_sharding_constraint`` and leaves propagation to GSPMD,
which the installed JAX's default of explicit axes would refuse (an
embedding gather then raises ``ShardingTypeError``).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """A mesh of ``shape`` over ``axes`` (``devices`` defaults to JAX's)."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def describe(mesh) -> dict:
    return {name: int(size) for name, size in
            zip(mesh.axis_names, mesh.devices.shape)}
