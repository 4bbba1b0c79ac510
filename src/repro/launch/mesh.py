"""Meshes.

``make_mesh`` builds every mesh in the repo with explicit ``Auto`` axis
types (``jax.sharding.AxisType``), so sharding stays compiler-chosen.
It is a FUNCTION (never module-level) so importing this module does not
touch jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
