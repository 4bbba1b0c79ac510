"""Production meshes.

Single pod:  (16, 16)      axes ("data", "model")   = 256 v5e chips
Multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

``make_production_mesh`` is a FUNCTION (never module-level) so importing
this module does not touch jax device state. The ``pod`` axis is
data-parallel by default (the paper's workload is document-parallel);
``pipeline=True`` retags it for 1F1B pipelining (distributed/pipeline.py).

``make_mesh`` builds every mesh in the repo with explicit ``Auto`` axis
types (``jax.sharding.AxisType``), so sharding stays compiler-chosen.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


# v5e hardware constants (roofline)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (~per chip per dir)
HBM_BYTES = 16 * 1024 ** 3        # 16 GiB per chip
