"""The one mesh constructor every call site uses.

``make_mesh`` builds meshes with Auto axis types (``jax.make_mesh``
defaults to Explicit), so shardings propagate through the routed steps
without per-op annotations.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)
