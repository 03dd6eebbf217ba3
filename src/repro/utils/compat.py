"""Mesh construction for the pinned JAX release.

Everything in the repo that builds a mesh goes through :func:`make_mesh`,
so the axis-type policy lives in exactly one place.
"""
from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with explicitly-Auto axes (sharding-in-types off)."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(
        tuple(shape), axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )
