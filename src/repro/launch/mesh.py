"""Production mesh construction.

A FUNCTION (not module-level constant) so importing this module never
touches jax device state.  Single pod = 256 chips as (data=16, model=16);
multi-pod = 2 pods x 256 = 512 chips with the leading "pod" axis composing
with "data" for data parallelism (gradient reductions cross the inter-pod
links last).
"""
from __future__ import annotations

import jax


def _build_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _build_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, elastic re-scale)."""
    return _build_mesh(tuple(shape), tuple(axes))
