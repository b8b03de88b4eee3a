"""Mesh construction for the production topology.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — smoke tests must keep seeing one
CPU device, while the dry-run process boots with 512 forced host devices.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (tests use small ones, e.g. (2, 4)).  Axes are
    ``Auto``: the model code places values with
    ``with_sharding_constraint``, which ``Explicit`` axes reject."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


def data_axes(mesh) -> Tuple[str, ...]:
    """Axes that carry data parallelism (pod folds into DP when present)."""
    return ("pod", "data") if "pod" in mesh.shape else ("data",)
