"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: (16, 16) = ("data", "model");
multi-pod: (2, 16, 16) = ("pod", "data", "model"). Tensor parallelism
stays inside the 16-wide "model" axis (one ICI domain); only
data-parallel gradient/batch traffic crosses the pod boundary (DCN).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax.sharding import AxisType


def auto_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: shardings come from
    ``in_shardings``/``with_sharding_constraint`` and the partitioner
    (the repo's model of sharding, DESIGN.md §4), not from the
    ``Explicit`` axis types ``jax.make_mesh`` defaults to."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def dp_axes_of(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_smoke_mesh():
    """1x1 mesh over however many local devices exist (tests)."""
    n = jax.device_count()
    return auto_mesh((n, 1), ("data", "model"))
