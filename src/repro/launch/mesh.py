"""Production mesh construction (deliverable e).

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state. Single pod: (16, 16) = 256 chips as (data, model);
multi-pod: (2, 16, 16) = 512 chips as (pod, data, model). The dry-run builds
these over 512 forced host devices; on real hardware the same call maps onto
the TPU slice topology.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI-scale dry-run tests (8 host devices)."""
    return _make_mesh(shape, axes)


def dp_axes(mesh) -> tuple[str, ...]:
    """Axes used for data parallelism / FSDP."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"
