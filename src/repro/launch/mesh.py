"""Production mesh construction.

Target hardware: TPU v5e, 256 chips per pod (16x16), optionally 2 pods.
``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run sets
``--xla_force_host_platform_device_count=512`` before any jax import.

Every axis is ``AxisType.Auto``, so GSPMD owns the "model" axis outside the
exchange's manual ``shard_map`` over the data axes.
"""
from __future__ import annotations

import jax


def _auto(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small ones, e.g. (2, 2))."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=_auto(len(axes)))


def data_axis_names(mesh) -> tuple[str, ...]:
    return tuple(n for n in mesh.axis_names if n in ("pod", "data"))


def n_data_workers(mesh) -> int:
    return int(
        __import__("math").prod(
            mesh.shape[n] for n in data_axis_names(mesh)))


def model_axis_size(mesh) -> int:
    return int(mesh.shape.get("model", 1))
