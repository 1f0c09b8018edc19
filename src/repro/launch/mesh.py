"""Production mesh definitions (TPU v5e pods).

Defined as FUNCTIONS so importing this module never touches jax device
state; only the dry-run entrypoint forces the 512-device host platform.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mk(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_host_mesh(n_data: int = 1) -> Mesh:
    """``(n_data, 1)`` mesh over the first ``n_data`` of ``jax.devices()``
    with the production axis names: 1 is the single-device CPU-test mesh,
    4 shards the FL client axis over the four chips of one v5e host."""
    return _mk((n_data, 1), ("data", "model"))


def mesh_axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
