"""Production meshes.

``make_production_mesh`` is the pinned deliverable mesh: a 16x16 pod
(256 chips; axes data x model) or 2x16x16 (512 chips; pod x data x model).
Defined as a function so importing this module never touches jax device
state.

In ARCAS terms the production mesh is the ``spread_rate = 1`` layout: each
model line of 16 chips is one contiguous chiplet group (ICI neighborhood).
The layout *family* around it — (256/m, m) factorizations with
locality-aware device order — comes from ``repro.core.layout.Layout``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def batch_axes(mesh) -> tuple:
    """Mesh axes that carry the batch dimension."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_size(mesh) -> int:
    return mesh.shape["model"]


def data_axis_size(mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n
