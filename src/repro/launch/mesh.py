"""Production mesh factory.

Defined as a function (never a module-level constant) so importing this
module does not touch JAX device state — the dry-run sets
``xla_force_host_platform_device_count`` *before* first JAX init.

Topology (TPU v5e posture):
* single pod:  (16, 16)        axes ("data", "model") — 256 chips
* multi-pod:   (2, 16, 16)     axes ("pod", "data", "model") — 512 chips

The factory generalizes to (n_pods, d, m) for elastic scaling: the
checkpoint manifest is mesh-agnostic, so restarts may change n_pods.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_mesh", "mesh_info"]


def _make(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Elastic variant: any (n_pods, data, model) factorization."""
    return _make(tuple(shape), tuple(axes))


def mesh_info(mesh: Mesh) -> dict:
    return {"axis_names": list(mesh.axis_names),
            "shape": [int(s) for s in mesh.devices.shape],
            "n_devices": int(mesh.devices.size)}
