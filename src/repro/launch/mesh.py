"""Production meshes (single-pod 16x16, multi-pod 2x16x16) + the W-HFL
refinement of the data axis into (cluster, user) sub-axes.

Functions, not module-level constants: importing this module never
touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import; smoke tests must keep seeing 1 device).
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def refine_mesh(mesh, *, users_per_cluster: int = 4):
    """Refine `data` -> (cluster, user) over the identical device order.

    Returns a Mesh with axes ('pod','cluster','user','model'); a
    single-pod input gets a size-1 'pod' axis.  Device placement equals
    the production mesh's, so shardings over ('cluster','user') are
    byte-identical to shardings over 'data'.
    """
    names = mesh.axis_names
    devs = mesh.devices
    if "pod" not in names:
        devs = devs[None]  # [1, data, model]
    n_pod, n_data, n_model = devs.shape
    M = users_per_cluster
    if n_data % M:
        raise ValueError(f"data axis {n_data} not divisible by M={M}")
    devs = devs.reshape(n_pod, n_data // M, M, n_model)
    names = ("pod", "cluster", "user", "model")
    return Mesh(devs, names, axis_types=(AxisType.Auto,) * 4)


def mesh_counts(mesh, users_per_cluster: int = 4) -> Tuple[int, int, int]:
    """(n_pods, n_clusters_total, users_per_cluster) for a production or
    refined mesh."""
    sh = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_pod = sh.get("pod", 1)
    if "cluster" in sh:
        return n_pod, n_pod * sh["cluster"], sh["user"]
    return n_pod, n_pod * (sh["data"] // users_per_cluster), users_per_cluster
