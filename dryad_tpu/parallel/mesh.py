"""Device mesh management — the cluster abstraction.

The reference's ``ICluster``/``IScheduler`` (``ClusterInterface/
Interfaces.cs:324,491``) abstracts a set of computers; the TPU-native
analog is a ``jax.sharding.Mesh`` over TPU chips with one named axis
``"p"`` (partitions).  The reference's LocalJobSubmission N-process mode
(``LinqToDryad/LocalJobSubmission.cs``) maps to a host-local CPU-device
mesh (``--xla_force_host_platform_device_count``) used by the tests.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Callable, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = "p"
# Cross-slice axis of a hybrid mesh: partitions within a slice talk over
# ICI ("p"), slices talk over DCN ("d") — the reference's machine→pod
# hierarchy (DrDynamicAggregateManager.h:35-168) as mesh structure.
DCN_AXIS = "d"


def force_cpu_backend(n_devices: int) -> None:
    """Pin this process to the host-CPU backend with ``n_devices`` virtual
    devices — the tests' and CPU-mesh drives' one way onto the virtual
    mesh.  Must run before the first backend query: the env vars cover a
    fresh interpreter, the config updates cover jax already imported, and
    a backend that is already initialized with a different device count
    raises (jax's own RuntimeError) instead of leaving the caller on
    whatever mesh it found.
    """
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)


def make_mesh(num_partitions: Optional[int] = None) -> Mesh:
    """1-D partition mesh over available devices.

    ``num_partitions`` defaults to the device count; it must evenly use
    the devices (one partition per device — gang-by-construction, the
    SPMD analog of Dryad cohorts ``DrCohort.h:23``).
    """
    devices = jax.devices()
    n = num_partitions if num_partitions is not None else len(devices)
    if n > len(devices):
        raise ValueError(
            f"num_partitions {n} exceeds available devices {len(devices)}"
        )
    return Mesh(np.array(devices[:n]), (AXIS,))


def make_hybrid_mesh(
    dcn_slices: int, ici_partitions: Optional[int] = None
) -> Mesh:
    """2-D (DCN_AXIS, AXIS) mesh: ``dcn_slices`` TPU slices (or host
    groups) by ``ici_partitions`` devices each.

    On real multi-slice TPU topologies the device grid comes from
    ``mesh_utils.create_hybrid_device_mesh`` so the inner axis rides ICI
    and the outer axis DCN; elsewhere (CPU meshes, single slice) devices
    are reshaped in order.  The engine's global partition id is the
    flattened (d, p) index, d-major.
    """
    devices = jax.devices()
    if dcn_slices < 1:
        raise ValueError("dcn_slices must be >= 1")
    n_ici = (
        ici_partitions
        if ici_partitions is not None
        else len(devices) // dcn_slices
    )
    if n_ici < 1 or dcn_slices * n_ici > len(devices):
        raise ValueError(
            f"hybrid mesh {dcn_slices}x{n_ici} exceeds "
            f"available devices {len(devices)}"
        )
    used = devices[: dcn_slices * n_ici]
    # Only a genuinely multi-slice topology gets the topology-aware
    # layout; everything else (CPU meshes, single slice) is an in-order
    # reshape.  A failure on real multi-slice hardware must NOT silently
    # degrade: the inner axis would span DCN and every exchange would
    # ride the slow network while claiming ICI.
    slice_ids = {getattr(d, "slice_index", None) for d in used}
    if len(slice_ids - {None}) > 1:
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_hybrid_device_mesh(
            (1, n_ici), (dcn_slices, 1), devices=used
        )
    else:
        arr = np.array(used).reshape(dcn_slices, n_ici)
    return Mesh(arr, (DCN_AXIS, AXIS))


def exclude_devices(mesh: Mesh, bad_ids) -> Mesh:
    """Rebuild the mesh without the excluded device ids — the elastic
    recovery step (reference: the computer set "may change as failures
    occur", ``Interfaces.cs:336-343``; failed-process requeue with
    exclusion).  The caller re-runs affected stages from checkpoints on
    the smaller mesh.

    A hybrid (DCN x ICI) mesh keeps its 2-D structure: each slice row
    sheds its bad devices, the ICI axis shrinks to the smallest surviving
    slice (rows must stay rectangular), and slices that lost every device
    are dropped — so cross-slice exchanges still ride the tree/DCN path
    instead of silently treating DCN links as ICI."""
    bad = set(bad_ids)
    if mesh.devices.ndim == 2:
        rows = [
            [d for d in row if d.id not in bad] for row in mesh.devices
        ]
        rows = [r for r in rows if r]
        if not rows:
            raise ValueError("excluding all devices leaves an empty mesh")
        k = min(len(r) for r in rows)
        arr = np.array([r[:k] for r in rows])
        return Mesh(arr, mesh.axis_names)
    keep = [d for d in mesh.devices.flat if d.id not in bad]
    if not keep:
        raise ValueError("excluding all devices leaves an empty mesh")
    return Mesh(np.array(keep), (AXIS,))


def mesh_axes(mesh: Mesh) -> tuple:
    """The mesh's partition axes, outermost first — ("p",) for a flat
    mesh, (DCN_AXIS, AXIS) for a hybrid one.  Collectives over this
    tuple address the flattened global partition id."""
    return tuple(mesh.axis_names)


def dcn_slice_count(mesh: Optional[Mesh]) -> int:
    """Number of DCN-connected slice groups — the outer extent of a
    hybrid mesh, 1 for a flat (single-slice) mesh or no mesh at all.
    The combine-tree planner sizes its level-0 groups from this: one
    accumulator per slice keeps every pre-fold merge off the DCN."""
    if mesh is None or DCN_AXIS not in mesh.axis_names:
        return 1
    return int(mesh.shape[DCN_AXIS])


def ici_partitions_per_slice(mesh: Optional[Mesh]) -> int:
    """Partitions reachable over ICI from any one device — the inner
    extent of a hybrid mesh, or the whole mesh when flat."""
    if mesh is None:
        return 1
    if DCN_AXIS in mesh.axis_names:
        return int(mesh.shape[AXIS])
    return num_partitions(mesh)


def partition_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(mesh_axes(mesh)))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def num_partitions(mesh: Mesh) -> int:
    n = 1
    for name in mesh.axis_names:
        n *= mesh.shape[name]
    return n


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    with mesh:
        yield mesh
