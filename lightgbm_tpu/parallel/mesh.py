"""Device mesh utilities.

TPU-native replacement for the reference Network layer
(ref: src/network/network.cpp, include/LightGBM/network.h:90). Machine
lists, sockets and Bruck/recursive-halving collectives are replaced by a
`jax.sharding.Mesh` over ICI/DCN: arrays carry shardings and XLA's SPMD
partitioner inserts the all-reduce / reduce-scatter / all-gather
collectives that the reference implements by hand.

Axis names:
  "data" — row (data-parallel) axis: the analog of
           DataParallelTreeLearner's machine axis (parallel_tree_learner.h:54).
  "dcn"/"ici" — hierarchical data-parallel axes (get_hierarchical_mesh):
           rows shard over BOTH; histogram reduce-scatter runs over the
           fast in-process "ici" axis, and only each shard's owned
           feature slice crosses the slow "dcn" (cross-process) axis.
"""

from __future__ import annotations

from typing import Optional

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"

_active_mesh: Optional[Mesh] = None


def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """`jax.shard_map` with the replication check off — every
    shard_mapped program in this framework goes through here."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def get_mesh(num_shards: int = 0, devices=None) -> Mesh:
    """Build (or fetch) a 1-D data-parallel mesh.

    num_shards=0 -> all local devices. A mesh with one device degrades to
    the serial learner (XLA elides the collectives).
    """
    global _active_mesh
    if devices is None:
        devices = jax.devices()
    if num_shards and num_shards > 0:
        devices = devices[:num_shards]
    if (_active_mesh is not None
            and list(_active_mesh.devices.flat) == list(devices)):
        return _active_mesh
    _active_mesh = Mesh(np.asarray(devices), (DATA_AXIS,))
    return _active_mesh


DCN_AXIS = "dcn"
ICI_AXIS = "ici"


def get_hierarchical_mesh(devices=None,
                          num_groups: int = 0) -> Mesh:
    """2-D ("dcn", "ici") mesh for hierarchical reduce-scatter.

    Groups devices by process (one "dcn" row per host, its local devices
    along "ici"), matching the physical topology: ICI links within a
    process, data-center network between processes. On a single process
    ``num_groups`` can force an artificial split for testing. Row
    sharding uses BOTH axes (shard_data handles tuple specs); the
    learner's builders reduce-scatter over the last ("ici") axis and
    psum the surviving 1/W slice over "dcn" — see
    learner._sharded_pallas_multi and ISSUE/docs for the byte model.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if num_groups and num_groups > 1:
        groups = num_groups
    else:
        procs = sorted({d.process_index for d in devices})
        groups = len(procs)
        if groups > 1:
            by_proc = {p: [d for d in devices if d.process_index == p]
                       for p in procs}
            per = min(len(v) for v in by_proc.values())
            grid = np.asarray([by_proc[p][:per] for p in procs])
            return Mesh(grid, (DCN_AXIS, ICI_AXIS))
        groups = 1
    if len(devices) % groups != 0:
        raise ValueError(
            f"{len(devices)} devices do not split into {groups} groups")
    grid = np.asarray(devices).reshape(groups, -1)
    return Mesh(grid, (DCN_AXIS, ICI_AXIS))


def rows_spec(mesh: Mesh, ndim: int, row_axis: int = 0) -> P:
    """PartitionSpec sharding `row_axis` over ALL mesh axes (1-D "data"
    meshes and hierarchical ("dcn","ici") meshes alike)."""
    names = mesh.axis_names
    spec = [None] * ndim
    spec[row_axis] = names[0] if len(names) == 1 else tuple(names)
    return P(*spec)


def shard_data(mesh: Mesh, array, row_axis: int):
    """Place `array` sharded along its row dimension (rows over the mesh's
    data axis, or over all axes of a hierarchical mesh)."""
    sharding = NamedSharding(mesh, rows_spec(mesh, array.ndim, row_axis))
    return jax.device_put(array, sharding)


def replicate(mesh: Mesh, array):
    return jax.device_put(array, NamedSharding(mesh, P()))


def num_machines() -> int:
    """Reference Network::num_machines analog."""
    return _active_mesh.size if _active_mesh is not None else 1


def data_sharding(mesh: Mesh, ndim: int, row_axis: int = 0) -> NamedSharding:
    """NamedSharding placing an ndim-array's `row_axis` over "data" —
    the serving engine uses this to land prediction chunks pre-sharded
    so the shard_mapped traversal starts without a reshard
    (ops/predict.py predict_raw_cached)."""
    spec = [None] * ndim
    spec[row_axis] = DATA_AXIS
    return NamedSharding(mesh, P(*spec))


def is_replicated_on(mesh: Mesh, array) -> bool:
    """True when `array` physically holds a full copy on every device of
    `mesh` — the precondition for the cross-shard drift sentinels
    (obs/health.py): only state that is SUPPOSED to be identical on
    every chip can meaningfully be digest-compared across them."""
    sharding = getattr(array, "sharding", None)
    if sharding is None or not getattr(sharding, "is_fully_replicated",
                                       False):
        return False
    try:
        devices = set(sharding.device_set)
    except Exception:
        return False
    return set(mesh.devices.flat).issubset(devices)


def pad_rows_to_shards(n: int, mesh: Mesh) -> int:
    """Smallest row count >= n divisible by the mesh's data axis (row
    blocks fed to shard_map must split evenly across devices)."""
    s = max(mesh.size, 1)
    return -(-n // s) * s
