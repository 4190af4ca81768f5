"""Host<->mesh data movement for ColumnBatches.

The ingest/egress edge: the reference reads partitioned tables from
partfile/HDFS/Azure into per-vertex channels (``LinqToDryad/
DataProvider.cs``); here a global host table becomes one sharded
ColumnBatch (leading axis = partitions * capacity) laid out over the
mesh with ``NamedSharding``.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh

from dryad_tpu.columnar.batch import ColumnBatch, encode_table
from dryad_tpu.columnar.schema import Schema, StringDictionary
from dryad_tpu.parallel.mesh import num_partitions, partition_sharding


def shard_batch(batch: ColumnBatch, mesh: Mesh) -> ColumnBatch:
    """Place a (global-capacity) batch onto the mesh, row-sharded."""
    sh = partition_sharding(mesh)
    data = {n: jax.device_put(v, sh) for n, v in batch.data.items()}
    return ColumnBatch(data, jax.device_put(batch.valid, sh))


def shard_host_padded(
    data: Dict[str, np.ndarray], valid: np.ndarray, mesh: Mesh
) -> ColumnBatch:
    """One device_put per already-laid-out (P * cap) host column onto
    the row sharding — the ingest edge for host-side layouts.  No
    jitted concatenate/slice programs run, so ingest compiles nothing."""
    sh = partition_sharding(mesh)
    return ColumnBatch(
        {c: jax.device_put(v, sh) for c, v in data.items()},
        jax.device_put(valid, sh),
    )


def from_host_table(
    schema: Schema,
    arrays: Dict[str, np.ndarray],
    mesh: Mesh,
    partition_capacity: Optional[int] = None,
    dictionary: Optional[StringDictionary] = None,
) -> ColumnBatch:
    """Block-partition rows into P partitions of equal static capacity.

    Mirrors FromEnumerable/FromStore ingestion
    (``DryadLinqContext.cs:1176-1223``); every shard is near-equal
    before the first shuffle.
    """
    # Encode once on the HOST at exactly n rows (only real rows are
    # hashed / dictionary-registered), then block-partition the physical
    # columns through the shared path: one sharded device_put per
    # column, no full-size array on the default device.
    phys, _n = encode_table(schema, arrays, dictionary)
    return from_physical_table(phys, mesh, partition_capacity)


def from_physical_table(
    phys: Dict[str, np.ndarray],
    mesh: Mesh,
    partition_capacity: Optional[int] = None,
) -> ColumnBatch:
    """Block-partition already-encoded physical columns (no hashing).

    Partition p holds contiguous rows [p*per, (p+1)*per), so the
    engine's partition-major global order equals the original row order
    (zip/take semantics match the host table).
    """
    P = num_partitions(mesh)
    names = list(phys.keys())
    n = len(np.asarray(phys[names[0]])) if names else 0
    per = -(-n // P) if n else 1
    cap = partition_capacity if partition_capacity is not None else per
    if cap < per:
        raise ValueError(f"partition_capacity {cap} < required {per}")
    # Lay out the (P * cap) global buffer entirely on the host (this
    # path used to build per-partition device arrays and compile four
    # concatenate/slice programs).
    sizes = [
        min((p + 1) * per, n) - min(p * per, n) for p in range(P)
    ]
    data = {}
    for c in names:
        a = np.asarray(phys[c])
        pad = np.zeros((P * cap,) + a.shape[1:], a.dtype)
        for p, m in enumerate(sizes):
            lo = min(p * per, n)
            pad[p * cap : p * cap + m] = a[lo : lo + m]
        data[c] = pad
    valid = np.zeros(P * cap, np.bool_)
    for p, m in enumerate(sizes):
        valid[p * cap : p * cap + m] = True
    return shard_host_padded(data, valid, mesh)


def to_host_table(
    batch: ColumnBatch,
    schema: Schema,
    dictionary: Optional[StringDictionary] = None,
) -> Dict[str, np.ndarray]:
    """Gather a sharded batch back to host logical columns (egress)."""
    return batch.to_numpy(schema, dictionary)
