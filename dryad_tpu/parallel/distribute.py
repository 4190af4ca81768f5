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

from dryad_tpu.columnar.batch import ColumnBatch, _nbytes, encode_table
from dryad_tpu.columnar.schema import Schema, StringDictionary
from dryad_tpu.obs.span import UNTRACED, Tracer
from dryad_tpu.parallel.mesh import num_partitions, partition_sharding


def shard_batch(
    batch: ColumnBatch, mesh: Mesh, tracer: Tracer = UNTRACED, metrics=None
) -> ColumnBatch:
    """Place a (global-capacity) batch onto the mesh, row-sharded."""
    return shard_host_padded(batch.data, batch.valid, mesh, tracer, metrics)


def shard_host_padded(
    data: Dict[str, np.ndarray], valid: np.ndarray, mesh: Mesh,
    tracer: Tracer = UNTRACED, metrics=None,
) -> ColumnBatch:
    """One device_put per already-laid-out (P * cap) host column onto
    the row sharding — the ingest edge for host-side layouts.  No
    jitted concatenate/slice programs run, so ingest compiles nothing.

    The one H2D site: the copies sit inside ONE ``h2d`` span of
    ``tracer`` whose ``bytes`` is also what the ``h2d_bytes`` counter of
    ``metrics`` (the executor's registry) gains.  ``device_put`` returns
    once the copies are enqueued, so the span times the enqueue, not
    the transfer."""
    sh = partition_sharding(mesh)
    nbytes = _nbytes(data) + valid.nbytes
    with tracer.span("h2d", cat="ingest", bytes=nbytes):
        out = ColumnBatch(
            {c: jax.device_put(v, sh) for c, v in data.items()},
            jax.device_put(valid, sh),
        )
    if metrics is not None:
        metrics.add("h2d_bytes", nbytes)
    return out


def from_host_table(
    schema: Schema,
    arrays: Dict[str, np.ndarray],
    mesh: Mesh,
    partition_capacity: Optional[int] = None,
    dictionary: Optional[StringDictionary] = None,
    tracer: Tracer = UNTRACED,
    metrics=None,
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
    rows = len(next(iter(arrays.values()))) if arrays else 0
    with tracer.span("encode", cat="ingest", account=True, rows=rows) as sp:
        phys, _n = encode_table(schema, arrays, dictionary, tracer)
        sp.add(bytes_out=_nbytes(phys))
    return from_physical_table(
        phys, mesh, partition_capacity, tracer=tracer, metrics=metrics
    )


def from_physical_table(
    phys: Dict[str, np.ndarray],
    mesh: Mesh,
    partition_capacity: Optional[int] = None,
    tracer: Tracer = UNTRACED,
    metrics=None,
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
    with tracer.span(
        "encode", cat="ingest", account=True, rows=n, capacity=P * cap
    ) as sp:
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
        sp.add(bytes_out=_nbytes(data) + valid.nbytes)
    return shard_host_padded(data, valid, mesh, tracer, metrics)


def to_host_table(
    batch: ColumnBatch,
    schema: Schema,
    dictionary: Optional[StringDictionary] = None,
) -> Dict[str, np.ndarray]:
    """Gather a sharded batch back to host logical columns (egress)."""
    return batch.to_numpy(schema, dictionary)
