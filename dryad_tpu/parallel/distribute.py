"""Host<->mesh data movement for ColumnBatches.

The ingest/egress edge: the reference reads partitioned tables from
partfile/HDFS/Azure into per-vertex channels (``LinqToDryad/
DataProvider.cs``); here a global host table becomes one sharded
ColumnBatch (leading axis = partitions * capacity) laid out over the
mesh with ``NamedSharding``.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from dryad_tpu.columnar.batch import (
    ColumnBatch,
    _nbytes,
    encode_physical,
    host_to_device,
)
from dryad_tpu.columnar.schema import Schema, StringDictionary, bytes_to_words
from dryad_tpu.obs.span import UNTRACED, Tracer
from dryad_tpu.parallel.mesh import num_partitions, partition_sharding


class _Arena:
    """One run of host bytes of a :class:`StagingPool`, and the device
    array last copied from it, for as long as that copy may still be
    reading the bytes."""

    __slots__ = ("mem", "sent_to", "used")

    def __init__(self, nbytes: int):
        self.mem = np.empty(nbytes, np.uint8)
        self.sent_to: Optional[jax.Array] = None
        self.used = True

    def landed(self) -> bool:
        """The copy out of the arena is done.  Only the array can say
        so (``is_ready``), so it is held until it has, and let go of
        here; a deleted one never can (``is_ready`` of a deleted array
        crashes jaxlib 0.9.0), and its arena is not offered again."""
        d = self.sent_to
        if d is not None and not d.is_deleted() and d.is_ready():
            self.sent_to = d = None
        return d is None


class StagingPool:
    """The host memory a context lays its tables out in, kept between
    jobs so that a table is written into pages that are already mapped
    (a copy into newly mapped memory runs at 0.9 GB/s on the chip's
    host, into warm memory at 16.5; PERF.md section 6, PR 34 and 36).

    An arena is a run of bytes; :meth:`take` hands out the smallest
    idle one that holds what is asked for, viewed by the caller as the
    dtype and length it needs, so a smaller table after a larger one
    is still warm; where none fits, it allocates (a miss: the
    ``warm_bytes`` of the ``encode`` span leave those bytes out).

    **When an arena is idle.**  It is checked out from :meth:`take`
    until :meth:`sent`, which is told the device array that was put
    from it.  jax reads a ``device_put``'s source until the transfer
    completes (on both backends in another thread, after the call has
    returned), so the arena is offered again only once that array
    ``is_ready()``.  That the array is GONE proves nothing: its holder
    may drop it while the copy, and a program that will read it, are
    still in flight (the streaming driver rebinds a cached input node
    to the next chunk, which drops the chunk before from the device
    cache ahead of the ingest), and rows written into the arena then
    would reach that program.  So the pool holds the array itself, and
    only until a :meth:`take` or :meth:`trim` finds it ready: from the
    end of the job that ingested it at the latest, nothing of the pool
    pins device memory, and an array the device cache evicts later is
    freed there and then.  An ingest that finds every arena in flight
    allocates.  Where the backend made the device array OF the arena
    instead of a copy (the CPU client aliases a 64-byte aligned
    source), :meth:`sent` finds a shard's buffer inside the arena's
    addresses and the pool forgets the arena: it belongs to that array
    now.

    **What is kept.**  :meth:`trim`, at the end of a job that ingested,
    lets go of the arenas no ingest has used since the trim before, so
    the pool holds about what one job staged; :meth:`clear` lets go of
    all (``DryadContext.close`` and ``rebuild_mesh``).  Threads share
    the pool under one lock; a checked-out arena is its holder's alone.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._idle: List[_Arena] = []

    def take(self, nbytes: int) -> Tuple[_Arena, bool]:
        """``(arena of at least nbytes, whether it was used before)``."""
        with self._lock:
            fits = [  # every arena is asked: a landed one lets its array go
                (a.mem.nbytes, i) for i, a in enumerate(self._idle)
                if a.landed() and a.mem.nbytes >= nbytes
            ]
            if fits:
                return self._idle.pop(min(fits)[1]), True
        return _Arena(nbytes), False

    def sent(self, arena: _Arena, array: jax.Array) -> None:
        """``array`` was ``device_put`` from a view of ``arena``."""
        lo = arena.mem.ctypes.data
        for shard in array.addressable_shards:
            if lo <= shard.data.unsafe_buffer_pointer() < lo + arena.mem.nbytes:
                return
        arena.sent_to = array
        arena.used = True
        with self._lock:
            self._idle.append(arena)

    def trim(self) -> None:
        with self._lock:
            self._idle = [a for a in self._idle if a.used]
            for a in self._idle:
                a.used = False
                a.landed()

    def clear(self) -> None:
        with self._lock:
            self._idle = []

    def held_bytes(self) -> int:
        with self._lock:
            return sum(a.mem.nbytes for a in self._idle)


_VALID = "#valid"  # no column's name (ColumnBatch.fetch_host keeps it so)


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
    the row sharding — the ingest edge for host-side layouts
    (:func:`lay_out` writes them).  No jitted concatenate/slice
    programs run, so ingest compiles nothing.

    The one H2D site: the copies sit inside ONE ``h2d`` span of
    ``tracer`` whose ``bytes`` is also what the ``h2d_bytes`` counter of
    ``metrics`` (the executor's registry) gains.  ``device_put`` returns
    once the copies are enqueued, so the span times the enqueue, not
    the transfer: the host columns must stay as they are until each
    device array ``is_ready()`` (the staging pool's rule)."""
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


def lay_out(
    dtypes: Dict[str, np.dtype],
    sizes: Sequence[int],
    cap: int,
    fill: Callable[[Dict[str, np.ndarray]], None],
    mesh: Mesh,
    tracer: Tracer = UNTRACED,
    metrics=None,
    pool: Optional[StagingPool] = None,
) -> ColumnBatch:
    """THE place a host table takes its device layout: ``P * cap``
    slots a physical column (``dtypes``: name -> dtype), partition p in
    ``[p * cap, p * cap + sizes[p])``, zeros behind each partition's
    rows, ``valid`` written the same way; then the one ``device_put`` a
    column (:func:`shard_host_padded`).

    ``fill(columns)`` writes the rows: it is handed the columns at
    ``P * cap`` slots and writes each partition's rows where they
    belong, straight from the caller's arrays (one pass, no array in
    between).  The columns are views of the ``pool``'s arenas and go
    back to it once sent; a caller without a context has no pool and
    gets one that dies with the call, so it allocates, as ever.

    ONE ``encode`` span a table around all of it: ``rows``,
    ``capacity`` (``P * cap``), ``bytes_out`` (the bytes of the layout)
    and ``warm_bytes`` (those of them written into an arena that was
    used before); ``metrics`` gains the same two numbers under
    ``ingest_staged_bytes`` / ``ingest_warm_bytes``."""
    slots = len(sizes) * cap
    if pool is None:
        pool = StagingPool()
    with tracer.span(
        "encode", cat="ingest", account=True, rows=sum(sizes), capacity=slots
    ) as sp:
        arenas: Dict[str, _Arena] = {}
        columns: Dict[str, np.ndarray] = {}
        staged = warm = 0
        for name, dtype in [*dtypes.items(), (_VALID, np.dtype(np.bool_))]:
            nbytes = slots * dtype.itemsize
            arenas[name], was_used = pool.take(nbytes)
            columns[name] = arenas[name].mem[:nbytes].view(dtype)
            staged += nbytes
            if was_used:
                warm += nbytes
        valid = columns.pop(_VALID)
        fill(columns)
        for p, m in enumerate(sizes):
            valid[p * cap : p * cap + m] = True
            for col in (valid, *columns.values()):
                col[p * cap + m : (p + 1) * cap] = 0
        sp.add(bytes_out=staged, warm_bytes=warm)
    if metrics is not None:
        metrics.add("ingest_staged_bytes", staged)
        metrics.add("ingest_warm_bytes", warm)
    batch = shard_host_padded(columns, valid, mesh, tracer, metrics)
    for name, arena in arenas.items():
        pool.sent(arena, batch.valid if name == _VALID else batch.data[name])
    return batch


def _block_sizes(n: int, P: int, partition_capacity: Optional[int]):
    """``(rows a partition, capacity a partition)`` of ``n`` rows cut
    into P contiguous blocks: partition p holds rows ``[p * per,
    (p + 1) * per)``, so the engine's partition-major global order
    equals the original row order (zip/take semantics match the host
    table)."""
    per = -(-n // P) if n else 1
    cap = partition_capacity if partition_capacity is not None else per
    if cap < per:
        raise ValueError(f"partition_capacity {cap} < required {per}")
    return [min((p + 1) * per, n) - min(p * per, n) for p in range(P)], cap


def _blocks(sizes: Sequence[int], cap: int):
    """``(slot of the partition's first row, its first row in the
    table, its rows)`` a partition."""
    at = 0
    for p, m in enumerate(sizes):
        yield p * cap, at, m
        at += m


def _copy_in(out, phys: Dict[str, np.ndarray], sizes, cap: int) -> None:
    """Each column's rows into its partitions' slots, cast to the
    layout's dtype on the way: what ``astype`` and a padded copy did
    in two passes, with no array in between."""
    for name, src in phys.items():
        for lo, at, m in _blocks(sizes, cap):
            np.copyto(out[name][lo : lo + m], src[at : at + m], casting="unsafe")


def from_host_table(
    schema: Schema,
    arrays: Dict[str, np.ndarray],
    mesh: Mesh,
    partition_capacity: Optional[int] = None,
    dictionary: Optional[StringDictionary] = None,
    tracer: Tracer = UNTRACED,
    metrics=None,
    pool: Optional[StagingPool] = None,
) -> ColumnBatch:
    """Block-partition rows into P partitions of equal static capacity.

    Mirrors FromEnumerable/FromStore ingestion
    (``DryadLinqContext.cs:1176-1223``); every shard is near-equal
    before the first shuffle.

    A logical column goes to its ``P * cap`` layout in one pass
    (:func:`lay_out`): a column whose physical form is a cast is cast
    as it is copied into place, a BYTES column's words are written
    where they belong by ``bytes_to_words(out=)`` (a ``pack`` span a
    column: ``bytes`` as handed in, ``rows``); only real rows are
    hashed / dictionary-registered.  STRING / INT64 / FLOAT64 columns
    take their physical form at n rows first
    (``columnar.batch.encode_physical``) and are copied in.  Nothing
    is staged on one device: one sharded device_put per column.
    """
    cols = {f.name: np.asarray(arrays[f.name]) for f in schema.fields}
    rows = {len(a) for a in cols.values()}
    if len(rows) > 1:
        raise ValueError("ragged input columns")
    sizes, cap = _block_sizes(
        rows.pop() if rows else 0, num_partitions(mesh), partition_capacity
    )

    def fill(out: Dict[str, np.ndarray]) -> None:
        for f in schema.fields:
            a = cols[f.name]
            if f.ctype.is_bytes:
                with tracer.span(
                    "pack", cat="ingest", account=True, bytes=a.size, rows=len(a)
                ) as sp:
                    words = [out[w] for w in f.device_names]
                    for lo, at, m in _blocks(sizes, cap):
                        bytes_to_words(
                            a[at : at + m], f.ctype.width,
                            out=[w[lo : lo + m] for w in words],
                        )
                    sp.add(bytes_out=4 * len(words) * len(a))
            elif f.ctype.is_split:
                _copy_in(out, encode_physical(f, a, dictionary), sizes, cap)
            elif a.dtype.kind == "M" and a.dtype != np.dtype("datetime64[D]"):
                # a DATE in another unit; days are cast as they are copied
                _copy_in(out, {f.name: host_to_device(f.ctype, a)}, sizes, cap)
            else:
                _copy_in(out, {f.name: a}, sizes, cap)

    return lay_out(
        schema.device_dtypes(), sizes, cap, fill, mesh, tracer, metrics, pool
    )


def from_physical_table(
    phys: Dict[str, np.ndarray],
    mesh: Mesh,
    partition_capacity: Optional[int] = None,
    tracer: Tracer = UNTRACED,
    metrics=None,
    pool: Optional[StagingPool] = None,
) -> ColumnBatch:
    """Block-partition already-encoded physical columns (no hashing):
    each is copied once, into its ``P * cap`` layout
    (:func:`lay_out`), partition p the contiguous rows
    ``[p * per, (p + 1) * per)``."""
    phys = {c: np.asarray(a) for c, a in phys.items()}
    n = len(next(iter(phys.values()))) if phys else 0
    sizes, cap = _block_sizes(n, num_partitions(mesh), partition_capacity)
    return lay_out(
        {c: a.dtype for c, a in phys.items()}, sizes, cap,
        lambda out: _copy_in(out, phys, sizes, cap),
        mesh, tracer, metrics, pool,
    )


def to_host_table(
    batch: ColumnBatch,
    schema: Schema,
    dictionary: Optional[StringDictionary] = None,
) -> Dict[str, np.ndarray]:
    """Gather a sharded batch back to host logical columns (egress)."""
    return batch.to_numpy(schema, dictionary)
