"""What an input node is bound to, and who holds it.

The binding types: one small class a kind of table an ``input`` node
can stand for, with the operations that mean something for it (one a
kind does not have raises).  And :class:`Inputs`, the one object of a
``DryadContext`` that maps node ids to bindings and owns what the
context keeps of a table between jobs.  Bindings pickle by reference
to this module (the job package ships them); ``kind`` is the string
events and the stream executor's plan cache carry.
"""

from __future__ import annotations

import gc
import math
import weakref
from collections import OrderedDict
from typing import Dict, Optional, Tuple

import numpy as np

from dryad_tpu.columnar.batch import ColumnBatch, _nbytes
from dryad_tpu.exec.checkpoint import content_fingerprint
from dryad_tpu.parallel import distribute as D
from dryad_tpu.parallel.mesh import num_partitions


def _len(cols) -> int:
    return len(next(iter(cols.values()))) if cols else 0


def _host_nbytes(cols) -> int:
    return sum(np.asarray(v).nbytes for v in cols.values())


class Binding:
    """What every kind answers; the defaults are those of a kind with
    nothing on the host and without the operation."""

    kind = ""
    # the device-resident table itself, for the one kind that is one
    batch: Optional[ColumnBatch] = None

    def rows(self) -> int:
        return 0

    def host_bytes(self) -> int:
        """Host bytes bound: a query's admission cost."""
        return 0

    def fingerprint(self) -> Optional[str]:
        """Content SHA-1 (checkpoint identity, the serving cache's
        key); None where it cannot be had without a host transfer."""
        return None

    def lay_out(self, schema, mesh, dictionary, **staging) -> ColumnBatch:
        """One sharded batch on ``mesh`` (``staging``: ``tracer`` /
        ``metrics`` / ``pool`` of ``parallel.distribute.lay_out``)."""
        raise RuntimeError(f"unknown binding kind {self.kind}")

    def table(self, schema, dictionary) -> Dict[str, np.ndarray]:
        """The plain physical table ``exec/localdebug.py`` interprets."""
        raise RuntimeError(f"localdebug: unsupported input binding {self.kind}")

    def part(self, i: int, n: int) -> "Binding":
        """The input channel of vertex task ``i`` of ``n`` (a
        ``DrStorageVertex`` holds one, ``DrVertex.h:146``): the union
        over the parts is exactly the whole input."""
        raise ValueError(f"cannot slice binding kind {self.kind!r}")

    def packed(self) -> "Binding":
        """What a job package ships in its place."""
        return self


class _HostColumns(Binding):
    """Equal-length host columns, split into contiguous blocks."""

    arrays: Dict[str, np.ndarray]

    def rows(self) -> int:
        return max((len(np.asarray(v)) for v in self.arrays.values()), default=0)

    def host_bytes(self) -> int:
        return _host_nbytes(self.arrays)

    def _block(self, i: int, n: int) -> Dict[str, np.ndarray]:
        return {
            k: np.array_split(np.asarray(v), n)[i] for k, v in self.arrays.items()
        }


class HostTable(_HostColumns):
    """``from_arrays``: the user's logical columns, as handed in."""

    kind = "host"

    def __init__(self, arrays, cap=None):
        self.arrays, self.cap = arrays, cap

    def fingerprint(self):
        return content_fingerprint(
            {str(k): np.asarray(v) for k, v in self.arrays.items()}
        ) + f":{self.cap}"

    def lay_out(self, schema, mesh, dictionary, **staging):
        return D.from_host_table(
            schema, self.arrays, mesh, partition_capacity=self.cap,
            dictionary=dictionary, **staging,
        )

    def table(self, schema, dictionary):
        b = ColumnBatch.from_numpy(
            schema, self.arrays, capacity=max(self.rows(), 1),
            dictionary=dictionary,
        )
        valid = np.asarray(b.valid)
        return {k: np.asarray(v)[valid] for k, v in b.data.items()}

    def part(self, i, n):
        return HostTable(self._block(i, n))


class PhysicalTable(_HostColumns):
    """``from_text``'s token words, the stream executor's chunks."""

    kind = "host_physical"

    def __init__(self, columns, cap=None):
        self.arrays, self.cap = columns, cap

    def fingerprint(self):
        return content_fingerprint(self.arrays) + (
            "" if self.cap is None else f":{self.cap}"
        )

    def lay_out(self, schema, mesh, dictionary, **staging):
        return D.from_physical_table(
            self.arrays, mesh, partition_capacity=self.cap, **staging
        )

    def table(self, schema, dictionary):
        return {k: np.asarray(v) for k, v in self.arrays.items()}

    def part(self, i, n):
        return PhysicalTable(self._block(i, n))


class RoutedTable(Binding):
    """The driver-routed layout of a co-partitioned vertex submission
    (``cluster/localjob.py``): rows pre-ordered by key bucket, part p
    owns ``[offsets[p], offsets[p + 1])``; shipped, never bound."""

    kind = "host_routed"

    def __init__(self, arrays, offsets):
        self.arrays, self.offsets = arrays, offsets

    def part(self, i, n):
        lo, hi = int(self.offsets[i]), int(self.offsets[i + 1])
        return HostTable({k: np.asarray(v)[lo:hi] for k, v in self.arrays.items()})


class StoreParts(Binding):
    """``from_store``: the store's partitions, read whole at bind time."""

    kind = "store"

    def __init__(self, parts, schema):
        self.parts, self.schema = parts, schema

    def rows(self):
        return sum(_len(cols) for cols in self.parts)

    def host_bytes(self):
        return sum(_host_nbytes(cols) for cols in self.parts)

    def fingerprint(self):
        return content_fingerprint({
            f"p{i}/{c}": v
            for i, cols in enumerate(self.parts) for c, v in cols.items()
        })

    def lay_out(self, schema, mesh, dictionary, **staging):
        # Fold store partitions onto mesh partitions (store partition i
        # concatenates into mesh partition i % P) so a store written on
        # a larger mesh loses nothing on a smaller one.
        P = num_partitions(mesh)
        folded: list = [[] for _ in range(P)]
        for i, cols in enumerate(self.parts):
            folded[i % P].append(cols)
        rows_per = [sum(_len(c) for c in group) for group in folded]
        cap = math.ceil(max(max(rows_per, default=1), 1) / 8) * 8

        def fill(out) -> None:
            for p, group in enumerate(folded):
                at = p * cap
                for cols in group:
                    n = _len(cols)
                    for c, col in out.items():
                        col[at : at + n] = cols[c]
                    at += n

        return D.lay_out(
            self.schema.device_dtypes(), rows_per, cap, fill, mesh, **staging
        )

    def table(self, schema, dictionary):
        return {
            c: np.concatenate([p[c] for p in self.parts]) for c in self.parts[0]
        }

    def part(self, i, n):
        # dealt round-robin, as ``lay_out`` folds them
        return StoreParts(self.parts[i::n], self.schema)


class DeviceTable(Binding):
    """``Query.cache()``, a ``do_while`` iteration's state."""

    kind = "device"

    def __init__(self, batch: ColumnBatch):
        self.batch = batch

    def packed(self):
        raise ValueError(
            "cannot pack a query over device-resident bindings; "
            "materialize to host or a store first"
        )


class ChunkStream(Binding):
    """``from_stream``: an ``exec.outofcore.ChunkSource``."""

    kind = "stream"

    def __init__(self, source):
        self.source = source

    def lay_out(self, schema, mesh, dictionary, **staging):
        raise RuntimeError(
            "a chunk-stream input cannot bind as a device table; "
            "this operator needs the whole input resident (e.g. "
            "cache/apply) — materialize with to_store() first"
        )


class LoopTable(Binding):
    """The state of a ``do_while`` iteration under ``local_debug``."""

    kind = "table"

    def __init__(self, table):
        self._table = table

    def table(self, schema, dictionary):
        return self._table


def _forget_owned(owner_ref, node_id: int) -> None:
    """Finalizer of an input node whose table the context made itself:
    nothing can reach the table once its node is gone.  The device
    cache's entry stays the LRU's, and goes by its budget."""
    owner = owner_ref()
    if owner is not None:
        owner._bound.pop(node_id, None)
        owner._fps.pop(node_id, None)


class Inputs:
    """The bindings of one ``DryadContext``, and the ONE place that
    says what the context holds of a table and when it lets go.

    **On the host, a kind.**  ``host`` (``from_arrays``): the user's
    own arrays, by reference, for the life of the binding (until
    ``rebind`` / ``forget`` or the context's end).  That is meant:
    they are the user's, and a requery after the device cache evicted
    the node lays the table out anew from them; a mutation in place is
    not seen (fingerprint and device table are the first execution's).
    ``store``: the partitions read at bind time, as ``host``.
    ``host_physical``: a table the context made; ``from_text`` binds
    its columns ``owned``, so binding and fingerprint die with their
    node (a ``weakref`` finalizer; a derived query keeps the node
    through ``inputs``) and the columns with the node's device entry,
    which holds them until the LRU evicts it (ROADMAP D11; inside a
    later job's ``collect``, under no span of its own); the stream
    executor's chunks go when the next chunk is moved onto their node.
    ``device`` / ``stream`` / ``table``: nothing.

    **Between host and device.**  ``device_batch`` lays a host kind
    out in the arenas of ``staging`` (host memory kept mapped between
    jobs; an arena is idle once its copy has landed) under a ``bind``
    span and keeps the batch in an LRU of ``config.device_cache_bytes``
    (the newest entry always stays; 0 turns it off).  jax keeps the
    source of every ``device_put`` until the calling thread next
    enters jax or Python collects (jax issue 14882); ``release`` is
    that collection (generation 0) under a ``release`` span, twice a
    job that ingested and never otherwise: before the first fetch
    (what a waited-for stage has used), and ``done`` after the last
    ``drop``, where it also unmaps what the runtime still held of the
    answer's host copies and trims the pool to the arenas the job
    used.  The sources are views of arenas, so no table is unmapped.

    **Every route that changes what a node stands for goes through
    ``rebind``**, which drops the node's fingerprint and device entry.

    A fifth holder of table-sized host memory (ROADMAP S13: answers
    that are views of the fetched arrays) would add here who holds the
    arrays (the answer), what lets go (the answer's death) and that
    ``release(done=True)`` must no longer unmap them.
    """

    def __init__(self, ctx) -> None:
        self._ctx = ctx
        self._bound: Dict[int, Binding] = {}
        self._fps: Dict[int, Optional[str]] = {}
        # node id -> (sharded batch, bytes, the binding it was laid out
        # from: its host columns live as long), least recently used first
        self._resident: "OrderedDict[int, tuple]" = OrderedDict()
        # an ingest has come and no ``release(done=True)`` since
        self._unreleased = False
        self.staging = D.StagingPool()
        # the fast gate of ``outofcore.has_stream_input``
        self.any_stream = False

    def bind(self, node, binding: Binding, owned: bool = False) -> None:
        """``owned``: the context made the table, so it goes when no
        query can reach ``node`` any more."""
        self.rebind(node.id, binding)
        if owned:
            weakref.finalize(
                node, _forget_owned, weakref.ref(self), node.id
            ).atexit = False

    def rebind(self, node_id: int, binding: Binding) -> None:
        self.forget(node_id)
        self._bound[node_id] = binding
        self.any_stream |= isinstance(binding, ChunkStream)

    def move(self, src_id: int, dst_id: int) -> None:
        """``dst_id`` now stands for what ``src_id`` stood for."""
        binding = self._bound[src_id]
        self.forget(src_id)
        self.rebind(dst_id, binding)

    def forget(self, node_id: int) -> None:
        self._bound.pop(node_id, None)
        self._fps.pop(node_id, None)
        self._resident.pop(node_id, None)

    def get(self, node_id: int) -> Optional[Binding]:
        return self._bound.get(node_id)

    def snapshot(self) -> Dict[int, Binding]:
        return dict(self._bound)

    def restore(self, bindings: Dict[int, Binding]) -> None:
        for node_id, binding in bindings.items():
            self.rebind(node_id, binding)

    def holds(self, node_id: int) -> Tuple[bool, bool, bool]:
        """Whether the node has (a binding, a fingerprint, a device entry)."""
        return (
            node_id in self._bound, node_id in self._fps,
            node_id in self._resident,
        )

    def fingerprint(self, node_id: int) -> Optional[str]:
        if node_id not in self._fps:
            self._fps[node_id] = self._bound[node_id].fingerprint()
        return self._fps[node_id]

    def device_batch(self, node) -> ColumnBatch:
        binding = self._bound.get(node.id)
        if binding is None:
            raise RuntimeError(
                f"input node {node.id} has no binding: its device-"
                "resident table was dropped (rebuild_mesh clears cached "
                "tables; release() drops them explicitly) — re-run "
                ".cache() or re-ingest"
            )
        if binding.batch is not None:
            return binding.batch
        ctx = self._ctx
        budget = ctx.config.device_cache_bytes
        if budget and node.id in self._resident:
            self._resident.move_to_end(node.id)
            return self._resident[node.id][0]
        with ctx.tracer.span("bind", cat="ingest", node=node.id):
            batch = binding.lay_out(
                node.schema, ctx.mesh, ctx.dictionary, tracer=ctx.tracer,
                metrics=ctx.executor.metrics, pool=self.staging,
            )
        self._unreleased = True
        if budget:
            self._resident[node.id] = (
                batch, _nbytes(batch.data) + batch.valid.size, binding
            )
            total = sum(e[1] for e in self._resident.values())
            while total > budget and len(self._resident) > 1:
                total -= self._resident.popitem(last=False)[1][1]
        return batch

    def release(self, done: bool = False) -> None:
        if not self._unreleased:
            return
        self._unreleased = not done
        with self._ctx.tracer.span("release", cat="ingest"):
            gc.collect(0)
            if done:
                self.staging.trim()

    def evict(self) -> None:
        """Empty the device cache; a node's next job lays it out anew."""
        self._resident.clear()

    def close(self) -> None:
        """Let go of what is kept between jobs for speed alone."""
        self.evict()
        self.staging.clear()

    def remesh(self) -> None:
        """What is sharded over the old mesh goes (device tables, the
        device cache, the pool); host and store bindings survive."""
        for node_id, binding in self.snapshot().items():
            if binding.batch is not None:
                self.forget(node_id)
        self.close()
