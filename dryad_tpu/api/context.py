"""DryadContext — the entry point and job driver.

The analog of ``DryadLinqContext`` (``LinqToDryad/DryadLinqContext.cs:566``):
owns platform selection (reference LOCAL/YARN_NATIVE/YARN_AZURE,
``DryadLinqContext.cs:55-71`` — here TPU mesh vs host-local CPU mesh),
per-context config, dataset ingestion (FromStore/FromEnumerable,
``:1176-1223``), the LocalDebug differential path
(``DryadLinqContext.cs:966-983`` — LINQ-to-Objects there, a NumPy
interpreter here), and job submission, which lowers the plan and runs
the GraphExecutor (replacing the GraphManager process tree).
"""

from __future__ import annotations

import itertools
import os
import time
from typing import Dict, Optional, Tuple

import numpy as np

from dryad_tpu.api.query import JobHandle, Query
from dryad_tpu.columnar import io as CIO
from dryad_tpu.columnar.batch import ColumnBatch, _nbytes
from dryad_tpu.columnar.schema import (
    BYTES,
    ColumnType,
    DecimalType,
    Schema,
    StringDictionary,
)
from dryad_tpu.exec.events import EventLog
from dryad_tpu.exec.executor import GraphExecutor
from dryad_tpu.exec.inputs import (
    ChunkStream,
    DeviceTable,
    HostTable,
    Inputs,
    PhysicalTable,
    StoreParts,
)
from dryad_tpu.obs import flightrec, tracectx
from dryad_tpu.obs.span import Tracer
from dryad_tpu.obs.diagnose import DiagnosisEngine
from dryad_tpu.rewrite.controller import RewriteController
from dryad_tpu.parallel.mesh import make_mesh, num_partitions
from dryad_tpu.plan.lower import lower
from dryad_tpu.plan.nodes import Node, PartitionInfo
from dryad_tpu.utils.config import DryadConfig
from dryad_tpu.utils.logging import get_logger

log = get_logger("dryad_tpu.api")


# Ring-buffer cap for the context EventLog's in-memory mirror: long
# out-of-core jobs emit per-chunk/span events without bound; the file
# sink (event_log_dir) keeps the full stream.
_EVENTS_MEM_CAP = 1 << 16

_NP_TYPE_MAP = {
    np.dtype(np.int32): ColumnType.INT32,
    np.dtype(np.int64): ColumnType.INT64,
    np.dtype(np.float32): ColumnType.FLOAT32,
    # float64 is PRESERVED (order-preserving split-word storage,
    # columnar/schema.py): exact round-trip, ordering, min/max, joins;
    # device arithmetic (sum/mean) requires an explicit f32 cast.
    np.dtype(np.float64): ColumnType.FLOAT64,
    np.dtype(np.bool_): ColumnType.BOOL,
    np.dtype(np.uint32): ColumnType.UINT32,
    np.dtype("datetime64[D]"): ColumnType.DATE,
}


def _word_vocab(distinct) -> np.ndarray:
    """The sorted 64-bit word hashes of a table, from the lists of
    distinct words its buffers' tokenizer passes found (never from the
    tokens)."""
    v = np.sort(np.concatenate(distinct)) if distinct else np.zeros(0, np.uint64)
    keep = np.ones(len(v), bool)
    keep[1:] = v[1:] != v[:-1]
    return v[keep]


def _infer_schema(arrays: Dict[str, np.ndarray]) -> Schema:
    fields = []
    for name, a in arrays.items():
        a = np.asarray(a)
        if a.dtype == np.uint8 and a.ndim == 2 and a.shape[1] > 0:
            fields.append((name, BYTES(a.shape[1])))
        elif a.dtype == object or a.dtype.kind in ("U", "S"):
            fields.append((name, ColumnType.STRING))
        elif a.dtype in _NP_TYPE_MAP:
            fields.append((name, _NP_TYPE_MAP[a.dtype]))
        else:
            raise TypeError(f"column {name!r}: unsupported dtype {a.dtype}")
    return Schema(fields)


def _fetch_with_miss(batch, deferred, tracer, metrics):
    """Fetch a result batch host-side with the job's deferred dict-miss
    counters riding the same ``device_get``, resolve the deferred tail
    (raises on a nonzero counter), and return ``(valid, host_cols,
    rows)`` as :meth:`ColumnBatch.fetch_host` gives them."""
    miss = deferred.miss_arrays()
    try:
        valid, host_cols, miss_vals, rows = batch.fetch_host(
            extra=miss, tracer=tracer, metrics=metrics
        )
    except Exception as e:  # transfer failure: close out the job
        deferred.abort(f"output transfer failed: {e!r}")
        raise
    deferred.finish(miss_vals)
    return valid, host_cols, rows


class DryadContext:
    def __init__(
        self,
        num_partitions_: Optional[int] = None,
        config: Optional[DryadConfig] = None,
        local_debug: bool = False,
        dcn_slices: Optional[int] = None,
        mesh=None,
    ):
        self.config = config or DryadConfig()
        self.config.validate()
        self.local_debug = local_debug
        self.dictionary = StringDictionary()
        # what every input node is bound to, and all the context keeps
        # of a table between jobs (exec.inputs.Inputs)
        self.inputs = Inputs(self)
        # Column-name -> TypeCodec for custom user types (the
        # IDryadLinqSerializer hook, columnar/codecs.py).
        self._codecs: Dict[str, object] = {}
        self.diagnosis: Optional[DiagnosisEngine] = None
        self.rewriter = None
        # Continuous telemetry plane (obs.telemetry): the tap-paced
        # resource sampler and its measured HeadroomProvider, consumed
        # by the adaptive exchange-window and dispatch-depth policies.
        # None (local_debug / obs_telemetry=False) = budget fallbacks.
        self.telemetry = None
        self.headroom = None
        if local_debug:
            self.mesh = None
            self.executor = None
            self.events = EventLog(None)
        else:
            if mesh is not None:
                self.mesh = mesh
            elif dcn_slices is not None:
                # Hybrid multi-slice mesh: inner axis over ICI, outer
                # over DCN (reference machine→pod hierarchy).
                from dryad_tpu.parallel.mesh import make_hybrid_mesh

                if (
                    num_partitions_ is not None
                    and num_partitions_ % dcn_slices != 0
                ):
                    raise ValueError(
                        f"num_partitions_ {num_partitions_} not divisible "
                        f"by dcn_slices {dcn_slices}"
                    )
                ici = (
                    num_partitions_ // dcn_slices
                    if num_partitions_ is not None
                    else None
                )
                self.mesh = make_hybrid_mesh(dcn_slices, ici)
            else:
                self.mesh = make_mesh(num_partitions_)
            path = None
            if self.config.event_log_dir:
                path = os.path.join(
                    self.config.event_log_dir, f"job-{int(time.time()*1000)}.jsonl"
                )
            self.events = EventLog(path, mem_cap=_EVENTS_MEM_CAP)
            # Flight recorder: always-on crash-forensics ring tapped
            # into this context's stream, dumped on JobFailedError /
            # unhandled exceptions (obs.flightrec).  The driver does
            # NOT dump on clean exit.  The process recorder may already
            # be owned by someone with a better dump location — the
            # worker harness (role "worker-<i>") or a LocalJobSubmission
            # driver, both dumping into the shared job root.  In that
            # case tap this context's stream into the existing ring
            # instead of displacing it.
            if self.config.obs_flight_recorder:
                rec = flightrec.get_recorder()
                if rec is not None:
                    self.events.add_tap(rec.record)
                else:
                    flightrec.install_recorder(
                        dump_dir=(
                            self.config.flightrec_dir
                            or self.config.event_log_dir
                            or "."
                        ),
                        role="driver",
                        events=self.events,
                    )
            # Online diagnosis engine: live pathology folds over the
            # same stream (obs.diagnose); diagnoses are emitted back
            # into it and retained for explain(analyze=True)/jobview.
            if self.config.obs_diagnosis:
                self.diagnosis = DiagnosisEngine(
                    config=self.config, events=self.events
                )
                self.events.add_tap(self.diagnosis.observe)
            # Runtime plan rewriter: folds the diagnoses above into
            # pending rewrite actions the execution drivers poll at
            # safe boundaries (rewrite.controller).  Rides the same
            # tap mechanism; needs the diagnosis engine upstream.
            if self.config.obs_diagnosis and self.config.plan_rewrite:
                self.rewriter = RewriteController(
                    config=self.config, events=self.events
                )
                self.events.add_tap(self.rewriter.observe)
            # Resource sampler: opportunistic (event-tap-paced, the
            # flightrec discipline — no thread here; resident
            # processes call ctx.telemetry.start()).  Its samples feed
            # the hbm_pressure diagnosis upstream and the measured
            # HeadroomProvider the executor consults below.
            if getattr(self.config, "obs_telemetry", True):
                from dryad_tpu.obs.telemetry import ResourceMonitor

                self.telemetry = ResourceMonitor(events=self.events)
                self.headroom = self.telemetry.headroom
                self.events.add_tap(self.telemetry.observe)
            self.executor = GraphExecutor(
                self.mesh, self.config, self.events,
                subquery_runner=self._run_subquery,
                loop_lowerer=self._lower_loop_stage,
            )
            self.executor.rewriter = self.rewriter
            self.executor.headroom = self.headroom
        # ONE tracer (one thread-local span stack) for the context, its
        # executor and its telemetry sampler, so a stage's span and a
        # sample's nest under the job's
        self.tracer = (
            self.executor.tracer if self.executor is not None
            else Tracer(self.events)
        )
        if self.telemetry is not None:
            self.telemetry.tracer = self.tracer

    def rebuild_mesh(self, exclude_device_ids) -> None:
        """Elastic recovery: shrink the mesh past failed devices and
        rebuild the executor (reference: dynamic computer set +
        requeue-with-exclusion, ``Interfaces.cs:336-343``).  Device-
        resident bindings are dropped — re-ingest or resume stages from
        the checkpoint store; host/store bindings survive."""
        from dryad_tpu.parallel.mesh import exclude_devices

        self.mesh = exclude_devices(self.mesh, exclude_device_ids)
        self.inputs.remesh()
        self.executor = GraphExecutor(
            self.mesh, self.config, self.events,
            subquery_runner=self._run_subquery,
            loop_lowerer=self._lower_loop_stage,
        )
        self.executor.rewriter = self.rewriter
        self.executor.headroom = self.headroom

    def close(self) -> None:
        """Let go of what the context keeps between jobs for speed
        alone: the staging pool's host memory and the device-resident
        ingest cache.  The context stays usable; its next job ingests
        again, into newly mapped memory."""
        self.inputs.close()

    # -- ingestion ----------------------------------------------------------
    def from_arrays(
        self,
        arrays: Dict[str, np.ndarray],
        schema: Optional[Schema] = None,
        partition_capacity: Optional[int] = None,
        codecs: Optional[Dict[str, object]] = None,
    ) -> Query:
        """Create a table from host arrays (reference FromEnumerable).

        ``codecs``: column name -> ``columnar.codecs.TypeCodec`` for
        custom user types; each coded column expands into typed device
        columns at ingest and folds back at egress."""
        if codecs:
            from dryad_tpu.columnar.codecs import expand_arrays

            arrays = expand_arrays(arrays, codecs)
            self._codecs.update(codecs)
        schema = schema or _infer_schema(arrays)
        # Register string values at DEFINITION time (unique-first, so
        # the pass is vocabulary-sized): the auto-dense STRING group_by
        # codes against the dictionary at lowering, which runs before
        # ingest would otherwise populate it.  Skipped when the feature
        # is off — ingest registers the same strings at bind time.
        str_vocab = {}
        if getattr(self.config, "auto_dense_strings", True):
            for name in schema.names:
                if (
                    schema.field(name).ctype is ColumnType.STRING
                    and name in arrays
                ):
                    # Unique the object array directly: .astype(str)
                    # would materialize a fixed-width unicode copy of
                    # the whole column (width = longest string) just to
                    # throw it away.  The per-COLUMN hash set feeds the
                    # per-ingest auto-dense gate: one big-vocabulary
                    # ingest elsewhere must not disable the fast path
                    # for every later query (round-3 weak item 7).
                    hs = [
                        self.dictionary.add(str(s))
                        for s in np.unique(np.asarray(arrays[name], object))
                    ]
                    str_vocab[name] = np.sort(
                        np.asarray(hs, dtype=np.uint64)
                    )
        # Ingest column statistics: INT32 ranges feed the int auto-dense
        # group_by rewrite (the observed-data-size adaptation of
        # DrDynamicRangeDistributor.cpp:54-110 applied to key domains).
        # Skipped when the sole consumer is off.
        col_stats = {}
        if getattr(self.config, "auto_dense_ints", True):
            for name in schema.names:
                if (
                    schema.field(name).ctype is ColumnType.INT32
                    and name in arrays
                ):
                    a = np.asarray(arrays[name])
                    if a.size:
                        col_stats[name] = (int(a.min()), int(a.max()))
        node = Node(
            "input", [], schema, PartitionInfo.roundrobin(),
            source="host", col_stats=col_stats, str_vocab=str_vocab,
        )
        self.inputs.bind(node, HostTable(arrays, partition_capacity))
        return Query(self, node)

    def append_arrays(
        self, query: Query, arrays: Dict[str, np.ndarray]
    ) -> Optional[str]:
        """Append host rows to an existing ``from_arrays`` table IN
        PLACE — the continuous-ingest write path.  The node keeps its
        identity (registered views and prepared queries keep pointing
        at it); the binding is REBOUND to the concatenated columns, so
        the device-ingest cache and the binding fingerprint both
        self-invalidate.  Auto-dense metadata (string vocab, int key
        ranges) WIDENS so lowering decisions stay sound for the grown
        domain.  Returns the binding fingerprint the table had BEFORE
        the append (None when unfingerprintable) — the invalidation
        key for any result cached against the old contents."""
        node = query.node
        binding = self.inputs.get(node.id)
        if node.kind != "input" or not isinstance(binding, HostTable):
            raise ValueError(
                "append_arrays() takes a from_arrays table; got a "
                f"{node.kind!r} node bound as "
                f"{binding.kind if binding else None!r}"
            )
        if self._codecs and any(c in self._codecs for c in arrays):
            from dryad_tpu.columnar.codecs import expand_arrays

            arrays = expand_arrays(
                arrays, {c: self._codecs[c] for c in arrays
                         if c in self._codecs}
            )
        old_arrays = binding.arrays
        if set(arrays) != set(old_arrays):
            raise ValueError(
                f"append columns {sorted(arrays)} != table columns "
                f"{sorted(old_arrays)}"
            )
        old_fp = self.inputs.fingerprint(node.id)
        merged = {}
        for name, old in old_arrays.items():
            old = np.asarray(old)
            new = np.asarray(arrays[name])
            if old.dtype == object or old.dtype.kind in ("U", "S"):
                new = np.asarray(new, object)
            elif new.dtype != old.dtype:
                raise TypeError(
                    f"column {name!r}: append dtype {new.dtype} != "
                    f"table dtype {old.dtype}"
                )
            merged[name] = np.concatenate(
                [np.asarray(old, object) if old.dtype == object else old,
                 new]
            )
        # Widen the auto-dense gates for the new rows (same policy as
        # from_arrays; a widened vocab/range only loosens the gate).
        if getattr(self.config, "auto_dense_strings", True):
            vocab = node.params.get("str_vocab") or {}
            for name in vocab:
                if name in arrays:
                    hs = [
                        self.dictionary.add(str(s))
                        for s in np.unique(np.asarray(arrays[name], object))
                    ]
                    vocab[name] = np.unique(np.concatenate([
                        vocab[name], np.asarray(hs, dtype=np.uint64)
                    ]))
            node.params["str_vocab"] = vocab
        if getattr(self.config, "auto_dense_ints", True):
            stats = node.params.get("col_stats") or {}
            for name, (lo, hi) in list(stats.items()):
                a = np.asarray(arrays.get(name, ()))
                if a.size:
                    stats[name] = (
                        min(lo, int(a.min())), max(hi, int(a.max()))
                    )
            node.params["col_stats"] = stats
        self.inputs.rebind(node.id, HostTable(merged, binding.cap))
        return old_fp

    def _tokenize_buf(self, buf: bytes):
        """Tokenize one byte buffer and register its distinct words in
        the context dictionary, each decoded from its first occurrence;
        returns the tokenizer's :class:`~dryad_tpu.runtime.bindings.Tokens`."""
        from dryad_tpu.runtime import bindings as RB

        toks = RB.tokenize(buf)
        known = self.dictionary._map
        for h, s, n in zip(
            toks.hashes.tolist(), toks.starts.tolist(), toks.lens.tolist()
        ):
            tok = buf[s : s + n].decode("utf-8", "replace")
            existing = known.get(h)
            if existing is not None and existing != tok:
                raise ValueError(f"hash64 collision: {existing!r} vs {tok!r}")
            known[h] = tok
        return toks

    def from_text(self, data, column: str = "word") -> Query:
        """Tokenize raw text into a one-STRING-column table using the
        native tokenizer (reference WordCount ingest; tokenization
        happens in generated vertex code there, at the ingest edge
        here).  ``data`` is a filesystem path, a list of paths (read
        with background prefetch, the async channel-reader path), a
        str, or bytes."""
        from dryad_tpu.runtime import bindings as RB

        many = isinstance(data, (list, tuple))
        on_disk = many or (isinstance(data, str) and os.path.exists(data))
        if on_disk:
            size = sum(os.path.getsize(d) for d in (data if many else [data]))
        else:
            data = data.encode("utf-8") if isinstance(data, str) else bytes(data)
            size = len(data)
        # bind time, before any collect(): no parent span and no qid
        with self.tracer.span(
            "tokenize", cat="ingest", account=True, bytes=size
        ) as span:
            if many:
                # Multi-file ingest: the native prefetch channel reads
                # file i+1 while file i tokenizes (reference async
                # channel buffer readers, channelbuffernativereader.cpp).
                with RB.PrefetchChannel(list(data), depth=4, threads=2) as ch:
                    parts = [self._tokenize_buf(fbuf) for fbuf in ch]
            else:
                if on_disk:
                    with open(data, "rb") as fh:
                        data = fh.read()
                parts = [self._tokenize_buf(data)]
            if len(parts) == 1:
                h0, h1, r0, r1 = parts[0][:4]
            elif parts:
                h0, h1, r0, r1 = (
                    np.concatenate([p[i] for p in parts]) for i in range(4)
                )
            else:
                h0 = h1 = r0 = r1 = np.zeros(0, np.uint32)
            # distinct: the words the tokenizer's table found, a buffer;
            # runs: the cuts of a buffer hashed side by side (1 = the
            # caller's thread alone)
            span.add(
                rows=len(h0),
                bytes_out=h0.nbytes + h1.nbytes + r0.nbytes + r1.nbytes,
                distinct=sum(len(p.hashes) for p in parts),
                runs=max((p.runs for p in parts), default=1),
            )
        with self.tracer.span(
            "vocab", cat="ingest", account=True, rows=len(h0)
        ) as span:
            vocab = _word_vocab([p.hashes for p in parts])
            span.add(bytes_out=vocab.nbytes)
        schema = Schema([(column, ColumnType.STRING)])
        node = Node(
            "input", [], schema, PartitionInfo.roundrobin(),
            source="host_physical", str_vocab={column: vocab},
        )
        # the columns are the context's own copy of the text: they go
        # when no query can reach the node any more (a derived query
        # holds it through ``inputs``)
        self.inputs.bind(node, PhysicalTable({
            f"{column}#h0": h0, f"{column}#h1": h1,
            f"{column}#r0": r0, f"{column}#r1": r1,
        }), owned=True)
        return Query(self, node)

    def from_stream(self, chunks, schema: Optional[Schema] = None) -> Query:
        """Out-of-core ingest: an iterable of host tables processed as
        bounded chunks by the streaming executor (``exec.outofcore``).

        The reference streams unbounded channel data through fixed
        buffers (``channelinterface.h:212`` RChannelReader) so a vertex
        handles data far larger than memory; here the morsel unit is a
        host table chunk and every device job stays within the
        ``(P x cap)`` layout.  Queries over a stream input support the
        row-local operators per chunk plus group_by/aggregate/distinct
        (partial combine), order_by (external distribution sort),
        join (Grace bucketing), take and concat."""
        from dryad_tpu.exec.outofcore import ChunkSource

        it = iter(chunks)
        if schema is None:
            first = next(it, None)
            if first is None:
                raise ValueError("an empty stream needs an explicit schema")
            first = {k: np.asarray(v) for k, v in first.items()}
            schema = _infer_schema(first)
            it = itertools.chain([first], it)
        node = Node(
            "input", [], schema, PartitionInfo.roundrobin(), source="stream"
        )
        self.inputs.bind(node, ChunkStream(ChunkSource(it, schema)))
        return Query(self, node)

    def text_stream(
        self, paths, chunk_bytes: int = 1 << 25, column: str = "word"
    ) -> Query:
        """Chunked tokenizing text ingest for corpora larger than
        memory (streaming ``from_text``; reference HDFS block readers,
        ``channelbufferhdfs.cpp``).  Chunks split at whitespace
        boundaries so no token straddles two chunks.  Chunks are
        emitted as PHYSICAL token columns straight off the native
        tokenizer (hash + prefix-rank words), so the streaming hot
        path never materializes per-token Python strings."""
        if isinstance(paths, str):
            paths = [paths]
        schema = Schema([(column, ColumnType.STRING)])

        def phys(buf):
            toks = self._tokenize_buf(buf)
            return {
                f"{column}#h0": toks.h0, f"{column}#h1": toks.h1,
                f"{column}#r0": toks.r0, f"{column}#r1": toks.r1,
                "#vocab": {column: _word_vocab([toks.hashes])},
            }

        def gen():
            for p in paths:
                with open(p, "rb") as fh:
                    carry = b""
                    while True:
                        buf = fh.read(chunk_bytes)
                        if not buf:
                            if carry.strip():
                                yield phys(carry)
                            break
                        buf = carry + buf
                        # cut at the last whitespace so tokens stay whole
                        cut = max(buf.rfind(b" "), buf.rfind(b"\n"),
                                  buf.rfind(b"\t"), buf.rfind(b"\r"))
                        if cut <= 0:
                            carry = buf
                            continue
                        chunk, carry = buf[:cut], buf[cut:]
                        if chunk.strip():
                            yield phys(chunk)

        return self.from_stream(gen(), schema)

    def store_stream(self, path: str, parts_per_chunk: int = 1) -> Query:
        """Open a store as a chunk stream, one (or N) partition files
        per chunk — the out-of-core counterpart of ``from_store``."""
        from dryad_tpu.columnar.batch import decode_physical_table
        from dryad_tpu.columnar.io import (
            _part_name,
            load_store_meta,
            read_partition_file,
        )

        manifest, schema, dict_map = load_store_meta(path)
        self.dictionary._map.update(dict_map)

        def flush(batch):
            if len(batch) == 1:
                return batch[0]
            return {
                c: np.concatenate([b[c] for b in batch])
                for c in batch[0]
            }

        def gen():
            batch: list = []
            for i in range(manifest["partitions"]):
                phys = read_partition_file(
                    os.path.join(path, _part_name(i))
                )
                batch.append(
                    decode_physical_table(
                        schema, slice(None), phys, self.dictionary
                    )
                )
                if len(batch) >= parts_per_chunk:
                    yield flush(batch)
                    batch = []
            if batch:
                yield flush(batch)

        return self.from_stream(gen(), schema)

    def from_store(self, path: str) -> Query:
        """Open a store by path or URI (reference FromStore/GetTable;
        scheme registry ``columnar/uri.py`` — partfile://, file://,
        mem://, http://)."""
        from dryad_tpu.columnar.uri import read_store_uri

        schema, parts, dictionary = read_store_uri(path)
        self.dictionary = self.dictionary.merge(dictionary)
        # the store dictionary bounds every STRING column's vocabulary
        # (a superset per column, still a sound auto-dense gate)
        store_hashes = np.sort(
            np.fromiter(dictionary._map.keys(), dtype=np.uint64)
        )
        node = Node(
            "input", [], schema, PartitionInfo.roundrobin(), source="store",
            str_vocab={
                f.name: store_hashes
                for f in schema.fields if f.ctype is ColumnType.STRING
            },
        )
        self.inputs.bind(node, StoreParts(parts, schema))
        return Query(self, node)

    def _from_device_batch(
        self, batch: ColumnBatch, schema: Schema, partition=None
    ) -> Query:
        """``partition``: the producing node's PartitionInfo — the batch
        physically has that layout, so propagating it lets downstream
        consumers elide exchanges the producer already paid for."""
        node = Node(
            "input", [], schema, partition or PartitionInfo(),
            source="device",
        )
        self.inputs.bind(node, DeviceTable(batch))
        return Query(self, node)

    def release(self, query: Query) -> None:
        """Drop a cached device-resident table (the pin created by
        ``Query.cache()``); later use of the query raises the
        stale-binding error rather than recomputing silently.  Only
        device-bound input queries qualify — releasing a source table
        or a derived query is a caller bug, surfaced loudly."""
        binding = self.inputs.get(query.node.id)
        cached_marker = query.node.params.get("cached")  # local_debug pin
        if (
            query.node.kind != "input"
            or binding is None
            or not (isinstance(binding, DeviceTable) or cached_marker)
        ):
            raise ValueError(
                "release() takes the query returned by cache(); got a "
                f"{query.node.kind!r} node bound as "
                f"{binding.kind if binding else None!r}"
            )
        self.inputs.forget(query.node.id)

    # -- serving-tier surface ----------------------------------------------
    def is_stream_query(self, query: Query) -> bool:
        """True when the plan draws on a chunk-stream binding — such
        plans route through the StreamExecutor and are not valid for
        the async dispatch path (or the serving result cache)."""
        from dryad_tpu.exec.outofcore import has_stream_input

        return has_stream_input(self, query.node)

    def query_fingerprint(self, query: Query):
        """Stable identity of (plan structure, output position, ingest
        content) — the serving tier's result-cache key, or None when
        the query is uncacheable (local_debug, stream inputs, or any
        device-resident binding whose content can't be fingerprinted
        without a host transfer).

        Plan structure comes from the executor's ``graph_key`` (the
        compile-cache machinery), so the key inherits its reference
        semantics: closure-bearing plans (select/where lambdas) match
        only when re-run from the same Query object — prepared
        statements — while value-hashable params match across rebuilt
        queries.  The output is identified by its stage's POSITION in
        the lowered graph (stage ids are fresh per lowering and would
        defeat every repeat).  Ingest content is the per-binding SHA-1
        fingerprint (``Inputs.fingerprint``) of every plan input, in plan
        creation order."""
        if self.local_debug or self.is_stream_query(query):
            return None
        graph = lower(
            [query.node], self.config, self.dictionary,
            P=num_partitions(self.mesh) if self.mesh is not None else None,
        )
        fps = []
        for nid in sorted(graph.inputs):
            fp = self.inputs.fingerprint(nid)
            if fp is None:
                return None
            fps.append(fp)
        sid, oidx = graph.outputs[query.node.id]
        pos = {s.id: i for i, s in enumerate(graph.stages)}[sid]
        return (self.executor.graph_key(graph), (pos, oidx), tuple(fps))

    def query_input_bytes(self, query: Query) -> int:
        """Host bytes bound under the plan — the admission-control cost
        of a query (device-resident and stream bindings count zero: no
        host copy is admitted on their behalf)."""
        total = 0
        seen = set()
        stack = [query.node]
        while stack:
            node = stack.pop()
            if node.id in seen:
                continue
            seen.add(node.id)
            stack.extend(node.inputs)
            binding = self.inputs.get(node.id)
            if binding is not None:
                total += binding.host_bytes()
        return total

    def _execute_roots(self, queries, defer_miss: bool = False):
        """Lower ``queries`` together as ONE graph (a stage several of
        them share is lowered, traced and run once), bind its inputs
        and run it: the batch of each query, in the order given, and
        the executor's ``DeferredFinish`` (None unless ``defer_miss``).
        The one place a plan becomes device work: ``collect`` of one
        query or of many, the asynchronous forms and ``cache`` all
        come through here."""
        with self.tracer.span("lower", cat="plan") as span:
            graph = lower(
                [q.node for q in queries], self.config, self.dictionary,
                P=num_partitions(self.mesh) if self.mesh is not None else None,
            )
            bound = [f for n in graph.inputs.values() for f in n.schema.fields]
            span.add(
                stages=len(graph.stages), roots=len(queries),
                # the logical types of the bound columns: which of them
                # the device holds as something else (DECIMAL, DATE)
                types=",".join(f"{f.name}:{f.ctype.value}" for f in bound),
                decimal_cols=sum(isinstance(f.ctype, DecimalType) for f in bound),
                date_cols=sum(f.ctype is ColumnType.DATE for f in bound),
            )
        bindings = {
            nid: self.inputs.device_batch(n) for nid, n in graph.inputs.items()
        }
        binding_fps = None
        if self.config.checkpoint_dir:
            binding_fps = {
                nid: self.inputs.fingerprint(nid) for nid in graph.inputs
            }
        results = self.executor.execute(
            graph, bindings, binding_fps, defer_miss=defer_miss
        )
        deferred = None
        if defer_miss:
            results, deferred = results
        return [results[graph.outputs[q.node.id]] for q in queries], deferred

    def _execute_device(self, query: Query, defer_miss: bool = False):
        (batch,), deferred = self._execute_roots([query], defer_miss)
        return (batch, deferred) if defer_miss else batch

    def _trace_ctx(self):
        """The active trace context, or a fresh mint for a non-serve
        job (serve minted one at admission and it is already active).
        None — a true no-op under ``tracectx.activate`` — when
        ``config.query_trace`` is off."""
        ctx = tracectx.current()
        if ctx is None and getattr(self.config, "query_trace", True):
            ctx = tracectx.mint()
        return ctx

    def run_to_host(self, query: Query) -> Dict[str, np.ndarray]:
        """``Query.collect()``: the job of one output."""
        return self.collect_many([query])[0]

    def collect_many(self, queries) -> Tuple[Dict[str, np.ndarray], ...]:
        """Run ``queries`` (of this context) as ONE job and return
        their host tables, in the order given (the reference's
        ``SubmitAndWait(q1, q2, ...)``).  The roots lower into
        one graph, so what they share (an ``apply`` + ``fork`` that
        feeds three pipelines) is traced and run once, and ``plan_fuse``
        may fold all of it into one dispatched program.  One ``collect``
        span (``outputs``), one ``lower`` (``roots``), one ``drain``;
        then a fetch, a decode and a drop an output (``output``), the
        dictionary-miss check riding the first fetch, so a miss in ANY
        output raises before any table is handed out.  An answer's
        device arrays go as soon as it is on the host.
        ``Query.collect()`` is this with one query.

        A context in ``local_debug`` mode, or a query that draws on a
        ``from_stream`` input, has no such graph: those run one by one,
        each by the rule a single ``collect()`` follows (the NumPy
        interpreter; the ``StreamExecutor``; a stream input under
        ``local_debug`` raises), inside the one ``collect`` span."""
        queries = list(queries)
        if not queries:
            raise ValueError("collect_many needs at least one query")
        for q in queries:
            if q.ctx is not self:
                raise ValueError(
                    "collect_many takes queries of ONE context; "
                    f"query over node {q.node.id} belongs to another"
                )
        # every span / exchange_round / dispatch_gap below carries the
        # minted (or inherited) context's qid
        with tracectx.activate(self._trace_ctx()):
            with self.tracer.span("collect", cat="job", outputs=len(queries)):
                return tuple(self._run_to_host(queries))

    def _run_to_host(self, queries) -> list:
        from dryad_tpu.exec.outofcore import has_stream_input

        if self.local_debug or any(
            has_stream_input(self, q.node) for q in queries
        ):
            return [self._run_one(q) for q in queries]
        return self._run_device_job(queries)

    def _run_one(self, query: Query) -> Dict[str, np.ndarray]:
        """One query by the rule of a single ``collect()``: a chunk
        stream goes through the ``StreamExecutor``, a ``local_debug``
        context through the NumPy interpreter, anything else is a
        device job of one output."""
        from dryad_tpu.exec.outofcore import StreamExecutor, has_stream_input

        if has_stream_input(self, query.node):
            if self.local_debug:
                raise RuntimeError(
                    "from_stream inputs are not supported in local_debug "
                    "mode (the NumPy interpreter holds whole tables); "
                    "materialize the chunks and use from_arrays"
                )
            return StreamExecutor(self).run_to_host(query.node)
        if self.local_debug:
            from dryad_tpu.exec.localdebug import LocalDebugInterpreter

            return LocalDebugInterpreter(self).run_to_logical(query.node)
        return self._run_device_job([query])[0]

    def _run_device_job(self, queries) -> list:
        # The dict-miss counters ride the SAME device_get as the first
        # output (one device->host round-trip instead of two); the
        # deferred check still raises before any result reaches the
        # caller.
        batches, deferred = self._execute_roots(queries, defer_miss=True)
        self.inputs.release()
        tables = []
        for i, query in enumerate(queries):
            # which answer it is, on every span of its fetch
            with self.tracer.stamped(output=i):
                tables.append(self._fetch_table(
                    query, batches[i], deferred, done=False
                ))
                # the answer's device arrays, and what jax cached on them
                with self.tracer.span("drop", cat="readback"):
                    batches[i] = deferred = None
        self.inputs.release(done=True)
        return tables

    def _fetch_table(self, query: Query, batch, deferred=None, done=True):
        """A result batch as the user's logical host table: the fetch
        (``deferred``'s miss counters riding it; the byte accounting is
        the fetch's own) and the decode of the valid rows, which are a
        slice a shard where the fetch measured the batch and found no
        hole, and the mask's otherwise.  ``done``: this call is a fetch
        of its own, so it lets go of what the job ingested before and
        after (``Inputs.release``; ``_run_device_job`` says no:
        it does so itself, once before its first output's fetch and
        once after its last's drop)."""
        metrics = self.executor.metrics if self.executor is not None else None
        if done:
            self.inputs.release()
        if deferred is not None:
            valid, host_cols, rows = _fetch_with_miss(
                batch, deferred, self.tracer, metrics
            )
        else:
            valid, host_cols, _, rows = batch.fetch_host(
                tracer=self.tracer, metrics=metrics
            )
        # valid rows a partition of the answer (for a range partition,
        # how evenly the elected splitters cut the table): as counted
        # on the device, or off the mask the host holds
        if rows is not None:
            shard_rows = list(rows.counts)
        else:
            shard_rows = [
                int(np.count_nonzero(part))
                for part in np.array_split(valid, num_partitions(self.mesh))
            ]
        with self.tracer.span(
            "decode", cat="decode", account=True, rows=sum(shard_rows),
            capacity=batch.capacity, fetched=len(valid),
            shards=len(shard_rows),
            shard_rows_max=max(shard_rows), shard_rows_min=min(shard_rows),
        ) as span:
            packed = rows is not None and rows.packed
            table = batch.to_numpy(
                query.schema, self.dictionary,
                _host=(rows.slices() if packed else valid, host_cols),
                tracer=self.tracer,
            )
            if self._codecs:
                from dryad_tpu.columnar.codecs import collapse_table

                table = collapse_table(table, self._codecs)
            span.add(bytes_out=_nbytes(table))
        # The fetched host copies go here (and in ``_run_device_job`` the
        # device arrays that may own them): a table's worth of memory
        # to unmap (15 ms for 302 MB, 45 ms for 881 MB, 56 ms for the
        # four shards' 718 MB; PERF.md section 6, PR 34), which fell at
        # the function's return, under no span.  Where the runtime still
        # holds a copy (it lets go at the thread's next call into jax
        # or Python collection) the unmap comes later: in ``release`` in
        # a job that ingested, in the next job otherwise, as ever.
        with self.tracer.span("drop", cat="readback"):
            del valid, host_cols
        if done:
            self.inputs.release(done=True)
        return table

    def run_to_host_async(self, query: Query):
        """Dispatch the device job NOW; return a zero-arg ``fetch``
        closure that blocks on the device->host transfer.  The
        streaming pipeline's dispatch/drain split: the driver launches
        bucket k+1's program while bucket k's results transfer
        (``exec.outofcore`` phase 2).  Not valid for stream-input
        plans (those route through the StreamExecutor).
        :meth:`run_many_to_host_async` of one query."""
        return self.run_many_to_host_async([query])[0]

    def run_many_to_host_async(self, queries):
        """:meth:`collect_many`'s asynchronous form: dispatch SEVERAL
        queries as ONE lowered program NOW and hand back a fetch each
        (cross-chunk plan fusion, ``config.chunk_fuse``): the roots
        lower together (:meth:`_execute_roots`, the lowering, binding
        and dispatch every ``collect`` goes through), their stage
        chains land consecutively in the graph, and ``plan_fuse`` folds
        them into a single dispatched region — K dispatch round trips
        collapse into one.  Each query stays its own computation inside
        the region (its reduction order is untouched), so results are
        byte-identical to K separate dispatches.

        Returns one zero-arg ``fetch`` closure per query, resolving
        that query's output from the shared execution through
        :meth:`_fetch_table`, as ``collect_many`` does.  The deferred
        dict-miss check rides the FIRST fetch's transfer (a miss
        anywhere in the group raises there, before any result of the
        group is committed)."""
        queries = list(queries)
        tctx = self._trace_ctx()
        with tracectx.activate(tctx):
            batches, deferred = self._execute_roots(queries, defer_miss=True)

        def make_fetch(output, query, batch):
            def fetch() -> Dict[str, np.ndarray]:
                nonlocal deferred  # the first fetch to come resolves it
                # the closure carries its job's context: a fetch drained
                # on another thread (DispatchWindow collector, serve
                # driver) still stamps readback spans with the right qid
                with tracectx.activate(tctx), self.tracer.stamped(output=output):
                    table = self._fetch_table(query, batch, deferred)
                    deferred = None
                    return table

            return fetch

        return [
            make_fetch(i, q, batch)
            for i, (q, batch) in enumerate(zip(queries, batches))
        ]

    def submit(self, query: Query) -> JobHandle:
        return JobHandle(self.run_to_host(query))

    def to_store(self, query: Query, path: str) -> JobHandle:
        """Execute and persist (reference ToStore + SubmitAndWait)."""
        with tracectx.activate(self._trace_ctx()):
            return self._to_store(query, path)

    def _to_store(self, query: Query, path: str) -> JobHandle:
        if not self.local_debug:
            from dryad_tpu.exec.outofcore import (
                StreamExecutor,
                has_stream_input,
            )

            if has_stream_input(self, query.node):
                rows = StreamExecutor(self).to_store(query.node, path)
                return JobHandle({"rows": np.asarray([rows])}, path)
        if self.local_debug:
            table = self.run_to_host(query)
            b = ColumnBatch.from_numpy(
                query.schema, table,
                capacity=len(next(iter(table.values()), [])),
                dictionary=self.dictionary,
            )
            parts = [
                {c: np.asarray(v) for c, v in b.data.items()}
            ]
            from dryad_tpu.columnar.uri import write_store_uri

            write_store_uri(
                path, parts, query.schema, self.dictionary,
                self.config.intermediate_compression,
            )
            return JobHandle(table, path)
        batch, deferred = self._execute_device(query, defer_miss=True)
        P = num_partitions(self.mesh)
        parts = []
        # overlapped d2h copies; miss counters ride the same transfer
        valid, host_cols, _ = _fetch_with_miss(
            batch, deferred, self.tracer, self.executor.metrics
        )
        cap = len(valid) // P  # slots a partition, as fetched
        for i in range(P):
            sl = slice(i * cap, (i + 1) * cap)
            m = valid[sl]
            parts.append({c: v[sl][m] for c, v in host_cols.items()})
        from dryad_tpu.columnar.uri import write_store_uri

        write_store_uri(
            path, parts, query.schema, self.dictionary,
            self.config.intermediate_compression,
        )
        return JobHandle(
            batch.to_numpy(
                query.schema, self.dictionary, _host=(valid, host_cols)
            ),
            path,
        )

    # -- do_while support ----------------------------------------------------
    def _lower_loop_stage(self, plan_fn, schema: Schema, example: ColumnBatch):
        """Lower a do_while body/cond subplan to ONE fused stage for the
        on-device loop path.  Raises ValueError when the subplan needs
        more than one stage (multi-consumer / join shapes) — the caller
        falls back to the driver loop."""
        q0 = self._from_device_batch(example, schema)
        out_q = plan_fn(q0)
        graph = lower([out_q.node], self.config, self.dictionary)
        if len(graph.stages) != 1:
            raise ValueError(
                f"subplan lowers to {len(graph.stages)} stages; device "
                f"loop needs exactly one"
            )
        stage = graph.stages[0]
        if stage.input_refs != [("plan_input", q0.node.id)] or len(
            stage.out_slots
        ) != 1:
            raise ValueError("subplan stage shape unsupported for device loop")
        return stage, out_q.schema

    def _run_subquery(self, plan_fn, schema: Schema, current: ColumnBatch, scalar: bool = False):
        # Build each body/cond plan ONCE per do_while and rebind the input
        # batch on later iterations — re-building would create fresh
        # closures every iteration and defeat the executor's structural
        # compile cache (one XLA compile per iteration).  Keyed by the
        # function OBJECT (strong ref), not id(): a freed function's id
        # can be reused and would serve the previous do_while's plan.
        cache_key = (plan_fn, tuple(schema.names))
        cached = getattr(self, "_subplans", None)
        if cached is None:
            cached = self._subplans = {}
        if cache_key not in cached:
            q0 = self._from_device_batch(current, schema)
            cached[cache_key] = (q0.node.id, plan_fn(q0))
        input_node_id, out_q = cached[cache_key]
        self.inputs.rebind(input_node_id, DeviceTable(current))
        if scalar:
            # The cond output is ROW-SHARDED (its one valid row lives on
            # one partition); in a multi-controller gang a plain host
            # fetch of a cross-process array raises, so gather the tiny
            # column through the collective path first.
            batch = self._execute_device(out_q)
            col = next(iter(batch.data.values()))
            valid = batch.valid
            import jax as _jax

            if _jax.process_count() > 1:
                from jax.experimental import multihost_utils as _mh

                col = _mh.process_allgather(col, tiled=True)
                valid = _mh.process_allgather(valid, tiled=True)
            vals = np.asarray(col)[np.asarray(valid)]
            return bool(vals[0]) if len(vals) else False
        return self._execute_device(out_q)
