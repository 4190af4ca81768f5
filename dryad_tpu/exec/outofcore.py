"""Out-of-core streaming execution — bounded-HBM morsel loop.

The reference streams unbounded byte streams through fixed-size
buffers with async read-ahead (``DryadVertex/VertexHost/system/channel/
channelinterface.h:212`` RChannelReader; ``channelbuffernativereader
.cpp``; ``channelbufferqueue.cpp``), so a vertex processes data far
larger than memory.  The TPU-native equivalent here is a **two-phase
partition-spill driver** over the existing engine:

- phase 1 (scatter): each ingest *chunk* runs the fused row-local
  prefix of the plan as one compiled device program, then is routed to
  range/hash buckets and spilled as ``.dpf`` pieces (the persisted
  file-channel analog, ``exec.spill``);
- phase 2 (gather): each bucket — sized to fit the ``(P x cap)``
  device layout — runs the wide operator (sort / group / join) as a
  normal engine job, and results stream out in bucket order.

Aggregations skip the spill when their aggs decompose: per-chunk
partials accumulate and periodically combine on device (the
machine->pod->overall aggregation tree of
``DrDynamicAggregateManager.h:117-168`` folded into a running
accumulator).  Oversized buckets re-split from *observed* volume —
the ``DrDynamicRangeDistributor.cpp:54-110`` consumer-resize semantics
applied at the spill boundary.
"""

from __future__ import annotations

import math
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from dryad_tpu.columnar.schema import ColumnType, Schema
from dryad_tpu.exec.combinetree import (
    DEGRADE_RATIO,
    KEY_RANGES,
    CombineTreePlanner,
    TreeCombiner,
    TreeShape,
    batch_bytes,
    neutral_snapshot,
)
from dryad_tpu.exec.partial import (
    MERGEABLE_AGGS,
    finalize_fn,
    merge_agg_spec,
    partial_plan,
)
from dryad_tpu.exec.failure import JobFailedError, StageFailedError
from dryad_tpu.exec.faults import InjectedFault
from dryad_tpu.exec.inputs import ChunkStream, HostTable, PhysicalTable
from dryad_tpu.exec.pipeline import DispatchWindow, prefetched
from dryad_tpu.exec.spill import SpillDir, SpillWriter
from dryad_tpu.obs import telemetry
from dryad_tpu.obs.metrics import KeyRangeHistogram, MetricsRegistry
from dryad_tpu.obs.span import Tracer
from dryad_tpu.plan.nodes import Node, walk
from dryad_tpu.utils.logging import get_logger

log = get_logger("dryad_tpu.stream")

# Node kinds applied chunk-locally in phase 1 (row-wise, stateless
# across chunks).  Partitioning hints are identity under streaming:
# every per-chunk/per-bucket engine job re-derives its own exchanges.
ROW_LOCAL = {"select", "where", "select_many"}
PARTITION_HINTS = {"hash_partition", "range_partition", "assume_partition"}

_MIX = np.uint64(0x9E3779B97F4A7C15)


class ChunkSource:
    """A stream ingest binding: an iterable of host tables.  The
    consumed flag lives HERE (not on the per-execution stream view) so
    a second collect() over the same query raises the explicit error
    instead of silently computing on a drained iterator."""

    def __init__(self, chunks, schema: Schema):
        self.chunks = chunks
        self.schema = schema
        self.state = {"consumed": False}


class _IngestScope:
    """Per-call-site chunk ingest state: a stable partition capacity
    (so every chunk compiles to the same shapes) and accumulated
    auto-dense metadata (string vocab / int ranges widen monotonically
    across chunks, so the dense code table saturates and the compile
    cache holds).

    With ``cache_plans`` (the pipelined driver) the scope also reuses
    the ingest Node itself: a chunk that introduces no new vocabulary,
    no wider int range, and fits the stable capacity REBINDS the
    previous chunk's input node to its arrays instead of building a
    fresh node — so downstream plan chains, lowering keys, and compiled
    programs repeat exactly (the cached-chunk-plan half of the
    pipeline; without it, a widened vocab baked into the coding tables
    forces a fresh XLA compile per chunk)."""

    def __init__(self, ctx, cache_plans: bool = False, slots: int = 1):
        self.ctx = ctx
        self.cap: Optional[int] = None
        # With cross-chunk fusion, K chunks are lowered into ONE
        # multi-root program — each needs its OWN input node (and
        # binding) alive at dispatch, so the reuse cache round-robins
        # over `slots` cached nodes instead of rebinding a single one.
        self.slots = max(1, int(slots))
        self._slot_counter = 0
        self.vocab: Dict[str, np.ndarray] = {}
        self.stats: Dict[str, Tuple[int, int]] = {}
        self.cache_plans = cache_plans
        # Runtime-operand coding tables (compile-once dictionary
        # coding): vocab widening within a pow2 palette tier keeps
        # every traced shape identical, so it must NOT bump the cached-
        # plan epoch — the cached input node is reused with its
        # str_vocab param refreshed in place (_maybe_reuse), the
        # lowering rebuilds the widened tables, and the executor's
        # operand pool scatters just the delta.
        self._runtime_tables = bool(
            getattr(ctx.config, "stringcode_runtime_tables", True)
        )
        # bumps whenever vocab/stats/capacity widen beyond what cached
        # plans can absorb: cached input nodes and the chains built on
        # them are valid while it holds still
        self.version = 0
        # (cap, binding kind) -> (version, node) reusable ingest input
        self._cached_input: Dict[Tuple, Tuple[int, Node]] = {}
        # (input node id, pending/extra node ids) -> cloned chain root
        self.chain_cache: Dict[Tuple, Node] = {}

    def _fit_cap(self, n: int, P: int) -> int:
        if self.cap is None or n > self.cap * P:
            self.cap = max(1, math.ceil(n / P / 8) * 8)
            self.version += 1
        return self.cap

    def _widen_vocab(self, col: str, v: np.ndarray) -> np.ndarray:
        from dryad_tpu.ops.stringcode import palette_domain

        prev = self.vocab.get(col)
        new = v if prev is None else np.union1d(prev, v)
        if prev is None:
            self.version += 1
        elif len(new) != len(prev):
            if not self._runtime_tables or palette_domain(
                len(new)
            ) != palette_domain(len(prev)):
                # legacy baked tables invalidate on ANY widen; runtime
                # tables only on a palette-tier crossing
                self.version += 1
        self.vocab[col] = new
        return new

    def _account(self, table: Dict[str, np.ndarray], n: int, P: int) -> None:
        """Ingest-side byte/row accounting: H2D-bound bytes and the
        layout-vs-valid rows behind the padding-waste ratio."""
        ex = getattr(self.ctx, "executor", None)
        if ex is None or self.cap is None:
            return
        ex.metrics.add(
            "h2d_bytes",
            sum(
                np.asarray(v).nbytes for c, v in table.items()
                if c != "#vocab"
            ),
        )
        ex.metrics.add("rows_in", n)
        ex.metrics.add("valid_rows", n)
        ex.metrics.add("layout_rows", self.cap * P)

    def ingest(self, table: Dict[str, np.ndarray], schema: Schema):
        ctx = self.ctx
        from dryad_tpu.parallel.mesh import num_partitions

        P = num_partitions(ctx.mesh) if ctx.mesh is not None else 8
        if is_physical_chunk(table, schema):
            return self._maybe_reuse(self._ingest_physical(table, schema, P))
        n = len(next(iter(table.values()))) if table else 0
        self._fit_cap(n, P)
        self._account(table, n, P)
        q = ctx.from_arrays(table, schema=schema, partition_capacity=self.cap)
        node = q.node
        # Widen auto-dense metadata to the stream scope.  The widened
        # dicts REPLACE the node's params — never written into the
        # original dicts, which clones share by reference (in-place
        # widening would leak one chunk's vocabulary into every node
        # holding the same params dict).
        sv = node.params.get("str_vocab") or {}
        if sv:
            node.params["str_vocab"] = {
                col: self._widen_vocab(col, vocab)
                for col, vocab in sv.items()
            }
        cs = node.params.get("col_stats") or {}
        if cs:
            merged = {}
            for col, (mn, mx) in cs.items():
                if col in self.stats:
                    pmn, pmx = self.stats[col]
                    nmn, nmx = min(mn, pmn), max(mx, pmx)
                else:
                    nmn, nmx = mn, mx
                if self.stats.get(col) != (nmn, nmx):
                    self.version += 1
                self.stats[col] = (nmn, nmx)
                merged[col] = (nmn, nmx)
            node.params["col_stats"] = merged
        return self._maybe_reuse(q)

    def _maybe_reuse(self, q):
        """Swap the freshly built input node for the cached one when
        this chunk's metadata is covered by it (vocab/stats widen
        monotonically, so an unchanged version proves coverage)."""
        if not self.cache_plans:
            return q
        from dryad_tpu.api.query import Query

        ctx = self.ctx
        node = q.node
        binding = ctx.inputs.get(node.id)
        if binding is None:
            return q
        slot = self._slot_counter % self.slots
        self._slot_counter += 1
        key = (self.cap, binding.kind, slot)
        cached = self._cached_input.get(key)
        if cached is not None and cached[0] == self.version:
            cnode = cached[1]
            # adopt the fresh chunk's binding under the cached node id
            # (the move drops the id's stale fingerprint and device
            # entry: checkpoint identity must follow the data)
            ctx.inputs.move(node.id, cnode.id)
            # refresh the cached node's vocabulary metadata in place: a
            # within-tier widen reuses the node (and every chain/compiled
            # program built on it) but the NEXT lowering must code
            # against the full accumulated vocab — a stale str_vocab
            # would build tables missing this chunk's new words and fail
            # them loudly as dictionary misses.
            sv = cnode.params.get("str_vocab")
            if sv:
                cnode.params["str_vocab"] = {
                    c: self.vocab.get(c, vv) for c, vv in sv.items()
                }
            return Query(ctx, cnode)
        self._cached_input[key] = (self.version, node)
        return q

    def _ingest_physical(self, table: Dict[str, np.ndarray], schema, P):
        """Pre-encoded chunk (physical columns, e.g. straight off the
        native tokenizer): bind as host_physical — no per-token Python
        string work on the streaming hot path (review r5; the
        reference's vertices likewise consume tokenized channel bytes
        directly, ``channelbufferhdfs.cpp``)."""
        from dryad_tpu.api.query import Query
        from dryad_tpu.plan.nodes import PartitionInfo

        ctx = self.ctx
        vocab = table.pop("#vocab", None) or {}
        for col, v in vocab.items():
            self._widen_vocab(col, v)
        n = len(next(iter(table.values()))) if table else 0
        self._fit_cap(n, P)
        self._account(table, n, P)
        node = Node(
            "input", [], schema, PartitionInfo.roundrobin(),
            source="host_physical",
            str_vocab={c: v.copy() for c, v in self.vocab.items()},
        )
        ctx.inputs.bind(node, PhysicalTable(table, self.cap))
        return Query(ctx, node)


class _AsyncDispatcher:
    """Driver-side async chunk dispatcher: marries the
    :class:`~dryad_tpu.exec.pipeline.DispatchWindow` with cross-chunk
    plan fusion.

    Queries queue up to ``fuse`` deep and dispatch in submit order —
    a fused batch lowers as ONE multi-root program
    (``run_many_to_host_async``, the asynchronous form of the
    context's ``collect_many``: one lowering, binding and dispatch
    under both, ``DryadContext._execute_roots``), collapsing K
    dispatch round trips into one — and each chunk's readback fetch
    (``DryadContext._fetch_table``, every ``collect``'s) hands off to the
    window's collector thread.  Outcomes are delivered strictly in
    submit order, so the caller's commit body (spill / accumulate /
    combine) observes the exact serial sequence and results stay
    byte-identical with the ``dispatch_depth=1`` loop.

    A fetch error surfacing at the drain site re-executes that chunk
    serially via the caller's ``retry`` callback — the retried result
    re-enters the stream at the failed chunk's commit position.
    Terminal failures (:class:`JobFailedError` — the executor already
    burned its attempt budget) and non-stage errors propagate; the
    caller's ``finally`` closes the window, which never deadlocks.
    """

    def __init__(self, ctx, depth, fuse, events=None, name="chunks",
                 retry=None):
        self.ctx = ctx
        self.fuse = max(1, int(fuse))
        self.retry = retry
        # a fused batch enters the window whole, so the window must
        # admit at least `fuse` in-flight fetches
        self.win = DispatchWindow(
            max(1, int(depth), self.fuse), events=events, name=name,
        )
        self._queued: List[Tuple[Any, Any]] = []  # awaiting fused dispatch

    def submit(self, tag, query) -> None:
        self._queued.append((tag, query))
        if len(self._queued) >= self.fuse:
            self._dispatch()

    def _dispatch(self) -> None:
        queued, self._queued = self._queued, []
        if not queued:
            return
        if len(queued) == 1:
            fetches = [self.ctx.run_to_host_async(queued[0][1])]
        else:
            fetches = self.ctx.run_many_to_host_async(
                [q for _tag, q in queued]
            )
        for (tag, _q), fetch in zip(queued, fetches):
            self.win.submit(tag, fetch)

    def ready(self):
        """Completed (tag, table) pairs, non-blocking — the driver's
        between-dispatches commit opportunity."""
        return self._deliver(self.win.ready())

    def drain(self):
        """Flush the fused queue and deliver every remaining outcome
        in submit order (blocking)."""
        self._dispatch()
        return self._deliver(self.win.drain())

    def _deliver(self, outcomes):
        for tag, value, error in outcomes:
            if error is not None:
                value = self._retry_one(tag, error)
            yield tag, value

    def _retry_one(self, tag, error):
        transient = isinstance(
            error, (StageFailedError, InjectedFault)
        ) and not isinstance(error, JobFailedError)
        if self.retry is None or not transient:
            raise error
        self.win.note_retry()
        log.warning(
            "async chunk fetch failed (%s: %s); retrying serially at "
            "the drain site", type(error).__name__, error,
        )
        return self.retry(tag)

    def close(self) -> None:
        self.win.close()


class _Stream:
    """A lazily-realized chunk stream: base chunks plus a pending
    chain of row-local plan nodes applied per chunk on device.

    Derived streams (``with_pending``) SHARE the consumption state with
    their base: two branches over one chunk iterator must raise the
    explicit already-consumed error, not silently split the data."""

    def __init__(
        self, base_schema: Schema, chunks: Iterator, pending=(),
        _state: Optional[dict] = None,
    ):
        self.base_schema = base_schema
        self.chunks = chunks
        self.pending: List[Node] = list(pending)
        self._state = _state if _state is not None else {"consumed": False}

    @property
    def consumed(self) -> bool:
        return self._state["consumed"]

    @consumed.setter
    def consumed(self, v: bool) -> None:
        self._state["consumed"] = v

    @property
    def schema(self) -> Schema:
        return self.pending[-1].schema if self.pending else self.base_schema

    def with_pending(self, node: Node) -> "_Stream":
        return _Stream(
            self.base_schema, self.chunks, self.pending + [node],
            _state=self._state,
        )


class StreamNotSupported(NotImplementedError):
    pass


def has_stream_input(ctx, root: Node) -> bool:
    if not ctx.inputs.any_stream:
        return False  # context never created a stream binding
    return bool(stream_reaching_ids(ctx, root))


def stream_reaching_ids(ctx, root: Node) -> set:
    """Ids of nodes whose subtree contains a stream binding — computed
    in ONE topological walk (consulted per node during evaluation)."""
    ids: set = set()
    for n in walk([root]):
        if isinstance(ctx.inputs.get(n.id), ChunkStream) or any(
            i.id in ids for i in n.inputs
        ):
            ids.add(n.id)
    return ids


def is_physical_chunk(table, schema: Schema) -> bool:
    """Chunks may arrive pre-encoded as physical columns (``name#h0``
    etc., straight off the native tokenizer) instead of logical host
    arrays; ``#vocab`` optionally carries the chunk's string vocab."""
    cols = set(table) - {"#vocab"}
    return cols != set(schema.names) and any("#" in c for c in cols)


def _chunk_rows(table) -> int:
    for c, v in table.items():
        if c != "#vocab":
            return len(v)
    return 0


class _DeviceCombiner:
    """Accumulator of device-resident partial batches — the
    ``DrDynamicAggregateManager.h:117-168`` machine->pod->overall
    aggregation tree kept entirely in HBM.

    Partials pile up untouched until their combined LAYOUT rows (sum of
    batch capacities — an upper bound on actual rows known without any
    device readback, so pushes never block the dispatch loop) exceed
    ``combine_rows`` or the fan-in cap; then ONE N-ary concat+merge job
    folds them to a single batch.  Concat is one plan node whatever the
    arity, so a flush compiles one program per distinct fan-in — and a
    steady stream flushes at a stable fan-in, reusing it.  This matches
    the serial driver's combine cadence (few, wide merges — not a
    per-chunk tree) while skipping its per-chunk D2H and host
    re-ingest.

    Merging on device only pays while merges actually REDUCE (the
    "merge where it reduces" scheduling of PAPERS.md "Chasing
    Similarity"): ``push`` returns False when a flush kept >= 3/4 of
    its inputs' combined layout — high-cardinality keys, whose merged
    batch would re-enter the accumulator near the threshold and force
    a shape-churning flush every chunk.  The caller then ``drain()``s
    and degrades to host-side threshold accumulation."""

    MAX_FANIN = 64  # bounds single-program arity (trace/compile cost)

    def __init__(self, merge_many, combine_rows: int, emit, split=None):
        self._merge_many = merge_many
        self._combine_rows = combine_rows
        self._emit = emit
        # optional (in_bytes, out_bytes) -> (ici, dcn) estimator
        # (combinetree.TreeShape.exchange_split): every flat flush pays
        # a FULL hash exchange, and tagging its collective byte split on
        # the event puts tree-on and tree-off runs on one scale
        self._split = split
        self._pending: List[Any] = []
        self.combines = 0

    def _cap(self) -> int:
        return sum(b.capacity for b in self._pending)

    def push(self, batch) -> bool:
        """Insert one partial; False = the flush this push triggered
        did not reduce (caller should ``drain()`` and change policy)."""
        self._pending.append(batch)
        if len(self._pending) < 2 or (
            self._cap() <= self._combine_rows
            and len(self._pending) < self.MAX_FANIN
        ):
            return True
        in_cap = self._cap()
        fan = len(self._pending)
        in_bytes = sum(batch_bytes(b) for b in self._pending)
        merged = self._merge_many(self._pending)
        self.combines += 1
        self._pending = [merged]
        ici, dcn = (
            self._split(in_bytes, batch_bytes(merged))
            if self._split else (0, 0)
        )
        self._emit("stream_combine", cap_rows=merged.capacity,
                   device=True, fan_in=fan, level=0,
                   ici_bytes=ici, dcn_bytes=dcn)
        return merged.capacity < 0.75 * in_cap

    def drain(self) -> List[Any]:
        """All held batches; the combiner is empty afterwards."""
        out = self._pending
        self._pending = []
        return out

    def fold(self):
        """Merge everything left into one batch; None when nothing was
        pushed."""
        if not self._pending:
            return None
        if len(self._pending) == 1:
            return self._pending.pop()
        merged = self._merge_many(self._pending)
        self.combines += 1
        self._pending = []
        return merged


class StreamExecutor:
    """Drives a plan whose input is a chunk stream; every device job it
    launches is bounded by the chunk/bucket budgets."""

    def __init__(self, ctx):
        self.ctx = ctx
        cfg = ctx.config
        self.bucket_rows = int(getattr(cfg, "stream_bucket_rows", 1 << 21))
        # The staged exchange (plan.xchgplan, config.exchange_window)
        # caps the per-dispatch redistribution footprint at
        # O(window * B) instead of the flat path's O(P * B); spend the
        # reclaimed HBM on bigger buckets — fewer device jobs, fewer
        # spill round-trips — scaling by the P/window buffer shrink,
        # clamped to 4x so ingest chunking stays responsive.
        # (-1 = auto policy resolves per-compilation in the executor;
        # no static bucket scaling can be assumed here)
        window = int(getattr(cfg, "exchange_window", 0))
        if window > 0:
            P = self._P()
            if P > window:
                self.bucket_rows *= min(4, max(1, P // window))
        self.combine_rows = int(getattr(cfg, "stream_combine_rows", 1 << 20))
        self.num_buckets = int(getattr(cfg, "stream_buckets", 32))
        # chunk pipeline: ingest / compute / readback-spill overlap with
        # this many chunks in flight; 1 = the serial legacy driver
        self.pipeline_depth = max(
            1, int(getattr(cfg, "stream_pipeline_depth", 1))
        )
        # async device-paced dispatch: how many chunk dispatches stay
        # in flight (readbacks drained by the DispatchWindow collector
        # thread); 1 = today's serial driver, the differential
        # baseline; -1 = adaptive — measured HBM headroom (the
        # context's telemetry HeadroomProvider) picks the tier, and
        # the collector's submit-order drain keeps ANY resolved depth
        # byte-identical to serial
        self.dispatch_depth = max(1, telemetry.resolve_depth(
            int(getattr(cfg, "dispatch_depth", 1)),
            getattr(ctx, "headroom", None),
        ))
        # cross-chunk fusion: K chunk partial-plans lowered as one
        # multi-root program, collapsing K dispatch RTTs into one
        self.chunk_fuse = max(1, int(getattr(cfg, "chunk_fuse", 1)))
        self.max_split_depth = 3
        self.events = ctx.executor.events if ctx.executor else None
        # runtime plan rewriter (rewrite.controller): polled at chunk
        # boundaries for hot-bucket splits and combine pins; None when
        # diagnosis/rewrite is off
        self.rewriter = getattr(ctx, "rewriter", None)
        # driver-loop spans (cat=chunk structural, engine jobs land on
        # cat=execute inside) + the shared counter registry
        self.tracer = Tracer(self.events)
        self.metrics = (
            ctx.executor.metrics if ctx.executor else MetricsRegistry()
        )
        self._small_nodes: Dict[int, Node] = {}
        self._eval_cache: Dict[int, Tuple[str, Any]] = {}
        self._stream_ids: Optional[set] = None

    @property
    def _pipelined(self) -> bool:
        return self.pipeline_depth > 1

    def _scope(self, slots: int = 1) -> _IngestScope:
        return _IngestScope(
            self.ctx, cache_plans=self._pipelined or slots > 1, slots=slots,
        )

    @property
    def _async_dispatch(self) -> bool:
        """Async drain path: window the chunk dispatches when the
        driver is NOT already device-resident pipelining partials."""
        return self.dispatch_depth > 1 or self.chunk_fuse > 1

    def _spill_writer(self) -> Optional[SpillWriter]:
        if not self._pipelined:
            return None
        return SpillWriter(events=self.events)

    # ---- public --------------------------------------------------------

    def run_to_host(self, root: Node) -> Dict[str, np.ndarray]:
        kind, val = self._eval(root)
        if kind == "small":
            self.metrics.emit(self.events)
            return val
        tables = list(self._realized(val))
        self.metrics.emit(self.events)
        return _concat_tables(tables, val.schema)

    def run_stream(self, root: Node):
        """(schema, iterator of host tables) — the bounded-memory
        result surface (Query.collect_stream)."""
        kind, val = self._eval(root)
        if kind == "small":
            return root.schema, iter([val])
        return val.schema, self._realized(val)

    def to_store(self, root: Node, path: str) -> int:
        """Stream results into a partitioned store; returns row count.
        Partitions write incrementally (one per emitted table); the
        shared metadata writer stamps the manifest at the end."""
        import os

        from dryad_tpu.columnar.io import _part_name, write_store_meta
        from dryad_tpu.runtime.bindings import write_partition

        kind, val = self._eval(root)
        schema = val.schema if kind == "stream" else root.schema
        tables = self._realized(val) if kind == "stream" else iter([val])
        os.makedirs(path, exist_ok=True)
        total = 0
        i = 0
        for t in tables:
            n = len(next(iter(t.values()))) if t else 0
            if not n:
                continue
            phys = _encode_store_part(t, schema, self.ctx.dictionary)
            write_partition(os.path.join(path, _part_name(i)), phys, None)
            total += n
            i += 1
        write_store_meta(path, i, schema, self.ctx.dictionary)
        self._emit("stream_store", path=path, rows=total, partitions=i)
        self.metrics.emit(self.events)
        return total

    # ---- helpers -------------------------------------------------------

    def _P(self) -> int:
        from dryad_tpu.parallel.mesh import num_partitions

        return (
            num_partitions(self.ctx.mesh)
            if self.ctx.mesh is not None else 8
        )

    def _emit(self, kind: str, **fields) -> None:
        if self.events is not None:
            self.events.emit(kind, **fields)

    def _run_engine(self, node: Node) -> Dict[str, np.ndarray]:
        from dryad_tpu.api.query import Query

        with self.tracer.span(f"engine:{node.kind}", cat="chunk"):
            return self.ctx.run_to_host(Query(self.ctx, node))

    def _clone(self, n: Node, new_inputs: Sequence[Node]) -> Node:
        return Node(n.kind, list(new_inputs), n.schema, n.partition, **n.params)

    def _materialize_small(self, node: Node) -> Node:
        """Run a stream-free subtree once; re-ingest as a host table so
        per-chunk jobs reuse the same binding instead of recomputing."""
        if node.id in self._small_nodes:
            return self._small_nodes[node.id]
        if node.kind == "input" and isinstance(
            self.ctx.inputs.get(node.id), (HostTable, PhysicalTable)
        ):
            self._small_nodes[node.id] = node  # already a cheap binding
            return node
        table = self._run_engine(node)
        q = self.ctx.from_arrays(table, schema=node.schema)
        self._small_nodes[node.id] = q.node
        return q.node

    def _chain_root(self, scope: _IngestScope, q, nodes: Sequence[Node]):
        """Clone the pending chain onto an ingest query ONCE per
        (reused) input node; a rebound chunk reuses the whole chain —
        no per-chunk Node cloning, and the lowering keys repeat."""
        if not scope.cache_plans:
            cur = q.node
            for n in nodes:
                cur = self._clone(n, [cur] + n.inputs[1:])
            return cur
        key = (q.node.id,) + tuple(n.id for n in nodes)
        root = scope.chain_cache.get(key)
        if root is None:
            root = q.node
            for n in nodes:
                root = self._clone(n, [root] + n.inputs[1:])
            scope.chain_cache[key] = root
        return root

    def _realize_table(
        self, table: Dict[str, np.ndarray], stream: _Stream,
        scope: _IngestScope, extra: Sequence[Node] = (),
    ) -> Dict[str, np.ndarray]:
        """Apply the stream's pending chain (+ extra nodes) to one chunk
        as a single engine job."""
        if not stream.pending and not extra:
            if is_physical_chunk(table, stream.base_schema):
                from dryad_tpu.columnar.batch import decode_physical_table

                t = {c: v for c, v in table.items() if c != "#vocab"}
                return decode_physical_table(
                    stream.base_schema, slice(None), t,
                    self.ctx.dictionary,
                )
            return table
        q = scope.ingest(table, stream.base_schema)
        cur = self._chain_root(
            scope, q, list(stream.pending) + list(extra)
        )
        return self._run_engine(cur)

    def _realized(self, stream: _Stream) -> Iterator[Dict[str, np.ndarray]]:
        scope = self._scope()
        for table in self._iter_base(stream):
            yield self._realize_table(table, stream, scope)

    # ---- evaluator -----------------------------------------------------

    def _eval(self, node: Node):
        """Memoized: a diamond (tee) re-requesting a node gets the same
        result object — small tables share; a second consumer of a
        stream raises the explicit already-consumed error."""
        if node.id in self._eval_cache:
            return self._eval_cache[node.id]
        if self._stream_ids is None:  # one walk per execution
            self._stream_ids = stream_reaching_ids(self.ctx, node)
        r = self._eval_inner(node)
        self._eval_cache[node.id] = r
        return r

    def _reaches_stream(self, node: Node) -> bool:
        # _stream_ids covers every node under the execution root (one
        # topological walk at first _eval)
        return node.id in self._stream_ids

    def _eval_inner(self, node: Node):
        b = self.ctx.inputs.get(node.id)
        if node.kind == "input" and isinstance(b, ChunkStream):
            src: ChunkSource = b.source
            self._emit("stream_start", node=node.id)
            return "stream", _Stream(
                src.schema, iter(src.chunks), _state=src.state
            )
        if not self._reaches_stream(node):
            return "small", self._run_engine(node)

        if node.kind in PARTITION_HINTS:
            return self._eval(node.inputs[0])
        if node.kind == "concat":
            return self._eval_concat(node)
        if node.kind == "join":
            return self._eval_join(node)
        # single-chain operators: a subtree that STREAMS may still
        # evaluate to a small table (e.g. group_by output feeding
        # order_by) — then this operator runs as one engine job over
        # the materialized input.
        k, v = self._eval(node.inputs[0])
        if k == "small":
            q = self.ctx.from_arrays(v, schema=node.inputs[0].schema)
            cur = self._clone(node, [q.node] + node.inputs[1:])
            return "small", self._run_engine(cur)
        if node.kind in ROW_LOCAL:
            return "stream", v.with_pending(node)
        if node.kind == "group_by":
            return self._eval_group(node, v)
        if node.kind == "aggregate":
            return self._eval_aggregate(node, v)
        if node.kind == "distinct":
            return self._eval_distinct(node, v)
        if node.kind == "order_by":
            return self._eval_order_by(node, v)
        if node.kind == "take":
            return self._eval_take(node, v)
        raise StreamNotSupported(
            f"operator {node.kind!r} over a chunk stream is not supported; "
            "materialize with to_store first"
        )

    # ---- group_by ------------------------------------------------------

    def _eval_group(self, node: Node, stream: _Stream):
        agg_list = node.params.get("aggs")
        keys = list(node.params["keys"])
        if agg_list and all(op in MERGEABLE_AGGS for op, _c, _o in agg_list):
            return self._group_partial(node, stream, keys, agg_list)
        # non-mergeable (custom decomposable without typed state, etc.):
        # Grace hash-bucketing, original group node per bucket.
        return "stream", _Stream(
            node.schema,
            self._grace_buckets([(stream, keys)], [node], node.schema),
        )

    def _finalize_query(self, q, plan, keys, out_schema):
        """Append the merge finalizer (mean = sum/count, renames) to a
        merged-partials query."""
        fin = finalize_fn(plan)

        def full(cols, _fin=fin, _keys=keys):
            from dryad_tpu.exec.partial import copy_physical

            out = {}
            for kk in _keys:
                copy_physical(cols, kk, kk, out)
            out.update(_fin(cols))
            return out

        return q.select(full, schema=out_schema)

    def _chunk_partial_query(self, scope, stream, table, node, keys, partial):
        """One chunk's partial group query, chain-cached: a rebound
        chunk reuses the ingest node, the pending clones, AND the
        group node — the whole per-chunk plan repeats (tentpole (a))."""
        from dryad_tpu.api.query import Query

        q = scope.ingest(table, stream.base_schema)
        key = ("gp", q.node.id)
        pq = scope.chain_cache.get(key)
        if pq is None:
            cur = self._chain_root(scope, q, stream.pending)
            pq = Query(self.ctx, cur).group_by(
                keys, partial,
                dense=node.params.get("dense"),
                salt=node.params.get("salt"),
            )
            if scope.cache_plans:
                scope.chain_cache[key] = pq
        return pq

    def _dispatcher(self, name: str, retry=None) -> _AsyncDispatcher:
        return _AsyncDispatcher(
            self.ctx, self.dispatch_depth, self.chunk_fuse,
            events=self.events, name=name, retry=retry,
        )

    def _group_partial(self, node, stream, keys, agg_list):
        if self._pipelined:
            return self._group_partial_device(node, stream, keys, agg_list)
        if self._async_dispatch:
            return self._group_partial_async(node, stream, keys, agg_list)
        return self._group_partial_serial(node, stream, keys, agg_list)

    def _group_partial_serial(self, node, stream, keys, agg_list):
        """Legacy serial driver (stream_pipeline_depth=1): per-chunk
        host readback of partials, host-side combine re-ingest."""
        partial, plan = partial_plan(agg_list)
        merge_spec = merge_agg_spec(plan)
        scope = self._scope()
        mscope = self._scope()
        acc: List[Dict[str, np.ndarray]] = []
        acc_rows = 0
        pschema = None

        def combine(tables, final: bool):
            cat = _concat_tables(tables, pschema)
            q = mscope.ingest(cat, pschema).group_by(keys, merge_spec)
            if final:
                q = self._finalize_query(q, plan, keys, node.schema)
            return self.ctx.run_to_host(q)

        shape = TreeShape(self.ctx.mesh, self.ctx.config)
        nchunks = 0
        for table in self._iter_base(stream):
            n = _chunk_rows(table)
            pq = self._chunk_partial_query(
                scope, stream, table, node, keys, partial
            )
            if pschema is None:
                pschema = pq.schema
            pt = self.ctx.run_to_host(pq)
            rows = len(next(iter(pt.values()))) if pt else 0
            acc.append(pt)
            acc_rows += rows
            nchunks += 1
            self._emit("stream_chunk", rows=n, partial_rows=rows)
            if acc_rows > self.combine_rows and len(acc) > 1:
                in_bytes = sum(
                    int(np.asarray(v).nbytes)
                    for t in acc for v in t.values()
                )
                merged = combine(acc, final=False)
                acc = [merged]
                acc_rows = len(next(iter(merged.values()))) if merged else 0
                out_bytes = sum(
                    int(np.asarray(v).nbytes) for v in merged.values()
                )
                ici, dcn = shape.exchange_split(in_bytes, out_bytes)
                self._emit("stream_combine", rows_out=acc_rows, level=0,
                           ici_bytes=ici, dcn_bytes=dcn)
        if pschema is None:  # empty stream
            return "small", _empty_table(node.schema)
        out = combine(acc, final=True)
        self._emit("stream_group_done", chunks=nchunks,
                   groups=len(next(iter(out.values()))) if out else 0)
        return "small", out

    def _group_partial_async(self, node, stream, keys, agg_list):
        """Async serial driver (``dispatch_depth``/``chunk_fuse`` > 1
        without the device-resident pipeline): the exact
        ``_group_partial_serial`` accumulate/combine body, but chunk
        partial dispatches stay in flight through the
        :class:`DispatchWindow` and readbacks drain on the collector
        thread.  Commits run strictly in submit order, so the host
        accumulator (and its float reduction order) matches the serial
        loop bit-for-bit."""
        partial, plan = partial_plan(agg_list)
        merge_spec = merge_agg_spec(plan)
        # one cached-input slot per fused chunk: a fused batch needs
        # all K input nodes bound simultaneously at dispatch
        scope = self._scope(slots=self.chunk_fuse)
        mscope = self._scope()
        acc: List[Dict[str, np.ndarray]] = []
        st = {"acc_rows": 0, "nchunks": 0, "pschema": None}
        shape = TreeShape(self.ctx.mesh, self.ctx.config)

        def combine(tables, final: bool):
            cat = _concat_tables(tables, st["pschema"])
            q = mscope.ingest(cat, st["pschema"]).group_by(keys, merge_spec)
            if final:
                q = self._finalize_query(q, plan, keys, node.schema)
            return self.ctx.run_to_host(q)

        def retry(tag):
            # serial re-execution of ONE chunk: the original cached
            # input node may have been rebound to a later chunk by
            # slot reuse, so re-ingest the retained host table through
            # a fresh uncached scope
            _n, table = tag
            rscope = _IngestScope(self.ctx)
            rq = self._chunk_partial_query(
                rscope, stream, table, node, keys, partial
            )
            return self.ctx.run_to_host(rq)

        def commit(tag, pt):
            n, _table = tag
            rows = len(next(iter(pt.values()))) if pt else 0
            acc.append(pt)
            st["acc_rows"] += rows
            st["nchunks"] += 1
            self._emit("stream_chunk", rows=n, partial_rows=rows)
            if st["acc_rows"] > self.combine_rows and len(acc) > 1:
                in_bytes = sum(
                    int(np.asarray(v).nbytes)
                    for t in acc for v in t.values()
                )
                merged = combine(acc, final=False)
                acc[:] = [merged]
                st["acc_rows"] = (
                    len(next(iter(merged.values()))) if merged else 0
                )
                out_bytes = sum(
                    int(np.asarray(v).nbytes) for v in merged.values()
                )
                ici, dcn = shape.exchange_split(in_bytes, out_bytes)
                self._emit("stream_combine", rows_out=st["acc_rows"],
                           level=0, ici_bytes=ici, dcn_bytes=dcn)

        dsp = self._dispatcher("grouppartial", retry=retry)
        try:
            for table in self._iter_base(stream):
                n = _chunk_rows(table)
                pq = self._chunk_partial_query(
                    scope, stream, table, node, keys, partial
                )
                if st["pschema"] is None:
                    st["pschema"] = pq.schema
                dsp.submit((n, table), pq)
                for tag, pt in dsp.ready():
                    commit(tag, pt)
            for tag, pt in dsp.drain():
                commit(tag, pt)
        finally:
            dsp.close()
        if st["pschema"] is None:  # empty stream
            return "small", _empty_table(node.schema)
        out = combine(acc, final=True)
        self._emit("stream_group_done", chunks=st["nchunks"],
                   groups=len(next(iter(out.values()))) if out else 0)
        return "small", out

    def _batch_to_host(self, batch, schema) -> Dict[str, np.ndarray]:
        """Materialize a device batch as a host logical table (the
        degrade path when device-side combining stops paying)."""
        fetched = batch.fetch_host(metrics=self.metrics)  # counts d2h_bytes
        return batch.to_numpy(schema, self.ctx.dictionary, _host=fetched[:2])

    def _group_partial_device(self, node, stream, keys, agg_list):
        """Pipelined driver: per-chunk partials stay DEVICE-RESIDENT
        (dispatched, never fetched), accumulate as ColumnBatches in HBM
        and merge device-to-device — the scatter phase pays one D2H at
        the END instead of one per chunk (the DrDynamicAggregateManager
        machine->pod tree folded onto the accelerator; DrJAX's
        device-resident MapReduce partials).

        With ``config.combine_tree`` on (default), accumulation runs
        through the topology/distribution-aware tree of
        :mod:`exec.combinetree`; the flat N-ary combiner below stays as
        the differential baseline and covers engine-order-sensitive
        aggregates (``first``), which the tree's similarity routing
        would reorder."""
        tree = bool(getattr(self.ctx.config, "combine_tree", True))
        ov = (
            self.rewriter.combine_tree_override()
            if self.rewriter is not None else None
        )
        if ov is not None and bool(ov) != tree:
            # combine_thrash rewrite: flip the strategy for streams
            # that START after the diagnosis (both strategies compute
            # the same groups — only the merge cadence differs)
            tree = bool(ov)
            self._emit(
                "plan_rewrite", phase="applied", action="flip_combine",
                rule="combine_thrash", subject="stream_combine",
                tree=tree,
            )
        if tree and not any(
            op == "first" for op, _c, _o in agg_list
        ):
            return self._group_partial_tree(node, stream, keys, agg_list)
        return self._group_partial_flat(node, stream, keys, agg_list)

    def _combine_pinned(self) -> bool:
        """True when a combine_thrash rewrite pinned the streaming
        combine to host accumulation (the always-correct conservative
        side of the oscillation)."""
        return (
            self.rewriter is not None
            and self.rewriter.combine_pin() == "host"
        )

    def _first_chunk_irreducible(self, table, stream, keys, batch, n):
        """Static high-cardinality signal for the first chunk: count the
        chunk's distinct keys with a HOST-side hash (exact, no device
        readback).  The partial batch's layout capacity is only trusted
        as a fallback for physical (device-resident) chunks, and only
        below the chunk's row count — the pow2 palette can pad capacity
        past n, which says nothing about the keys."""
        if n <= 0:
            return False
        if not is_physical_chunk(table, stream.base_schema):
            h = _host_key_hash64(table, keys, dictionary=self.ctx.dictionary)
            return np.unique(h).size >= 0.75 * n
        return n > batch.capacity >= 0.75 * n

    def _group_partial_flat(self, node, stream, keys, agg_list):
        """Flat N-ary device combiner (the tree-off baseline).

        High-cardinality streams whose merges show no reduction (static
        capacity check in :class:`_DeviceCombiner`) degrade to the
        serial driver's host-side threshold accumulation — on such
        streams device merging re-processes every row for nothing,
        while host accumulation pays one cheap transfer per chunk.  The
        degrade is no longer sticky: after
        ``config.stream_host_reprobe`` CONSECUTIVE host combines that
        do reduce below the device capacity check, the device path is
        retried with the merged accumulator re-ingested."""
        partial, plan = partial_plan(agg_list)
        merge_spec = merge_agg_spec(plan)
        scope = self._scope()
        mscope = self._scope()
        pschema = None
        shape = TreeShape(self.ctx.mesh, self.ctx.config)
        reprobe_after = int(
            getattr(self.ctx.config, "stream_host_reprobe", 0) or 0
        )

        def merge_many(batches):
            qs = [self.ctx._from_device_batch(b, pschema) for b in batches]
            q = qs[0].concat(*qs[1:])  # ONE N-ary concat node/stage
            return self.ctx._execute_device(q.group_by(keys, merge_spec))

        def host_combine(tables, final: bool):
            cat = _concat_tables(tables, pschema)
            q = mscope.ingest(cat, pschema).group_by(keys, merge_spec)
            if final:
                q = self._finalize_query(q, plan, keys, node.schema)
            return self.ctx.run_to_host(q)

        comb = _DeviceCombiner(
            merge_many, self.combine_rows, self._emit,
            split=shape.exchange_split,
        )
        host_acc: Optional[List[Dict[str, np.ndarray]]] = None
        host_rows = 0
        reduce_streak = 0  # consecutive host combines that DID reduce
        nchunks = 0
        pin_applied = False
        if self._combine_pinned():
            # pin_combine rewrite: start (and stay) on host
            # accumulation — no probe merge, no reprobe oscillation
            host_acc = []
            pin_applied = True
            self._emit("stream_combine_policy", mode="host", chunks=0,
                       pinned=True)
            self._emit(
                "plan_rewrite", phase="applied", action="pin_combine",
                rule="combine_thrash", subject="stream_combine",
                mode="host",
            )
        for table in self._iter_base(stream):
            n = _chunk_rows(table)
            pq = self._chunk_partial_query(
                scope, stream, table, node, keys, partial
            )
            if pschema is None:
                pschema = pq.schema
            batch = self.ctx._execute_device(pq)  # partial stays in HBM
            nchunks += 1
            self._emit("stream_chunk", rows=n, partial_cap=batch.capacity)
            if host_acc is None and nchunks == 1 \
                    and self._first_chunk_irreducible(table, stream, keys,
                                                     batch, n):
                # the FIRST chunk's keys are ~all distinct: device
                # merging cannot pay — degrade before paying even one
                # probe merge
                host_acc = []
                self._emit("stream_combine_policy", mode="host",
                           chunks=nchunks, static=True)
            if host_acc is None:
                if comb.push(batch):
                    continue
                # no reduction: degrade to host accumulation
                host_acc = [
                    self._batch_to_host(b, pschema) for b in comb.drain()
                ]
                host_rows = sum(
                    len(next(iter(t.values()))) if t else 0
                    for t in host_acc
                )
                self._emit("stream_combine_policy", mode="host",
                           chunks=nchunks)
            else:
                pt = self._batch_to_host(batch, pschema)
                host_acc.append(pt)
                host_rows += len(next(iter(pt.values()))) if pt else 0
            if host_rows > self.combine_rows and len(host_acc) > 1:
                pre_rows = host_rows
                in_bytes = sum(
                    int(np.asarray(v).nbytes)
                    for t in host_acc for v in t.values()
                )
                merged = host_combine(host_acc, final=False)
                host_acc = [merged]
                host_rows = len(next(iter(merged.values()))) if merged else 0
                out_bytes = sum(
                    int(np.asarray(v).nbytes) for v in merged.values()
                )
                ici, dcn = shape.exchange_split(in_bytes, out_bytes)
                self._emit("stream_combine", rows_out=host_rows, level=0,
                           ici_bytes=ici, dcn_bytes=dcn)
                # un-stick the degrade: host combines that keep reducing
                # mean the keys DO collapse — the earlier no-reduction
                # signal was transient (skew burst, unlucky first chunk)
                if host_rows < 0.75 * pre_rows:
                    reduce_streak += 1
                else:
                    reduce_streak = 0
                if self._combine_pinned():
                    # a combine_thrash diagnosis mid-stream pins the
                    # degrade: stop re-probing the device path
                    reduce_streak = 0
                    if not pin_applied:
                        pin_applied = True
                        self._emit(
                            "plan_rewrite", phase="applied",
                            action="pin_combine", rule="combine_thrash",
                            subject="stream_combine", mode="host",
                        )
                elif (
                    reprobe_after
                    and reduce_streak >= reprobe_after
                    and host_rows > 0
                ):
                    back = self.ctx._execute_device(
                        mscope.ingest(merged, pschema)
                    )
                    self.metrics.add(
                        "h2d_bytes",
                        sum(int(np.asarray(v).nbytes)
                            for v in merged.values()),
                    )
                    comb.push(back)
                    host_acc = None
                    host_rows = 0
                    reduce_streak = 0
                    self._emit("stream_combine_policy", mode="device",
                               chunks=nchunks, reprobe=True)
        if pschema is None:  # empty stream
            return "small", _empty_table(node.schema)
        if host_acc is not None:
            out = host_combine(host_acc, final=True)
        else:
            folded = comb.fold()
            q = self.ctx._from_device_batch(folded, pschema).group_by(
                keys, merge_spec
            )
            q = self._finalize_query(q, plan, keys, node.schema)
            out = self.ctx.run_to_host(q)
        self._emit("stream_group_done", chunks=nchunks,
                   groups=len(next(iter(out.values()))) if out else 0)
        return "small", out

    def _group_partial_tree(self, node, stream, keys, agg_list):
        """Combine-tree driver (``exec.combinetree``): chunk partials
        route into similarity-placed tree groups whose merges ELIDE the
        hash exchange — partials are co-hash-partitioned on the group
        keys, so equal keys are already colocated and one local reduce
        merges them with zero collective bytes.  Only the final
        merge+finalize query pays a full exchange: on a hybrid mesh the
        tree exchange's ICI hop, per-slice combine, and exactly one DCN
        hop last.

        The all-or-nothing host degrade becomes PER-KEY-RANGE: the
        driver hashes each raw chunk's keys host-side (before ingest),
        folds them into a :class:`KeyRangeHistogram`, and ranges whose
        distinct-key estimate tracks their row count — merging cannot
        reduce them — split out of subsequent chunks and stream to host
        accumulation, while hot, still-reducing ranges stay on
        device."""
        cfg = self.ctx.config
        partial, plan = partial_plan(agg_list)
        merge_spec = merge_agg_spec(plan)
        scope = self._scope()
        cscope = self._scope()  # degraded-range (cold) chunk plans
        mscope = self._scope()  # host-side combine plans
        pschema = None
        shape = TreeShape(self.ctx.mesh, cfg)
        planner = CombineTreePlanner(KEY_RANGES, DEGRADE_RATIO)
        hist = KeyRangeHistogram(KEY_RANGES)

        def merge_local(batches):
            # every chunk's partial group_by hash-exchanged on the same
            # keys over the same mesh, so the batches are co-partitioned
            # and the merge elides its exchange entirely
            # (plan.lower._needs_hash_exchange on the assume claim)
            qs = [self.ctx._from_device_batch(b, pschema) for b in batches]
            q = qs[0].concat(*qs[1:]).assume_hash_partition(keys)
            return self.ctx._execute_device(q.group_by(keys, merge_spec))

        def host_combine(tables, final: bool):
            cat = _concat_tables(tables, pschema)
            q = mscope.ingest(cat, pschema).group_by(keys, merge_spec)
            if final:
                q = self._finalize_query(q, plan, keys, node.schema)
            return self.ctx.run_to_host(q)

        comb = TreeCombiner(merge_local, shape, self.combine_rows, self._emit)
        host_acc: List[Dict[str, np.ndarray]] = []
        host_rows = 0
        degraded: set = set()
        nchunks = 0
        for table in self._iter_base(stream):
            n = _chunk_rows(table)
            h = None
            if not is_physical_chunk(table, stream.base_schema):
                h = _host_key_hash64(
                    table, keys, dictionary=self.ctx.dictionary
                )
            snap = None
            if h is not None:
                ch = KeyRangeHistogram(KEY_RANGES)
                ch.observe(h)
                hist.merge(ch)
                snap = ch.snapshot()
            nchunks += 1
            hot: Optional[Dict[str, Any]] = table
            if degraded and h is not None:
                rid = KeyRangeHistogram.range_ids(h, KEY_RANGES)
                cold_mask = np.isin(
                    rid, np.fromiter(degraded, np.int64, len(degraded))
                )
                if cold_mask.any():
                    cold = {
                        c: np.asarray(v)[cold_mask]
                        for c, v in table.items()
                    }
                    hot = (
                        {
                            c: np.asarray(v)[~cold_mask]
                            for c, v in table.items()
                        }
                        if not cold_mask.all() else None
                    )
                    cq = self._chunk_partial_query(
                        cscope, stream, cold, node, keys, partial
                    )
                    if pschema is None:
                        pschema = cq.schema
                    pt = self.ctx.run_to_host(cq)
                    host_acc.append(pt)
                    host_rows += len(next(iter(pt.values()))) if pt else 0
            if hot is not None:
                pq = self._chunk_partial_query(
                    scope, stream, hot, node, keys, partial
                )
                if pschema is None:
                    pschema = pq.schema
                batch = self.ctx._execute_device(pq)  # stays in HBM
                self._emit(
                    "stream_chunk", rows=n, partial_cap=batch.capacity
                )
                comb.push(batch, snap or neutral_snapshot(KEY_RANGES))
            else:
                self._emit("stream_chunk", rows=n, partial_cap=0)
            if host_rows > self.combine_rows and len(host_acc) > 1:
                in_bytes = sum(
                    int(np.asarray(v).nbytes)
                    for t in host_acc for v in t.values()
                )
                merged = host_combine(host_acc, final=False)
                host_acc = [merged]
                host_rows = len(next(iter(merged.values()))) if merged else 0
                out_bytes = sum(
                    int(np.asarray(v).nbytes) for v in merged.values()
                )
                ici, dcn = shape.exchange_split(in_bytes, out_bytes)
                self._emit("stream_combine", rows_out=host_rows, level=0,
                           ici_bytes=ici, dcn_bytes=dcn)
            if h is not None:
                planner.note_cumulative(hist.snapshot())
                new = planner.degrade_set()
                if new - degraded:
                    degraded = new
                    self._emit(
                        "combine_tree_degrade", degraded=len(degraded),
                        fraction=round(planner.degraded_fraction(), 4),
                        chunks=nchunks,
                    )
        if pschema is None:  # empty stream
            return "small", _empty_table(node.schema)
        if not host_acc:
            # pure device path: collapse the survivors to ONE batch with
            # elided merges first — the root query's exchange pays bytes
            # proportional to what it ingests, and elided merges are
            # nearly free, so the root must see the minimum — then run
            # the one exchanged merge+finalize reduction, with the DCN
            # hop accounted at the distribution-informed output estimate
            # (the exchange folds to at most the estimated distinct keys)
            folded = comb.fold(1)
            if not folded:  # every chunk was empty
                return "small", _empty_table(node.schema)
            root = folded[0]
            in_bytes = batch_bytes(root)
            est_rows = (
                float(hist.distinct_estimates().sum()) if hist.rows else 0.0
            )
            per_row = in_bytes / max(int(root.capacity), 1)
            out_bytes = int(min(in_bytes, per_row * max(est_rows, 1.0)))
            ici, dcn = shape.exchange_split(in_bytes, out_bytes)
            self._emit(
                "combine_tree_level", level=comb.max_level + 1,
                fan_in=1, cap_rows=int(root.capacity), bytes=in_bytes,
                ici_bytes=ici, dcn_bytes=dcn, device=True,
            )
            q = self.ctx._from_device_batch(root, pschema).group_by(
                keys, merge_spec
            )
            q = self._finalize_query(q, plan, keys, node.schema)
            out = self.ctx.run_to_host(q)
        else:
            # degraded ranges finish host-side: the device remainder
            # folds once, pays ONE D2H, and merges with the host
            # accumulator in the final combine
            folded = comb.fold(1)
            tables = list(host_acc)
            if folded:
                tables.append(self._batch_to_host(folded[0], pschema))
            out = host_combine(tables, final=True)
        self._emit("stream_group_done", chunks=nchunks,
                   groups=len(next(iter(out.values()))) if out else 0)
        return "small", out

    # ---- scalar aggregate ---------------------------------------------

    def _eval_aggregate(self, node: Node, stream: _Stream):
        from dryad_tpu.api.query import Query

        agg_list = node.params["aggs"]
        bad = [op for op, _c, _o in agg_list
               if op not in MERGEABLE_AGGS or op == "first"]
        if bad:
            raise StreamNotSupported(
                f"streaming scalar aggregate cannot merge {bad}"
            )
        partial, plan = partial_plan(agg_list)
        merge_spec = merge_agg_spec(plan)
        scope = self._scope(
            slots=1 if self._pipelined else self.chunk_fuse
        )
        fin = finalize_fn(plan)
        pschema = None

        def chunk_query(table):
            q = scope.ingest(table, stream.base_schema)
            key = ("agg", q.node.id)
            pq = scope.chain_cache.get(key)
            if pq is None:
                cur = self._chain_root(scope, q, stream.pending)
                pq = Query(self.ctx, cur).aggregate_as_query(partial)
                if scope.cache_plans:
                    scope.chain_cache[key] = pq
            return pq

        if self._pipelined:
            # device-resident partials + N-ary device merge: one D2H
            # total (scalar partials are one row each, so flushes
            # always reduce and never degrade)
            def merge_many(batches):
                qs = [
                    self.ctx._from_device_batch(b, pschema) for b in batches
                ]
                q = qs[0].concat(*qs[1:]).aggregate_as_query(merge_spec)
                return self.ctx._execute_device(q)

            comb = _DeviceCombiner(
                merge_many, self.combine_rows, self._emit,
                split=TreeShape(self.ctx.mesh, self.ctx.config).exchange_split,
            )
            for table in self._iter_base(stream):
                pq = chunk_query(table)
                if pschema is None:
                    pschema = pq.schema
                comb.push(self.ctx._execute_device(pq))
            folded = comb.fold()
            if folded is None:
                raise StreamNotSupported(
                    "scalar aggregate over an empty stream"
                )
            q = self.ctx._from_device_batch(folded, pschema)
            q = q.aggregate_as_query(merge_spec)
            q = q.select(lambda cols: fin(cols), schema=node.schema)
            return "small", self.ctx.run_to_host(q)

        # serial driver: host partials, bounded by the SAME combine
        # threshold as _group_partial — a long stream must not grow the
        # accumulator one partial row per chunk without bound
        acc_t: List[Dict[str, np.ndarray]] = []
        st = {"rows": 0}
        mscope = self._scope()

        def commit(pt):
            acc_t.append(pt)
            st["rows"] += len(next(iter(pt.values()))) if pt else 0
            if st["rows"] > self.combine_rows and len(acc_t) > 1:
                cat = _concat_tables(acc_t, pschema)
                merged = self.ctx.run_to_host(
                    mscope.ingest(cat, pschema).aggregate_as_query(merge_spec)
                )
                acc_t[:] = [merged]
                st["rows"] = len(next(iter(merged.values()))) if merged else 0
                self._emit(
                    "stream_combine", rows_out=st["rows"],
                    level=0, ici_bytes=0, dcn_bytes=0,
                )

        if self._async_dispatch:
            # async serial driver: partial dispatches stay in flight
            # through the window; the host accumulator commits at the
            # drain site in submit order (same body, same float order)
            def retry(table):
                rscope = _IngestScope(self.ctx)
                rq = Query(
                    self.ctx,
                    self._chain_root(
                        rscope, rscope.ingest(table, stream.base_schema),
                        stream.pending,
                    ),
                ).aggregate_as_query(partial)
                return self.ctx.run_to_host(rq)

            dsp = self._dispatcher("aggpartial", retry=retry)
            try:
                for table in self._iter_base(stream):
                    pq = chunk_query(table)
                    if pschema is None:
                        pschema = pq.schema
                    dsp.submit(table, pq)
                    for _tag, pt in dsp.ready():
                        commit(pt)
                for _tag, pt in dsp.drain():
                    commit(pt)
            finally:
                dsp.close()
        else:
            for table in self._iter_base(stream):
                pq = chunk_query(table)
                if pschema is None:
                    pschema = pq.schema
                commit(self.ctx.run_to_host(pq))
        if pschema is None:
            raise StreamNotSupported("scalar aggregate over an empty stream")
        cat = _concat_tables(acc_t, pschema)
        q = mscope.ingest(cat, pschema).aggregate_as_query(merge_spec)
        q = q.select(lambda cols: fin(cols), schema=node.schema)
        return "small", self.ctx.run_to_host(q)

    def _iter_base(self, stream: _Stream):
        """Non-empty base chunks, read ahead by the prefetch thread when
        the pipeline is on: the source generator's host work (tokenize,
        disk read, decode) for chunk k+2 overlaps the driver's device
        dispatch of chunk k+1 (``exec.pipeline``)."""
        if stream.consumed:
            raise RuntimeError("stream already consumed (tee over streams "
                               "needs an explicit to_store)")
        stream.consumed = True

        def nonempty():
            for table in stream.chunks:
                if _chunk_rows(table):
                    yield table

        yield from prefetched(
            nonempty(), self.pipeline_depth, events=self.events,
            name="ingest",
        )

    # ---- distinct ------------------------------------------------------

    def _eval_distinct(self, node: Node, stream: _Stream):
        keys = list(node.params["keys"] or stream.schema.names)
        scope = self._scope()
        acc: List[Dict[str, np.ndarray]] = []
        acc_rows = 0
        spill = None
        writer = None
        try:
            for table in self._iter_base(stream):
                t = self._realize_table(table, stream, scope, extra=[node])
                rows = len(next(iter(t.values()))) if t else 0
                if spill is not None:
                    self._spill_by_hash(spill, t, keys, 0, writer=writer)
                    continue
                acc.append(t)
                acc_rows += rows
                if acc_rows > self.combine_rows and len(acc) > 1:
                    cscope = self._scope()
                    cat = _concat_tables(acc, node.schema)
                    cur = self._clone(
                        node, [cscope.ingest(cat, node.schema).node]
                    )
                    merged = self._run_engine(cur)
                    acc = [merged]
                    acc_rows = (
                        len(next(iter(merged.values()))) if merged else 0
                    )
                    if acc_rows > self.bucket_rows:
                        # high cardinality: switch to Grace spilling
                        spill = SpillDir(self.ctx.dictionary,
                                         root=self._spill_root())
                        writer = self._spill_writer()
                        self._spill_by_hash(spill, merged, keys, 0,
                                            writer=writer)
                        acc = []
                        self._emit("stream_distinct_spill", rows=acc_rows)
            if writer is not None:
                writer.flush()
        except BaseException:
            if writer is not None:
                writer.close(drain=False)
                writer = None
            if spill is not None:
                spill.cleanup()
            raise
        finally:
            if writer is not None:
                writer.close()
        if spill is None:
            if not acc:
                return "small", _empty_table(node.schema)
            cscope = self._scope()
            cat = _concat_tables(acc, node.schema)
            cur = self._clone(node, [cscope.ingest(cat, node.schema).node])
            return "small", self._run_engine(cur)

        def buckets():
            try:
                bscope = self._scope()
                for b in spill.buckets():
                    rows = spill.bucket_rows(b)
                    t = spill.read_bucket(b)
                    bscope.cap = self._bucket_cap(rows)
                    cur = self._clone(
                        node, [bscope.ingest(t, node.schema).node]
                    )
                    out = self._run_engine(cur)
                    self._emit("stream_bucket", bucket=b, depth=0, rows=rows)
                    yield out
            finally:
                spill.cleanup()

        return "stream", _Stream(node.schema, buckets())

    # ---- order_by (external distribution sort) -------------------------

    def _eval_order_by(self, node: Node, stream: _Stream):
        keys = list(node.params["keys"])  # [(name, desc)]
        return "stream", _Stream(
            node.schema, self._external_sort(node, stream, keys)
        )

    def _bucket_cap(self, rows: int) -> int:
        """Per-partition capacity for a bucket job from its OBSERVED
        rows: the next power-of-two step of the per-partition need
        (min 8), capped at the configured bucket budget.  Padding
        shrinks from the worst-case layout (~16x waste on typical
        shapes) to < 2x the data, while the pow2 palette keeps the
        number of distinct compiled programs logarithmic.

        The serial legacy driver (depth 1) keeps its original
        worst-case capacity — one compiled program for ALL buckets, and
        the differential baseline the pipeline is measured against."""
        P = self._P()
        full = max(1, math.ceil(self.bucket_rows / P / 8) * 8)
        if not self._pipelined:
            return full
        need = max(1, -(-max(rows, 1) // P))
        cap = 8
        while cap < need:
            cap *= 2
        return min(cap, full)

    def _external_sort(
        self, node, stream, keys, pieces=None, depth=0, splitters=None
    ):
        """Route chunks to range buckets by the primary key, then sort
        each bucket on device and emit in key order.  Oversized buckets
        re-split from observed volume; a single-value bucket falls
        through to the secondary keys (or emits as-is when none —
        equal-key order is unspecified).

        Pipelined (depth knob > 1): bucket writes go through the
        background SpillWriter so they overlap the next chunk's
        routing, and phase 2 keeps ``stream_pipeline_depth`` bucket
        sorts in flight — read/decode of bucket k+2 on the prefetch
        thread, dispatch of k+1, readback of k."""
        primary, pdesc = keys[0]
        spill = SpillDir(self.ctx.dictionary, root=self._spill_root())
        writer = self._spill_writer()
        # rewrite-split hot buckets: bucket -> {"splitters", "spill",
        # "extent", "rows"} — rows landing in a refined bucket route
        # straight into its sub-range spill at depth+1 (rewrite
        # controller's split_bucket action, claimed at chunk bounds)
        refined: Dict[int, dict] = {}
        try:
            scope = self._scope()
            if pieces is not None:
                src = prefetched(
                    self._iter_pieces_realized(pieces),
                    self.pipeline_depth, events=self.events,
                    name=f"resplit{depth}",
                )
            else:
                src = (self._realize_table(t, stream, scope)
                       for t in self._iter_base(stream))
            # exact per-bucket key extent, tracked at spill time — the
            # all-equal decision below must not rest on a sample (a few
            # minority rows in a fat bucket would go out unsorted)
            extent: Dict[int, Tuple] = {}
            for t in src:
                col = _sort_key_view(t[primary])
                if splitters is None:
                    splitters = _sample_splitters(col, self.num_buckets)
                # chunk boundary = safe application point: no partial
                # chunk is in flight, bucket contents are self-contained
                if self.rewriter is not None:
                    self._apply_sort_splits(
                        spill, writer, refined, primary, depth
                    )
                bids = np.searchsorted(splitters, col, side="right")
                for b in np.unique(bids):
                    sel = bids == b
                    piece = {c: v[sel] for c, v in t.items()}
                    if int(b) in refined:
                        self._route_refined(
                            refined[int(b)], piece, primary, depth
                        )
                        continue
                    vals = col[sel]
                    mn, mx = vals.min(), vals.max()
                    if b in extent:
                        pmn, pmx = extent[b]
                        mn, mx = min(mn, pmn), max(mx, pmx)
                    extent[int(b)] = (mn, mx)
                    self.metrics.observe(
                        "partition_rows", int(sel.sum()), depth=depth
                    )
                    if writer is not None:
                        writer.submit(spill, int(b), piece, depth)
                    else:
                        b0 = spill.bytes_written
                        n = spill.append(int(b), piece)
                        self.metrics.add(
                            "spill_bytes", spill.bytes_written - b0
                        )
                        self._emit("stream_spill", bucket=int(b), rows=n,
                                   depth=depth)
            if writer is not None:
                writer.flush()  # phase barrier: bucket metadata is final
            order = spill.buckets()
            if refined:
                order = sorted(set(order) | set(refined))
            if pdesc:
                order = list(reversed(order))
            yield from self._sort_buckets(
                node, spill, order, extent, keys, depth,
                refined=refined or None,
            )
        finally:
            if writer is not None:
                writer.close(drain=False)
            for rec in refined.values():
                rec["spill"].cleanup()
            spill.cleanup()

    def _apply_sort_splits(self, spill, writer, refined, primary, depth):
        """Claim pending split_bucket rewrites for this depth and turn
        each into a range refinement: sub-splitters from the bucket's
        live sample, already-spilled pieces re-routed eagerly, future
        rows routed on arrival (``_route_refined``).  Byte-identity:
        sub-buckets nest inside the parent range and emit in range
        order, so the global sorted order is exactly preserved."""
        acts = self.rewriter.claim_splits(depth)
        acts = [a for a in acts
                if int(a.params["bucket"]) not in refined]
        if not acts or depth >= self.max_split_depth:
            return
        if writer is not None:
            writer.flush()  # bucket piece lists must be final to reroute
        for act in acts:
            b = int(act.params["bucket"])
            if b not in spill.buckets():
                continue  # diagnosis about another spill at this depth
            sample = _bucket_sample(spill, b, primary)
            sub = _splitters_from_sample(
                sample, int(act.params.get("fan", 8) or 8)
            )
            if len(sub) == 0:
                continue  # single-valued: a range split cannot help
            rec = {
                "splitters": sub,
                "spill": SpillDir(
                    self.ctx.dictionary, root=self._spill_root()
                ),
                "extent": {},
                "rows": 0,
            }
            for piece in spill.read_bucket_pieces(b):
                self._route_refined(rec, piece, primary, depth)
            spill.drop_bucket(b)
            refined[b] = rec
            self._emit(
                "plan_rewrite", phase="applied", action="split_bucket",
                rule=act.rule, subject=act.subject, bucket=b,
                depth=depth, fan=int(len(sub)) + 1,
            )

    def _route_refined(self, rec, piece, primary, depth):
        """Route one piece of a rewrite-split bucket into its sub-range
        spill at ``depth + 1``, tracking exact sub-extents (the same
        invariant phase 1 keeps for the parent buckets)."""
        col = _sort_key_view(piece[primary])
        bids = np.searchsorted(rec["splitters"], col, side="right")
        rspill = rec["spill"]
        for sb in np.unique(bids):
            sel = bids == sb
            vals = col[sel]
            mn, mx = vals.min(), vals.max()
            if int(sb) in rec["extent"]:
                pmn, pmx = rec["extent"][int(sb)]
                mn, mx = min(mn, pmn), max(mx, pmx)
            rec["extent"][int(sb)] = (mn, mx)
            sub = {c: v[sel] for c, v in piece.items()}
            self.metrics.observe(
                "partition_rows", int(sel.sum()), depth=depth + 1
            )
            b0 = rspill.bytes_written
            n = rspill.append(int(sb), sub)
            self.metrics.add("spill_bytes", rspill.bytes_written - b0)
            self._emit("stream_spill", bucket=int(sb), rows=n,
                       depth=depth + 1)
            rec["rows"] += n

    def _sort_buckets(self, node, spill, order, extent, keys, depth,
                      refined=None):
        """Phase 2 of the external sort: per-bucket device sorts in
        key order, with read-ahead and a bounded dispatch window when
        pipelined."""
        from dryad_tpu.api.query import Query

        primary, _pdesc = keys[0]
        # one scope for all buckets: the pow2 capacity palette keeps
        # repeated bucket sizes on the same compiled program
        bscope = self._scope(slots=self.chunk_fuse)

        def reads():
            for b in order:
                if refined and b in refined:
                    # rewrite-split: contents live in the sub-spill,
                    # the driver recurses below (never read whole)
                    yield b, refined[b]["rows"], None
                    continue
                rows = spill.bucket_rows(b)
                # oversized buckets are re-split by the driver, which
                # streams their pieces — don't read them whole ahead
                table = (
                    spill.read_bucket(b) if rows <= self.bucket_rows
                    else None
                )
                yield b, rows, table

        src = prefetched(
            reads(), self.pipeline_depth, events=self.events,
            name=f"sortread{depth}",
        )
        inflight: deque = deque()  # (fetch, bucket, rows)

        def drain_one():
            fetch, b, rows = inflight.popleft()
            out = fetch()
            self._emit("stream_bucket", bucket=b, rows=rows, depth=depth)
            spill.drop_bucket(b)
            return out

        def retry(tag):
            # serial re-run of one bucket through a fresh scope (the
            # shared bscope's cached node may have been rebound to a
            # later bucket by the time the drain site sees the error)
            b, rows, t = tag
            rscope = _IngestScope(self.ctx)
            rscope.cap = self._bucket_cap(rows)
            return self._run_engine(
                self._clone(node, [rscope.ingest(t, node.schema).node])
            )

        dsp = (
            self._dispatcher(f"sortdrain{depth}", retry=retry)
            if self._async_dispatch else None
        )

        def committed(outcomes):
            for (db, drows, _dt), out in outcomes:
                self._emit("stream_bucket", bucket=db, rows=drows,
                           depth=depth)
                spill.drop_bucket(db)
                yield out

        try:
            for b, rows, t in src:
                if t is not None:
                    bscope.cap = self._bucket_cap(rows)
                    cur = self._clone(
                        node, [bscope.ingest(t, node.schema).node]
                    )
                    if dsp is not None:
                        # async drain path: the collector owns the
                        # readback, the driver commits in key order
                        dsp.submit((b, rows, t), Query(self.ctx, cur))
                        yield from committed(dsp.ready())
                    elif self._pipelined:
                        fetch = self.ctx.run_to_host_async(
                            Query(self.ctx, cur)
                        )
                        inflight.append((fetch, b, rows))
                        while len(inflight) >= self.pipeline_depth:
                            yield drain_one()
                    else:
                        out = self._run_engine(cur)
                        self._emit("stream_bucket", bucket=b, rows=rows,
                                   depth=depth)
                        yield out
                        spill.drop_bucket(b)
                    continue
                # refined or oversized: results must stay in key order,
                # so the dispatch window drains before the recursion
                if dsp is not None:
                    yield from committed(dsp.drain())
                while inflight:
                    yield drain_one()
                if refined and b in refined:
                    # rewrite-split bucket: sub-ranges nest inside the
                    # parent range, so emitting them in range order
                    # here preserves the global sorted order exactly
                    rec = refined[b]
                    rorder = sorted(rec["spill"].buckets())
                    if _pdesc:
                        rorder = list(reversed(rorder))
                    self._emit("stream_bucket_split", bucket=b,
                               rows=rows, depth=depth, mode="rewrite",
                               fanout=len(rorder))
                    yield from self._sort_buckets(
                        node, rec["spill"], rorder, rec["extent"],
                        keys, depth + 1,
                    )
                    continue
                if depth >= self.max_split_depth:
                    raise RuntimeError(
                        f"sort bucket {b} still holds {rows} rows at "
                        f"split depth {depth}; raise stream_bucket_rows"
                    )
                mn, mx = extent[b]
                if mn == mx:  # exact: every primary value identical
                    if len(keys) > 1:
                        self._emit("stream_bucket_split", bucket=b,
                                   rows=rows, depth=depth,
                                   mode="secondary_key")
                        yield from self._external_sort(
                            node, None, keys[1:],
                            pieces=(spill, b), depth=depth + 1,
                        )
                    else:
                        # all key values equal: any order is sorted
                        self._emit("stream_bucket_split", bucket=b,
                                   rows=rows, depth=depth,
                                   mode="equal_keys")
                        for piece in spill.read_bucket_pieces(b):
                            yield piece
                    spill.drop_bucket(b)
                    continue
                # fan-out from OBSERVED volume (DrDynamicRangeDistributor
                # .cpp:54-110: copies = sampled size / data per vertex)
                # and splitters from the whole bucket's sample, not its
                # first piece — the first-chunk estimate failed here.
                sample = _bucket_sample(spill, b, primary)
                fan = min(256, max(2, -(-rows // self.bucket_rows) * 2))
                sub = _splitters_from_sample(sample, fan)
                self._emit("stream_bucket_split", bucket=b, rows=rows,
                           depth=depth, mode="resplit", fanout=fan)
                yield from self._external_sort(
                    node, None, keys, pieces=(spill, b),
                    depth=depth + 1, splitters=sub,
                )
                spill.drop_bucket(b)
            if dsp is not None:
                yield from committed(dsp.drain())
            while inflight:
                yield drain_one()
        finally:
            if dsp is not None:
                dsp.close()
            if hasattr(src, "close"):
                src.close()

    def _iter_pieces_realized(self, pieces):
        spill, b = pieces
        yield from spill.read_bucket_pieces(b)

    # ---- join ----------------------------------------------------------

    def _eval_join(self, node: Node):
        left, right = node.inputs
        lstream = self._reaches_stream(left)
        rstream = self._reaches_stream(right)
        if lstream and not rstream:
            rnode = self._materialize_small(right)
            k, s = self._eval(left)
            assert k == "stream"
            clone = self._clone(node, [None, rnode])  # input[0] = chain
            return "stream", s.with_pending(clone)
        if rstream and not lstream:
            # chain enters the RIGHT slot: per-chunk join with the
            # materialized left is wrong for outer kinds (left rows
            # would duplicate per chunk) — Grace both sides instead.
            pass
        lk_cols = list(node.params["left_keys"])
        rk_cols = list(node.params["right_keys"])
        kl, ls = self._eval(left)
        kr, rs = self._eval(right)
        ls = ls if kl == "stream" else _table_as_stream(ls, left.schema)
        rs = rs if kr == "stream" else _table_as_stream(rs, right.schema)
        return "stream", _Stream(
            node.schema,
            self._grace_join(node, ls, rs, lk_cols, rk_cols),
        )

    def _grace_join(self, node, ls, rs, lk, rk, depth=0):
        lspill = SpillDir(self.ctx.dictionary, root=self._spill_root())
        rspill = SpillDir(self.ctx.dictionary, root=self._spill_root())
        writer = self._spill_writer()
        # rewrite-split hot buckets: bucket -> (left sub-spill, right
        # sub-spill), re-hashed at salt=depth+1 on BOTH sides so
        # matching keys stay co-bucketed (split_bucket action claimed
        # at chunk boundaries of either spill loop)
        jrefined: Dict[int, Tuple[SpillDir, SpillDir]] = {}
        try:
            lscope = self._scope()
            rscope = self._scope()
            for t in (self._realize_table(x, ls, lscope)
                      for x in self._iter_base(ls)):
                if self.rewriter is not None:
                    self._apply_join_splits(
                        jrefined, lspill, rspill, lk, rk, writer, depth
                    )
                self._spill_by_hash(lspill, t, lk, depth, writer=writer,
                                    refined=jrefined, side=0)
            for t in (self._realize_table(x, rs, rscope)
                      for x in self._iter_base(rs)):
                if self.rewriter is not None:
                    self._apply_join_splits(
                        jrefined, lspill, rspill, lk, rk, writer, depth
                    )
                self._spill_by_hash(rspill, t, rk, depth, writer=writer,
                                    refined=jrefined, side=1)
            if writer is not None:
                writer.flush()
            yield from self._join_buckets(
                node, lspill, rspill, lk, rk, depth,
                refined=jrefined or None,
            )
        finally:
            if writer is not None:
                writer.close(drain=False)
            for l2, r2 in jrefined.values():
                l2.cleanup()
                r2.cleanup()
            lspill.cleanup()
            rspill.cleanup()

    def _apply_join_splits(self, jrefined, lspill, rspill, lk, rk,
                           writer, depth):
        """Claim pending split_bucket rewrites for this depth and
        re-hash the hot bucket into per-side sub-spills at depth+1 —
        the SAME salt/fanout the oversized rehash path would use, so
        the resulting per-key co-bucketing (and thus the join output)
        is identical; only when the work happens changes."""
        acts = self.rewriter.claim_splits(depth)
        acts = [a for a in acts
                if int(a.params["bucket"]) not in jrefined]
        if not acts or depth >= self.max_split_depth:
            return
        if writer is not None:
            writer.flush()  # bucket piece lists must be final to reroute
        for act in acts:
            b = int(act.params["bucket"])
            l2 = SpillDir(self.ctx.dictionary, root=self._spill_root())
            r2 = SpillDir(self.ctx.dictionary, root=self._spill_root())
            jrefined[b] = (l2, r2)
            if b in lspill.buckets():
                for piece in lspill.read_bucket_pieces(b):
                    self._spill_by_hash(l2, piece, lk, depth + 1)
                lspill.drop_bucket(b)
            if b in rspill.buckets():
                for piece in rspill.read_bucket_pieces(b):
                    self._spill_by_hash(r2, piece, rk, depth + 1)
                rspill.drop_bucket(b)
            self._emit(
                "plan_rewrite", phase="applied", action="split_bucket",
                rule=act.rule, subject=act.subject, bucket=b,
                depth=depth,
            )

    def _join_buckets(self, node, lspill, rspill, lk, rk, depth,
                      refined=None):
        jkind = node.params.get("join_kind", "inner")
        # shared per-side scopes: the pow2 capacity palette keeps
        # repeated bucket sizes on the same compiled join program
        lscope = self._scope()
        rscope = self._scope()
        allb = set(lspill.buckets()) | set(rspill.buckets())
        if refined:
            allb |= set(refined)
        for b in sorted(allb):
            if refined and b in refined:
                # rewrite-split: both sides already re-hashed at
                # depth+1 — join the sub-buckets in the parent's slot
                # (exactly where the oversized rehash would emit them)
                l2, r2 = refined[b]
                rows2 = (
                    sum(l2.bucket_rows(x) for x in l2.buckets())
                    + sum(r2.bucket_rows(x) for x in r2.buckets())
                )
                self._emit("stream_bucket_split", bucket=b, rows=rows2,
                           depth=depth, mode="rewrite")
                yield from self._join_buckets(node, l2, r2, lk, rk,
                                              depth + 1)
                continue
            lrows = lspill.bucket_rows(b)
            rrows = rspill.bucket_rows(b)
            if lrows == 0 and jkind in ("inner", "left", "semi", "anti",
                                        "count", "ranked"):
                continue
            if rrows == 0 and jkind in ("inner", "semi", "ranked"):
                continue
            if lrows + rrows > self.bucket_rows:
                if depth >= self.max_split_depth:
                    raise RuntimeError(
                        f"join bucket {b} holds {lrows}+{rrows} rows at "
                        f"split depth {depth}; raise stream_bucket_rows "
                        "(skewed key?)"
                    )
                self._emit("stream_bucket_split", bucket=b,
                           rows=lrows + rrows, depth=depth, mode="rehash")
                l2 = SpillDir(self.ctx.dictionary, root=self._spill_root())
                r2 = SpillDir(self.ctx.dictionary, root=self._spill_root())
                try:
                    for piece in lspill.read_bucket_pieces(b):
                        self._spill_by_hash(l2, piece, lk, depth + 1)
                    for piece in rspill.read_bucket_pieces(b):
                        self._spill_by_hash(r2, piece, rk, depth + 1)
                    yield from self._join_buckets(node, l2, r2, lk, rk,
                                                  depth + 1)
                finally:
                    l2.cleanup()
                    r2.cleanup()
                continue
            lt = lspill.read_bucket(b)
            rt = rspill.read_bucket(b)
            if not lt:
                lt = _empty_table(node.inputs[0].schema)
            if not rt:
                rt = _empty_table(node.inputs[1].schema)
            lscope.cap = self._bucket_cap(lrows)
            rscope.cap = self._bucket_cap(rrows)
            lq = lscope.ingest(lt, node.inputs[0].schema)
            rq = rscope.ingest(rt, node.inputs[1].schema)
            cur = self._clone(node, [lq.node, rq.node])
            out = self._run_engine(cur)
            self._emit("stream_bucket", bucket=b, rows=lrows + rrows,
                       depth=depth)
            yield out

    def _grace_buckets(self, sides, tail_nodes, out_schema):
        """Generic single-input Grace: spill each (stream, keys) side,
        then run the tail nodes per bucket (used for non-mergeable
        group_by)."""
        (stream, keys), = sides
        spill = SpillDir(self.ctx.dictionary, root=self._spill_root())
        writer = self._spill_writer()
        try:
            scope = self._scope()
            for t in (self._realize_table(x, stream, scope)
                      for x in self._iter_base(stream)):
                self._spill_by_hash(spill, t, keys, 0, writer=writer)
            if writer is not None:
                writer.flush()
            bscope = self._scope()
            base_schema = stream.schema
            yield from self._grace_bucket_tables(
                spill, bscope, base_schema, tail_nodes
            )
        finally:
            if writer is not None:
                writer.close(drain=False)
            spill.cleanup()

    def _grace_bucket_tables(self, spill, bscope, base_schema, tail_nodes):
        for b in spill.buckets():
            rows = spill.bucket_rows(b)
            t = spill.read_bucket(b)
            bscope.cap = self._bucket_cap(rows)
            cur = bscope.ingest(t, base_schema).node
            for n in tail_nodes:
                cur = self._clone(n, [cur] + n.inputs[1:])
            out = self._run_engine(cur)
            self._emit("stream_bucket", bucket=b, depth=0, rows=rows)
            yield out

    def _spill_by_hash(self, spill, table, keys, depth, writer=None,
                       refined=None, side=0):
        bids = _host_hash_buckets(
            table, keys, self.num_buckets, salt=depth,
            dictionary=self.ctx.dictionary,
        )
        for b in np.unique(bids):
            sel = bids == b
            piece = {c: v[sel] for c, v in table.items()}
            if refined and int(b) in refined:
                # rewrite-split hot bucket: route straight into the
                # per-side sub-spill at depth+1 (same salt the rehash
                # resplit uses — co-bucketing is preserved)
                self._spill_by_hash(
                    refined[int(b)][side], piece, keys, depth + 1,
                    writer=writer,
                )
                continue
            # per-partition row histogram = the skew signal
            # distribution-aware scheduling needs (PAPERS.md "Chasing
            # Similarity"); one sample per (bucket, piece)
            self.metrics.observe(
                "partition_rows", int(sel.sum()), depth=depth
            )
            if writer is not None:
                writer.submit(spill, int(b), piece, depth)
                continue
            b0 = spill.bytes_written
            n = spill.append(int(b), piece)
            self.metrics.add("spill_bytes", spill.bytes_written - b0)
            self._emit("stream_spill", bucket=int(b), rows=n, depth=depth)

    def _spill_root(self):
        import os
        import tempfile

        base = getattr(self.ctx.config, "stream_spill_dir", None)
        if base:
            os.makedirs(base, exist_ok=True)
            return tempfile.mkdtemp(prefix="spill_", dir=base)
        return None

    # ---- take / concat -------------------------------------------------

    def _eval_take(self, node: Node, s: _Stream):
        want = int(node.params["n"])

        def gen():
            got = 0
            for t in self._realized(s):
                rows = len(next(iter(t.values()))) if t else 0
                if got + rows >= want:
                    keep = want - got
                    yield {c: v[:keep] for c, v in t.items()}
                    return
                got += rows
                yield t

        return "stream", _Stream(node.schema, gen())

    def _eval_concat(self, node: Node):
        parts = [self._eval(i) for i in node.inputs]

        def gen():
            for (k, v), inp in zip(parts, node.inputs):
                if k == "small":
                    yield v
                else:
                    yield from self._realized(v)

        return "stream", _Stream(node.schema, gen())


# ---- host-side helpers -------------------------------------------------


def _concat_tables(
    tables: List[Dict[str, np.ndarray]], schema: Optional[Schema]
) -> Dict[str, np.ndarray]:
    tables = [t for t in tables if t and len(next(iter(t.values())))]
    if not tables:
        if schema is None:
            return {}
        return _empty_table(schema)
    names = list(tables[0].keys())
    return {n: np.concatenate([np.asarray(t[n]) for t in tables])
            for n in names}


def _empty_table(schema: Schema) -> Dict[str, np.ndarray]:
    out = {}
    for f in schema.fields:
        if f.ctype is ColumnType.STRING:
            out[f.name] = np.array([], object)
        elif f.ctype.is_bytes:
            out[f.name] = np.zeros((0, f.ctype.width), np.uint8)
        else:
            out[f.name] = np.array([], f.ctype.numpy_dtype)
    return out


def _table_as_stream(table, schema) -> "_Stream":
    return _Stream(schema, iter([table]))


def _sort_key_view(col: np.ndarray) -> np.ndarray:
    """An order-preserving comparable view of a sort-key column.
    String columns become object arrays: numpy compares them lexically
    and reductions (min/max for the exact bucket extent) dispatch to
    Python comparisons, which fixed-width ``<U``/``<S`` dtypes lack."""
    a = np.asarray(col)
    if a.dtype.kind in ("U", "S"):
        return a.astype(object)
    return a


def _sample_splitters(col: np.ndarray, buckets: int) -> np.ndarray:
    """B-1 value splitters from the first chunk (the 0.1% sampler of
    ``DryadLinqSampler.cs:38-42`` collapsed onto the leading morsel;
    estimation error is repaired by observed-volume re-splits)."""
    n = len(col)
    if n == 0:
        return np.asarray([])
    take = min(n, 1 << 16)
    idx = np.linspace(0, n - 1, take).astype(np.int64)
    return _splitters_from_sample(col[idx], buckets)


def _splitters_from_sample(sample: np.ndarray, buckets: int) -> np.ndarray:
    if len(sample) == 0:
        return np.asarray([])
    s = np.sort(sample)
    pos = np.linspace(0, len(s) - 1, buckets + 1).astype(np.int64)[1:-1]
    return np.unique(s[pos])


def _bucket_sample(spill: SpillDir, bucket: int, primary: str) -> np.ndarray:
    vals = []
    for piece in spill.read_bucket_pieces(bucket):
        col = np.asarray(piece[primary])
        take = min(len(col), 4096)
        if take:
            vals.append(col[np.linspace(0, len(col) - 1, take).astype(np.int64)])
    return np.concatenate(vals) if vals else np.asarray([])


def _host_key_hash64(
    table, keys, salt: int = 0, dictionary=None
) -> np.ndarray:
    """Deterministic 64-bit row hash over the key columns.  Any mixing
    works as long as every consumer uses the same function; equal
    logical values must produce equal words, so strings hash via the
    engine dictionary (``Hash64.cs`` precedent) and numerics widen to a
    canonical 64-bit pattern.  Feeds both the exchange bucket ids and
    the combine-tree key-range histograms (same high bits, coarser
    modulus), so range-level decisions align with exchange routing."""
    n = len(np.asarray(table[keys[0]]))
    h = np.full(n, np.uint64(0x84222325 + salt * 0x1000193), np.uint64)
    for kcol in keys:
        a = np.asarray(table[kcol])
        if a.dtype == object or a.dtype.kind in ("U", "S"):
            uniq, inv = np.unique(a.astype(object), return_inverse=True)
            hs = np.asarray(
                [dictionary.add(str(s)) for s in uniq], np.uint64
            )
            w = hs[inv]
        elif a.dtype.kind == "f":
            w = np.ascontiguousarray(a.astype(np.float64)).view(np.uint64)
        elif a.dtype.kind == "b":
            w = a.astype(np.uint64)
        else:
            w = a.astype(np.int64).view(np.uint64)
        h = (h ^ w) * _MIX
        h ^= h >> np.uint64(29)
    return h


def _host_hash_buckets(
    table, keys, buckets: int, salt: int = 0, dictionary=None
) -> np.ndarray:
    """Deterministic row hash over the key columns -> bucket ids."""
    h = _host_key_hash64(table, keys, salt=salt, dictionary=dictionary)
    return ((h >> np.uint64(33)) % np.uint64(buckets)).astype(np.int64)


def _encode_store_part(table, schema: Schema, dictionary):
    """Host table -> physical store columns via the shared ingest
    encoding, so streamed parts read back through the same ``store``
    binding path as engine-written ones."""
    from dryad_tpu.columnar.batch import encode_physical

    out = {}
    for f in schema.fields:
        out.update(encode_physical(f, np.asarray(table[f.name]), dictionary))
    return out
