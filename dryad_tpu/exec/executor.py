"""GraphExecutor — the job manager.

The TPU-native GraphManager (reference ``GraphManager/vertex/DrGraph.h:75``,
``DrGraphExecutor.cpp:15-65``): executes the stage DAG in dependency
order.  Where the reference schedules per-vertex processes with cohorts,
property mailboxes and channel files, this driver launches one compiled
SPMD program per stage on the mesh and keeps intermediates in HBM.

Fault tolerance keeps the reference *semantics* in TPU form:
- versioned re-execution with a failure budget
  (``DrVertexRecord.h:164-194`` version generator; ``DrGraph.h:42``
  m_maxActiveFailureCount) — each stage attempt is a numbered version;
  injected/real failures re-run it, and the budget aborts the job;
- adaptive shapes: shuffle/join overflow is a *retryable* outcome that
  re-compiles the stage with a boosted capacity from a bounded palette
  (the dynamic fan-out sizing of ``DrDynamicRangeDistributor.cpp:54``
  turned into a shape-palette choice);
- per-stage duration statistics feed the straggler model
  (``exec.stats``) and every transition lands in the event log
  (``exec.events``, the Calypso reporter analog).
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from dryad_tpu.columnar.batch import ColumnBatch
from dryad_tpu.exec import faults
from dryad_tpu.exec.checkpoint import CheckpointStore, stage_fingerprint
from dryad_tpu.exec.events import EventLog
from dryad_tpu.exec.failure import (
    Attempt,
    FailureKind,
    JobFailedError,
    RetryPolicy,
    StageFailedError,
    classify,
)
from dryad_tpu.exec.kernels import (
    NON_OVERFLOW_OPS,
    OPERAND_PARAMS,
    build_fused_fn,
    build_stage_fn,
    stage_operand_objs,
)
from dryad_tpu.exec.operands import DeviceOperandPool, is_operand_capable
from dryad_tpu.exec.stats import StageStatistics
from dryad_tpu.obs import flightrec, tracectx
from dryad_tpu.obs.metrics import MetricsRegistry
from dryad_tpu.obs.span import Tracer
from dryad_tpu.parallel.mesh import mesh_axes, num_partitions
from dryad_tpu.parallel.stage import compile_fused, compile_stage
from dryad_tpu.plan.fuse import (
    ADAPT_OK_OPS,
    SHRINKING_OPS,
    FusedStage,
    fuse as fuse_plan,
)
from dryad_tpu.plan.lower import Stage, StageGraph, StageOp
from dryad_tpu.plan.xchgplan import resolve_window
from dryad_tpu.utils.config import DryadConfig
from dryad_tpu.utils.logging import get_logger

log = get_logger("dryad_tpu.exec")


def _stage_has_miss_guard(stage) -> bool:
    """Stages whose compiled program accumulates a dense-domain miss
    counter needing the deferred readback: STRING dictionary coding, or
    the guarded int auto-dense bucket reduce."""
    return any(
        op.kind == "string_code"
        or (op.kind == "group_reduce_dense" and op.params.get("guard"))
        for op in stage.ops
    )


class DeferredFinish:
    """Tail of an ``execute(defer_miss=True)`` job: the dict-miss
    counters whose readback the caller batches into its own
    device->host transfer, plus the guarded checkpoint writes that
    must not happen until those counters prove clean.

    Contract: fetch ``miss_arrays()`` alongside the job outputs (one
    ``device_get``), then call ``finish(host_vals)`` — it raises
    ``StageFailedError`` on a nonzero counter (discarding the gated
    checkpoints) and writes them otherwise.  ``finish()`` with no
    argument falls back to its own readback."""

    def __init__(self, executor, pending, ckpts):
        self._executor = executor
        self._pending = pending
        self._ckpts = ckpts

    def miss_arrays(self):
        return [m for _, m in self._pending]

    def abort(self, reason: str) -> None:
        """Terminal path for a failed output transfer: drop the gated
        checkpoint writes (never persist unproven results) and emit
        ``job_failed`` so the event log distinguishes a transfer
        failure from a job that simply hung (ADVICE r4)."""
        self._ckpts = []
        self._pending = []
        self._executor.events.emit(
            "job_failed", reason=reason, failure_kind="transient"
        )

    def finish(self, host_vals=None) -> None:
        if host_vals is None:
            host_vals = (
                jax.device_get(self.miss_arrays()) if self._pending else []
            )
        if len(host_vals) != len(self._pending):
            raise AssertionError(
                f"DeferredFinish.finish: {len(host_vals)} host values for "
                f"{len(self._pending)} pending miss counters — fetch "
                "miss_arrays() alongside the outputs"
            )
        for (name, _), m in zip(self._pending, host_vals):
            if int(m):
                self._ckpts = []  # poisoned results: never persist
                self._executor.events.emit(
                    "job_failed",
                    reason=f"dict miss in {name}",
                    failure_kind="deterministic",
                )
                self._executor._raise_miss(name, int(m))
        for stage, fp, outs in self._ckpts:
            self._executor._write_checkpoint(stage, fp, outs)
        self._ckpts = []
        self._pending = []
        self._executor.events.emit("job_complete")


# StageFailedError/JobFailedError live in exec.failure (imported above
# and re-exported here for the existing call sites and tests).


def _lowering_key_hash(key) -> str:
    """Short per-run digest of a compile-cache key — the lowering-key
    identity ``xla_compile`` events are grouped by (object reprs embed
    ids, so the hash is stable within a run, which is the scope the
    recompile accounting needs)."""
    import zlib

    return format(zlib.crc32(repr(key).encode()) & 0xFFFFFFFF, "08x")


def _fold_stats(stage) -> Dict[str, int]:
    """Of a stage with a builtin-aggregate group-by, what its widest
    fold carries a slot (``ops/segmented.py::fold_stats`` of the
    ``group_reduce`` with the most state words); nothing for any other
    stage.  Static: read from the stage's ops, not from a trace."""
    from dryad_tpu.ops.segmented import fold_stats

    folds = [
        fold_stats(op.params["keys"], op.params["aggs"])
        for op in stage.ops if op.kind == "group_reduce"
    ]
    return max(folds, key=lambda f: f["agg_state_words"], default={})


def _ici_bytes(xchg_rounds) -> int:
    """Bytes one chip puts on the ICI in a dispatch: the sum over the
    program's ``exchange_round`` accounting."""
    return sum(int(rnd["ici_bytes"]) for rnd in xchg_rounds)


class _CompileTimed:
    """First-call timing shim over a freshly compiled stage program.

    ``jax.jit`` traces + compiles on the FIRST invocation at these
    shapes (the cache key includes the shape key, so a fresh entry
    always pays it there); that call's wall time is recorded as the
    compile cost for this lowering key and emitted as ONE
    ``xla_compile`` event — the signal that makes the vocab-widening
    recompile open item (ROADMAP) measurable.  Subsequent calls pay a
    single attribute check.
    """

    __slots__ = (
        "fn", "_exec", "_name", "_key", "_build_s", "_pending",
        "xchg_rounds", "join_plans", "sorted_words", "elided", "seen_log",
    )

    def __init__(self, fn, executor, name, key_hash, build_s,
                 xchg_rounds=None, join_plans=None, sorted_words=None,
                 elided=None, seen_log=None):
        self.fn = fn
        self._exec = executor
        self._name = name
        self._key = key_hash
        self._build_s = build_s
        self._pending = True
        # Static exchange-round byte accounting, filled at trace time by
        # the stage builder's cell (kernels._exchange): one dict per
        # round, emitted as exchange_round events on every dispatch.
        self.xchg_rounds = xchg_rounds if xchg_rounds is not None else []
        # What each join kernel of the stage decided at trace time
        # (kernels._apply_join_strategy): emitted as join_plan events
        # by the first call, which is the one that traces.
        self.join_plans = join_plans if join_plans is not None else []
        # [4-byte words of the widest row a sort of the stage carries]
        # (kernels.build_stage_fn), filled at trace time like the two
        # above: the ``row_words`` stat of every ``dispatch`` span.
        self.sorted_words = sorted_words if sorted_words is not None else []
        # [exchanges the trace skipped because the mesh has one
        # partition] (kernels._elided), filled at trace time too: the
        # ``xchg_elided`` stat of every ``dispatch`` span.
        self.elided = elided if elided is not None else []
        # Of each array of the program's third replicated output, what
        # only the trace knows (``kernels.StageContext.seen_log``): its
        # ``kind`` (``exchange`` / ``join``) and the ``capacity`` a chip
        # holds the received rows, or the pairs, in.
        self.seen_log = seen_log if seen_log is not None else []

    @property
    def row_words(self) -> int:
        return max(self.sorted_words, default=0)

    @property
    def xchg_elided(self) -> int:
        return sum(self.elided)

    def __call__(self, *args):
        if not self._pending:
            return self.fn(*args)
        self._pending = False
        ex = self._exec
        t0 = time.monotonic()
        with ex.tracer.span(self._name, cat="compile"):
            out = self.fn(*args)
        dt = time.monotonic() - t0
        ex.metrics.add("xla_compiles", 1.0, stage=self._name)
        ex.metrics.add("xla_compile_s", dt, stage=self._name)
        ex.events.emit(
            "xla_compile", stage=self._name, key=self._key,
            qid=tracectx.current_qid(),
            trace_s=round(self._build_s, 6), compile_s=round(dt, 6),
        )
        for plan in self.join_plans:
            ex.events.emit(
                "join_plan", stage=self._name, key=self._key,
                qid=tracectx.current_qid(), **plan,
            )
        return out


def _phys_np_dtype(col: str, schema):
    """numpy dtype of one physical device column."""
    from dryad_tpu.columnar.schema import ColumnType

    if "#" in col:
        return np.dtype(np.uint32)
    return {
        ColumnType.INT32: np.dtype(np.int32),
        ColumnType.FLOAT32: np.dtype(np.float32),
        ColumnType.BOOL: np.dtype(np.bool_),
        ColumnType.UINT32: np.dtype(np.uint32),
    }[schema.field(col).ctype.storage]


class GraphExecutor:
    def __init__(
        self,
        mesh,
        config: Optional[DryadConfig] = None,
        events: Optional[EventLog] = None,
        subquery_runner: Optional[Callable] = None,
        loop_lowerer: Optional[Callable] = None,
    ):
        self.mesh = mesh
        self.config = config or DryadConfig()
        self.events = events or EventLog(None)
        # structured tracing + counters (obs): spans serialize into the
        # event stream; the registry feeds JobMetrics attribution
        self.tracer = Tracer(self.events)
        self.metrics = MetricsRegistry()
        self.P = num_partitions(mesh)
        self._compiled: Dict[Tuple, Any] = {}
        # Static-vs-operand split for plan params: OPERAND-registered
        # params (the string coding tables) key the compile cache by
        # shape-palette TIER and travel as call-time device inputs via
        # the content-addressed operand pool — vocabulary widening
        # within a tier reuses the compiled program and scatters only
        # the widened table delta to the device.  Off = the legacy
        # baked-constant path (key by content; recompile per widen).
        self.runtime_operands = bool(
            getattr(self.config, "stringcode_runtime_tables", True)
        )
        self.operand_pool = DeviceOperandPool(mesh, metrics=self.metrics)
        # health probes for the flight recorder's microsnapshots
        # (no-ops when no recorder is installed): compiled-program and
        # operand-pool residency on THIS executor (last one wins when
        # a process holds several — fine for forensics)
        flightrec.probe(
            "xla_programs", lambda: len(self._compiled)
        )
        flightrec.probe(
            "operand_pool",
            lambda: {
                "tiers": len(self.operand_pool._tiers),
                "hits": self.operand_pool.hits,
                "full_uploads": self.operand_pool.full_uploads,
                "delta_scatters": self.operand_pool.delta_scatters,
            },
        )
        # Runtime plan rewriter (dryad_tpu.rewrite), wired by the
        # context AFTER construction — the engine never imports the
        # policy layer, it only consults the handle.  Consulted for
        # per-stage starting-boost floors (overflow pre-widening) and
        # the auto exchange-window hint.
        self.rewriter = None
        # Measured-headroom provider (obs.telemetry.HeadroomProvider),
        # wired by the context alongside the rewriter.  Consulted by
        # the auto exchange-window policy; None (or a provider with no
        # measurement yet) falls back to the configured HBM budget.
        self.headroom = None
        self._rewrites_applied: set = set()
        # do_while loop-state compaction programs (see _compact_loop_state)
        self._compact_cache: Dict[Tuple, Any] = {}
        self.stats: Dict[str, StageStatistics] = {}
        # Callback used by do_while stages to run body/cond subplans.
        self.subquery_runner = subquery_runner
        self.loop_lowerer = loop_lowerer
        self._profiling = False
        # (stage name, device int32) dictionary-miss counters awaiting
        # their deferred readback (_check_pending_miss)
        self._pending_miss: List[Tuple[str, Any]] = []
        # (stage, fp, outs) checkpoint saves of miss-GUARDED stages,
        # persisted only after their counters drain clean
        self._pending_ckpt: List[Tuple[Any, Any, Any]] = []
        self.checkpoints = (
            CheckpointStore(self.config.checkpoint_dir, events=self.events)
            if self.config.checkpoint_dir
            else None
        )
        # Failure-domain retry policy (exec.failure): transient stage
        # failures back off exponentially with seeded jitter under the
        # per-stage budget; deterministic repeats fail fast.
        self.retry_policy = RetryPolicy(
            max_attempts=self.config.max_stage_failures,
            backoff_base=self.config.retry_backoff_base,
            backoff_max=self.config.retry_backoff_max,
            jitter=self.config.retry_jitter,
            seed=self.config.retry_seed,
        )
        # injectable sleep: backoff-timing tests record instead of wait
        self._sleep: Callable[[float], None] = time.sleep

    # -- compilation cache -------------------------------------------------
    def _stage_key(self, stage: Stage, split_operands: bool = True) -> Tuple:
        """Structural stage identity: op kinds + static params + fn object
        ids.  Re-lowering the same logical plan yields new stage ids but
        identical structure (fn objects live on the plan nodes), so
        repeated collect()/do_while iterations hit the cache.

        Params registered as OPERANDs (``kernels.OPERAND_PARAMS``, with
        the runtime-tables split on) key by their shape-palette TIER
        (``operand_signature()``) instead of content — the compiled fn
        takes their arrays as call-time device inputs, so every table
        of a tier shares one program.  ``split_operands=False`` keeps
        the content key (the do_while device path, which builds its
        loop body without operand plumbing and must not share programs
        across table contents)."""
        if isinstance(stage, FusedStage):
            # Member-local slot numbers overlap across members, so the
            # chained-op key alone would alias differently wired
            # regions; fold the member keys (each with its own
            # out_slots) plus the region wiring/exports.
            return (
                "fused",
                tuple(
                    self._stage_key(m, split_operands)
                    for m in stage.members
                ),
                tuple(stage.wiring),
                tuple(stage.exports),
            )
        split = split_operands and self.runtime_operands
        parts = []
        for op in stage.ops:
            items = []
            for k, v in sorted(op.params.items()):
                if (
                    split
                    and (op.kind, k) in OPERAND_PARAMS
                    and is_operand_capable(v)
                ):
                    items.append((k, ("operand", v.operand_signature())))
                    continue
                if isinstance(v, list):
                    v = tuple(v)
                try:
                    hash(v)
                except TypeError:
                    v = repr(v)  # unhashable static param: structural repr
                # Hashable objects (incl. functions, AggSpecs) go into the
                # key BY REFERENCE — the key holds them alive, so a freed
                # object's id can never alias a new one (id()-keyed caches
                # silently serve stale compiled programs after GC reuse).
                items.append((k, v))
            parts.append((op.kind, tuple(items)))
        return (tuple(parts), tuple(stage.out_slots))

    def graph_key(self, graph) -> Tuple:
        """Public structural identity of a LOWERED stage graph — one
        ``_stage_key`` per stage, in graph order.  The serving tier's
        result-cache keying surface: built on the exact machinery the
        compile cache uses, so two lowerings share a graph key iff
        their stages would share compiled programs.  fn-valued params
        key BY REFERENCE (see ``_stage_key``), so closure-bearing plans
        match only when re-run from the same Query object — prepared-
        statement semantics — while value-hashable params (group_by
        agg tuples, take counts, ...) match across rebuilt queries."""
        return tuple(self._stage_key(s) for s in graph.stages)

    def _stage_rep(self, stage: Stage) -> Tuple:
        """Call-time replicated operand arrays for a dispatch of
        ``stage`` — the flattened device buffers of every OPERAND
        param, in ``stage_operand_objs`` order (the same enumeration
        ``build_stage_fn`` bound the trace against)."""
        if not self.runtime_operands:
            return ()
        return tuple(
            a
            for obj in stage_operand_objs(stage)
            for a in self.operand_pool.get(obj)
        )

    def _get_compiled(
        self, stage: Stage, boost: int, shape_key: Tuple,
        fan: Optional[int] = None,
    ):
        """``fan``: observed-volume width override — exchanges/resizes
        lowered at full width (nparts=None) concentrate onto ``fan``
        partitions instead.  Fans quantize to powers of two, so the
        compile cache forms a small width palette reused across jobs
        (the re-dispatch-without-recompile requirement of
        ``DrDynamicRangeDistributor.cpp:54-110`` adaptation)."""
        run_stage = stage
        if fan:
            run_stage = self._fan_adapted_stage(stage, fan)
        window = self._resolve_window(shape_key, boost)
        # the resolved window shapes the lowered exchange: it must be
        # part of the compile identity (the auto policy / rewriter
        # hint may resolve differently across dispatches)
        key = (self._stage_key(run_stage), boost, shape_key, window)
        hit = self._compiled.get(key)
        if hit is None:
            t0 = time.monotonic()
            objs = tuple(
                stage_operand_objs(run_stage)
                if self.runtime_operands else ()
            )
            axes = mesh_axes(self.mesh)
            sizes = tuple(self.mesh.shape[a] for a in axes)
            cell: List[Dict[str, int]] = []
            joins: List[Dict[str, Any]] = []
            sorts: List[int] = []
            elided: List[int] = []
            seen_log: List[Dict[str, Any]] = []
            if isinstance(run_stage, FusedStage):
                fn = build_fused_fn(
                    run_stage, self.P, self.config.shuffle_slack, boost,
                    axes, sizes, operand_objs=objs,
                    window=window, xchg_cell=cell, join_cell=joins,
                    sort_cell=sorts, elided_cell=elided, seen_cell=seen_log,
                )
                compiled = compile_fused(self.mesh, fn)
            else:
                fn = build_stage_fn(
                    run_stage, self.P, self.config.shuffle_slack, boost,
                    axes, sizes, operand_objs=objs,
                    window=window, xchg_cell=cell, join_cell=joins,
                    sort_cell=sorts, elided_cell=elided, seen_cell=seen_log,
                )
                compiled = compile_stage(self.mesh, fn)
            hit = _CompileTimed(
                compiled, self, run_stage.name,
                _lowering_key_hash(key), time.monotonic() - t0,
                xchg_rounds=cell, join_plans=joins, sorted_words=sorts,
                elided=elided, seen_log=seen_log,
            )
            self._compiled[key] = hit
        return hit

    @staticmethod
    def _shape_key(inputs: Tuple[ColumnBatch, ...]) -> Tuple:
        return tuple(
            (tuple(sorted(b.data.keys())), b.capacity) for b in inputs
        )

    def _resolve_window(self, shape_key: Tuple, boost: int) -> int:
        """Effective staged-exchange window for one compilation.

        Static ``config.exchange_window >= 0`` passes through; ``-1``
        delegates to :func:`plan.xchgplan.resolve_window` with a
        conservative per-destination bucket estimate derived from the
        shape key (capacity x columns x 8B, widened by slack/boost —
        the same quantities the lowered exchange sizes its send buffer
        from), the configured HBM budget, the runtime rewriter's
        retune hint when one is pinned, and the MEASURED live headroom
        when a telemetry provider is wired (precedence: hint >
        measured > budget).  Live headroom is quantized to a power of
        two before it enters the policy — the resolved window rides
        the compile-cache key, and raw byte-exact measurements would
        fragment the palette into one entry per sample.  Deterministic
        in its (quantized) inputs.
        """
        cfgw = int(getattr(self.config, "exchange_window", 0))
        if cfgw >= 0:
            return cfgw
        slack = float(getattr(self.config, "shuffle_slack", 1.25))
        bucket_bytes = 1
        for cols, capacity in shape_key:
            rows = -(-int(capacity) * max(1, int(boost)) // max(1, self.P))
            est = int(rows * slack) * max(1, len(cols)) * 8
            bucket_bytes = max(bucket_bytes, est)
        budget = (
            int(getattr(self.config, "exchange_hbm_budget_mb", 256)) << 20
        )
        hint = None
        if self.rewriter is not None:
            hint = self.rewriter.exchange_window_hint()
        headroom = None
        if self.headroom is not None:
            h = self.headroom.headroom_bytes()
            if h is not None and int(h) > 0:
                headroom = 1 << (int(h).bit_length() - 1)
        return resolve_window(
            cfgw, self.P, bucket_bytes, budget, hint=hint,
            headroom_bytes=headroom,
        )

    # -- execution ---------------------------------------------------------
    def execute(
        self,
        graph: StageGraph,
        bindings: Dict[int, ColumnBatch],
        binding_fps: Optional[Dict[int, Optional[str]]] = None,
        defer_miss: bool = False,
    ) -> Any:
        """Run all stages; returns (stage_id, out_idx) -> output batch.

        ``bindings``: plan-input node id -> mesh-sharded global batch.
        ``binding_fps``: plan-input node id -> content SHA-1 (or None if
        the binding can't be fingerprinted) for checkpoint identity.

        ``defer_miss=True`` returns ``(results, DeferredFinish)``
        instead: the dict-miss readback (and the checkpoint writes it
        gates) are handed to the caller, who batches the counters into
        its own device->host transfer and calls ``finish(host_vals)``
        — saving one device->host round-trip per job versus the
        synchronous check.
        """
        # Whole-DAG fusion (plan.fuse): maximal runs of device-eligible
        # stages collapse into FusedStage regions — one compiled
        # program, one dispatch per region.  Per-execute cost is
        # O(stages); the compile cache keys regions structurally, so
        # repeated submissions (and the out-of-core driver's cached
        # chunk plans) reuse fused programs across calls.  Off = the
        # legacy per-stage path, kept as the differential baseline.
        if getattr(self.config, "plan_fuse", True) and len(graph.stages) > 1:
            with self.tracer.span(
                "fuse", cat="plan", stages=len(graph.stages)
            ):
                graph, fuse_report = fuse_plan(
                    graph, self.config,
                    single_axis=len(mesh_axes(self.mesh)) == 1,
                )
            for br in fuse_report.breaks:
                self.events.emit(
                    "fuse_break", after=br["after"], before=br["before"],
                    reason=br["reason"],
                )
        # Topology rides the event log so jobview can redraw the DAG
        # post-hoc — the reference JobBrowser reconstructs the graph
        # from GM logs the same way (``JobBrowser/JOM/jobinfo.cs:62``).
        topology = [
            {
                "id": s.id,
                "name": s.name,
                "deps": [
                    ["in", idx] if ref == "plan_input" else [ref, idx]
                    for ref, idx in s.input_refs
                ],
            }
            for s in graph.stages
        ]
        self.events.emit(
            "job_start", stages=len(graph.stages), topology=topology
        )
        results: Dict[Tuple[int, int], ColumnBatch] = {}
        # do_while subqueries re-enter execute(); the adaptation state
        # is per-graph (stage ids restart per lowering), so save and
        # restore the outer job's view around the nested run
        adapt_state = (
            getattr(self, "_observed_rows", None),
            getattr(self, "_count_wanted", None),
            getattr(self, "_adapt_safe", None),
        )
        self._prepare_width_adapt(graph)
        # do_while re-enters execute() through subquery_runner; only the
        # top-level call may own the profiler session.
        profile = (
            jax.profiler.trace(self.config.profile_dir)
            if self.config.profile_dir and not self._profiling
            else contextlib.nullcontext()
        )
        self._profiling = bool(self.config.profile_dir)
        # stage id -> Merkle fingerprint (None = not checkpointable)
        stage_fps: Dict[int, Optional[str]] = {}
        # Re-entrancy (do_while subqueries) and failure hygiene: drain
        # only the counters THIS call added; on failure discard them so
        # a stale counter can't fail a later unrelated job.
        mark = len(self._pending_miss)
        mark_ckpt = len(self._pending_ckpt)
        try:
            with profile:
                self._execute_stages(graph, bindings, results, binding_fps, stage_fps)
        except BaseException:
            del self._pending_miss[mark:]
            del self._pending_ckpt[mark_ckpt:]
            raise
        finally:
            if adapt_state[0] is not None:
                (self._observed_rows, self._count_wanted,
                 self._adapt_safe) = adapt_state
            if not isinstance(profile, contextlib.nullcontext):
                self._profiling = False
        if defer_miss:
            pending = self._pending_miss[mark:]
            del self._pending_miss[mark:]
            ckpts = self._pending_ckpt[mark_ckpt:]
            del self._pending_ckpt[mark_ckpt:]
            # job_complete is emitted by DeferredFinish.finish() once
            # the miss counters prove clean — a miss-failed job must
            # not be logged as completed (jobview counts on it).
            return results, DeferredFinish(self, pending, ckpts)
        try:
            self._check_pending_miss(mark)
        except BaseException:
            # guarded stages' results are poisoned — never persist them
            del self._pending_ckpt[mark_ckpt:]
            raise
        # miss counters clean: guarded stages' checkpoints may persist
        for stage, fp, outs in self._pending_ckpt[mark_ckpt:]:
            self._write_checkpoint(stage, fp, outs)
        del self._pending_ckpt[mark_ckpt:]
        self.events.emit("job_complete")
        return results

    # -- observed-volume stage-width adaptation -----------------------------
    #
    # The reference resizes a consumer stage from MEASURED upstream
    # volume and rewires the graph (DrDynamicRangeDistributor.cpp:54-110
    # copies = sampledSize/samplingRate/dataPerVertex;
    # DrPipelineSplitManager.h:23).  Here: completed stages report their
    # observed output row counts (riding readbacks that happen anyway),
    # and a consumer whose exchanges were lowered at full width because
    # the STATIC estimator had no bound re-dispatches at a reduced
    # power-of-two width when the observed volume is tail-sized.
    # Producers are untouched; correctness is internal to the adapted
    # stage — a join side whose exchange was ELIDED on partition claims
    # gets a matching reduced-width exchange inserted (the runtime
    # graph-rewiring of the reference's distributors).

    # op kinds proven width-insensitive (everything else blocks
    # adaptation: zip/sliding_window/rank/take-style ops depend on row
    # placement or engine order across the full mesh width).  ONE
    # definition shared with the fuse pass, whose adapt-seam rule must
    # mirror this gate (plan.fuse leaves adaptation candidates unfused).
    _ADAPT_OK_OPS = ADAPT_OK_OPS

    def _prepare_width_adapt(self, graph: StageGraph) -> None:
        self._observed_rows: Dict[Tuple[int, int], int] = {}
        self._count_wanted: set = set()
        # (producer sid, out idx) -> True iff EVERY consumer re-routes
        # that input through a leading exchange.  An ADAPTED stage's
        # output no longer satisfies the full-width hash claim its plan
        # node advertises, so a consumer that elided its exchange on
        # that claim would silently mis-join — such producers must not
        # adapt (the static twin of lower.py's `reduced` guard).
        self._adapt_safe: Dict[Tuple[int, int], bool] = {}
        single_axis = len(mesh_axes(self.mesh)) == 1
        limit = getattr(self.config, "tail_fanout_rows", 0)
        for st in graph.stages:
            for j, (ref, idx) in enumerate(st.input_refs):
                if ref == "plan_input":
                    continue
                key = (ref, idx)
                ok = self._slot_reroutes(st, j)
                self._adapt_safe[key] = (
                    self._adapt_safe.get(key, True) and ok
                )
            if single_axis and limit and self._adaptable(st):
                for ref, _idx in st.input_refs:
                    if ref != "plan_input":
                        self._count_wanted.add(ref)

    def _consumers_allow_adapt(self, stage: Stage) -> bool:
        """Every consumer of this stage's outputs re-routes them
        through a leading exchange (missing key = no consumers)."""
        return all(
            self._adapt_safe.get((stage.id, i), True)
            for i in range(len(stage.out_slots))
        )

    @staticmethod
    def _slot_reroutes(stage: Stage, slot: int) -> bool:
        """True when the first op touching ``slot`` is an exchange —
        rows re-route by key, so upstream placement is irrelevant."""
        if isinstance(stage, FusedStage):
            # member-local slot numbers make the scan meaningless for a
            # region; be strict (pins the producer to full width)
            return False
        for op in stage.ops:
            touched = [
                op.params.get(k)
                for k in ("slot", "left_slot", "right_slot")
                if k in op.params
            ]
            if slot in touched:
                return op.kind in ("exchange_hash", "exchange_range")
        return False  # pass-through or unknown: be strict

    def _adaptable(self, stage: Stage) -> bool:
        if isinstance(stage, FusedStage):
            # a region compiles at its static widths; the fuse pass
            # leaves genuine adaptation candidates unfused instead
            return False
        return all(
            op.kind in self._ADAPT_OK_OPS for op in stage.ops
        ) and any(
            op.kind in ("exchange_hash", "exchange_range")
            and not op.params.get("nparts")
            for op in stage.ops
        )

    def _fan_adapted_stage(self, stage: Stage, fan: int) -> Stage:
        """Stage copy at reduced width: full-width exchanges/resizes
        concentrate onto ``fan`` partitions, and a join slot whose
        exchange was elided on static partition claims gets a matching
        reduced-width exchange inserted so both sides stay
        co-partitioned."""
        ops: List[StageOp] = []
        exchanged = set()
        for op in stage.ops:
            if op.kind == "join":
                for side, keys_p in (
                    ("left_slot", "left_keys"), ("right_slot", "right_keys")
                ):
                    sl = op.params[side]
                    if sl not in exchanged and keys_p in op.params:
                        ops.append(StageOp("exchange_hash", {
                            "slot": sl,
                            "keys": list(op.params[keys_p]),
                            "nparts": fan,
                        }))
                        ops.append(StageOp("resize", {
                            "slot": sl, "factor": 1.0, "nparts": fan,
                        }))
                        exchanged.add(sl)
            if op.kind in ("exchange_hash", "exchange_range", "resize"):
                exchanged.add(op.params.get("slot"))
                if not op.params.get("nparts"):
                    ops.append(StageOp(op.kind, {**op.params, "nparts": fan}))
                    continue
            ops.append(op)
        return Stage(
            stage.id, stage.name, list(stage.input_refs), ops=ops,
            out_slots=list(stage.out_slots), growth=stage.growth,
        )

    # aggregation-shaped ops that shrink data by orders of magnitude;
    # shared with plan.fuse (the adapt-seam rule keys on the same set)
    _SHRINKING_OPS = SHRINKING_OPS

    def _drain_for_adapt(self, stage: Stage, window) -> bool:
        """Worth syncing the window early: this stage could adapt its
        width, every input's count is pending in the window (or already
        known), and at least one producer is aggregation-shaped (the
        shapes that shrink data by orders of magnitude — draining for a
        map stage would pay the sync the window exists to avoid)."""
        limit = getattr(self.config, "tail_fanout_rows", 0)
        if not limit or len(mesh_axes(self.mesh)) != 1:
            return False
        if not self._adaptable(stage):
            return False
        if not self._consumers_allow_adapt(stage):
            return False  # a consumer pinned this stage to full width
        in_window = {w["stage"].id: w for w in window}
        shrinker = False
        for ref, idx in stage.input_refs:
            if ref == "plan_input":
                return False
            if (ref, idx) in self._observed_rows:
                continue  # already counted (earlier drain)
            w = in_window.get(ref)
            if w is None or not w.get("counts"):
                return False
            if any(
                op.kind in self._SHRINKING_OPS
                for op in w["stage"].ops
            ):
                shrinker = True
        return shrinker

    def _record_observed(
        self, stage: Stage, host_counts, capacities=None
    ) -> None:
        for idx, c in enumerate(host_counts):
            self._observed_rows[(stage.id, idx)] = int(c)
            # rows-out + layout accounting ride the readback that
            # happened anyway: valid vs layout rows is the padding-
            # waste ratio JobMetrics reports
            self.metrics.add("rows_out", int(c), stage=stage.name)
            self.metrics.add("valid_rows", int(c))
            if capacities is not None and idx < len(capacities):
                self.metrics.add("layout_rows", int(capacities[idx]))

    def _exchange_observed(self, drain, dispatches, overflowed) -> None:
        """What the exchanges and join kernels of the drained dispatches
        saw (``kernels._observe_exchange``, ``kernels._traced_join``),
        already on the host: it rode the readback of the overflow flag.
        ``dispatches``: ``(stage, boost, seen, seen_log)`` each, ``seen``
        one array an exchange (``(3, P)``: rows the combiner before it
        was handed, rows sent, rows received; a column a chip) or a join
        (``(1, P)``: candidate pairs in the pair buffer), ``seen_log``
        the trace's record of each (``kind``, ``capacity``, and of an
        exchange ``resize_sorts``: 1 where the ``resize`` after it
        traced a compaction, ``kernels._reader_sorts``).  One
        ``exchange_observed`` event a dispatch that ran an exchange,
        summed over its exchanges, and one ``join_observed`` a dispatch
        that ran a join; onto the ``drain`` span the sums over all of
        them, the highest boost among them, the ``stage_overflow``
        events of the job so far (this drain's own counted), and beside
        the sums, which an even exchange flattens, the worst single
        exchange: ``recv_balance_max`` (fullest chip's rows x chips /
        rows sent) and ``recv_fill_max`` (fullest chip's rows / the
        capacity its ``resize`` leaves)."""
        self._job_overflows += int(overflowed)

        def fields(rows):  # of a (3, P) array of counts, a column a chip
            return dict(
                combine_rows_in=int(rows[0].sum()),
                combine_rows_out=int(rows[1].sum()),
                recv_rows=[int(r) for r in rows[2]],
            )

        ran, joined = [], []
        for stage, boost, seen, seen_log in dispatches:
            of = {"exchange": [], "join": []}
            for rows, said in zip(seen, seen_log):
                of[said["kind"]].append(
                    (np.asarray(rows, dtype=np.int64), said["capacity"])
                )
            if of["exchange"]:
                sorts = sum(said.get("resize_sorts", 0) for said in seen_log)
                ran.append((stage, boost, of["exchange"], sorts))
            if of["join"]:
                joined.append((stage, of["join"]))
        for stage, boost, exchanges, sorts in ran:
            self.events.emit(
                "exchange_observed", stage=stage.id, name=stage.name,
                exchanges=len(exchanges), resize_sorts=sorts, boost=boost,
                overflows=self._job_overflows,
                qid=tracectx.current_qid(),
                **fields(sum(rows for rows, _ in exchanges)),
            )
        for stage, joins in joined:
            self.events.emit(
                "join_observed", stage=stage.id, name=stage.name,
                joins=len(joins), slots=sum(slots for _, slots in joins),
                pairs=[int(n) for n in sum(rows[0] for rows, _ in joins)],
                qid=tracectx.current_qid(),
            )
        if ran:
            each = [x for _, _, exchanges, _ in ran for x in exchanges]
            total = fields(sum(rows for rows, _ in each))
            # the list reaches the span's event; a profiler annotation
            # keeps numbers only, so its largest entry goes beside it
            drain.add(
                exchanges=len(each),
                resize_sorts=sum(sorts for _, _, _, sorts in ran),
                boost=max(boost for _, boost, _, _ in ran),
                overflows=self._job_overflows,
                recv_rows_max=max(total["recv_rows"]), **total,
                recv_balance_max=max(
                    float(rows[2].max() * len(rows[2]) / max(rows[1].sum(), 1))
                    for rows, _ in each
                ),
            )
            fills = [float(rows[2].max() / capacity)
                     for rows, capacity in each if capacity]
            if fills:  # an exchange no ``resize`` follows has no room to fill
                drain.add(recv_fill_max=max(fills))
        if joined:
            each = [x for _, joins in joined for x in joins]
            drain.add(
                join_pairs=int(sum(rows.sum() for rows, _ in each)),
                join_pairs_max=int(max(rows.max() for rows, _ in each)),
                join_slots=sum(slots for _, slots in each),
            )

    def _adapt_fan_for(self, stage: Stage) -> Optional[int]:
        """Reduced width for this stage from its inputs' OBSERVED rows;
        None = run as lowered (full width or static reduction)."""
        limit = getattr(self.config, "tail_fanout_rows", 0)
        if not limit or len(mesh_axes(self.mesh)) != 1:
            return None
        if not self._adaptable(stage):
            return None
        if not self._consumers_allow_adapt(stage):
            return None
        total = 0
        for ref, idx in stage.input_refs:
            if ref == "plan_input":
                return None  # static bindings: lowering already decided
            c = self._observed_rows.get((ref, idx))
            if c is None:
                return None
            total += c
        from dryad_tpu.plan.lower import tail_width

        w = tail_width(total, self.config, self.P)
        if w is None:
            return None
        fan = 1 << (w - 1).bit_length()  # pow2 palette for cache reuse
        return fan if fan < self.P else None

    def _raise_miss(self, name: str, m: int) -> None:
        self.events.emit("dict_miss", stage_name=name, rows=m)
        raise StageFailedError(
            f"stage {name!r}: {m} rows fall outside the dense "
            "path's key domain (STRING values missing from the "
            "context dictionary, or INT32 keys past their "
            "ingest-time range — fabricated at run time?); the "
            "dense kernel would drop them. Register/ingest the "
            "values, or use group_by(salt=) to force the sort "
            "path."
        )

    def _check_pending_miss(self, mark: int = 0) -> None:
        """Drain deferred dictionary-miss counters added at or after
        ``mark`` (ONE batched readback for all guarded stages, after
        all dispatches).  A nonzero count means rows carried STRING
        hash words absent from the context dictionary — the dense
        kernel dropped them, so fail loudly instead of returning a
        silently wrong aggregate."""
        pending = self._pending_miss[mark:]
        del self._pending_miss[mark:]
        if not pending:
            return
        vals = jax.device_get([m for _, m in pending])
        for (name, _), m in zip(pending, vals):
            if int(m):
                self._raise_miss(name, int(m))

    def _execute_stages(self, graph, bindings, results, binding_fps, stage_fps):
        depth = max(1, self.config.overflow_sync_depth)
        self._job_overflows = 0  # drains of this job that saw the flag set
        # Speculative dispatch window (DrMessagePump.h:116-180 pump
        # concurrency): overflow-capable stages dispatch without their
        # per-stage host sync; flags drain in one batched readback when
        # the window fills, before any host-consuming stage, and at job
        # end.  Downstream stages consume the optimistic results — an
        # overflow (rare) re-runs the affected suffix synchronously.
        window: List[Dict] = []
        # the window list object outlives this call only in the probe
        # closure; re-registering per run keeps the sample live
        flightrec.probe("inflight_dispatches", lambda: len(window))
        for stage in graph.stages:
            if stage.ops and stage.ops[0].kind == "do_while":
                self._drain_window(window, graph, bindings, results,
                                   binding_fps or {}, stage_fps)
                stage_fps[stage.id] = None  # loop state is data-dependent
                self._run_do_while(stage, graph, bindings, results)
                continue
            if stage.ops and stage.ops[0].kind == "apply_host":
                self._drain_window(window, graph, bindings, results,
                                   binding_fps or {}, stage_fps)
                stage_fps[stage.id] = None  # host fn is opaque
                self._run_apply_host(stage, bindings, results)
                continue
            if window and self._drain_for_adapt(stage, window):
                # adaptation opportunity: an aggregation-shaped producer
                # of this stage sits undrained in the window, so its
                # observed count is one batched readback away — pay the
                # sync now to dispatch this stage at observed width
                # (DrDynamicRangeDistributor.cpp:54-110 semantics)
                self._drain_window(window, graph, bindings, results,
                                   binding_fps or {}, stage_fps)
            self._run_stage(
                stage, graph, bindings, results, binding_fps or {}, stage_fps,
                window=window if depth > 1 else None,
            )
            if len(window) >= depth:
                self._drain_window(window, graph, bindings, results,
                                   binding_fps or {}, stage_fps)
        self._drain_window(window, graph, bindings, results,
                           binding_fps or {}, stage_fps)

    def _drain_window(self, window, graph, bindings, results,
                      binding_fps, stage_fps) -> None:
        """Resolve all speculatively dispatched stages: ONE batched
        overflow readback for the all-clear case; on an overflow,
        finalize the clean prefix and re-run the overflowing stage and
        everything dispatched after it synchronously (their inputs or
        contents were garbage) at an escalated boost."""
        if not window:
            return
        import jax.numpy as jnp

        flags = [w["flag"] for w in window if w["flag"] is not None]
        self.events.emit(
            "overflow_drain", inflight=len(window),
            stages=[w["stage"].name for w in window],
        )
        combined = (
            False if not flags
            else flags[0] if len(flags) == 1
            else jnp.any(jnp.stack(flags))
        )
        # observed row counts ride the SAME batched readback
        counted = [w for w in window if w.get("counts")]
        with self.tracer.span(
            "drain", cat="readback", inflight=len(window)
        ) as drain:
            combined_v, counts_v, seen_v = jax.device_get(
                (combined, [w["counts"] for w in counted],
                 [w["seen"] for w in window])
            )
            self._exchange_observed(
                drain,
                [(w["stage"], w["boost"], sv, w["seen_log"])
                 for w, sv in zip(window, seen_v)],
                bool(combined_v),
            )
        count_of = {id(w): cv for w, cv in zip(counted, counts_v)}
        if not bool(combined_v):
            for w in window:
                if id(w) in count_of:
                    self._record_observed(
                        w["stage"], count_of[id(w)],
                        [o.capacity for o in w["outs"]],
                    )
                self._finalize_entry(w, results)
            window.clear()
            return
        bad = next(
            i for i, w in enumerate(window)
            if w["flag"] is not None and bool(w["flag"])
        )
        # entries at/after the pivot hold garbage: record counts only
        # for the clean prefix and purge any stale count the redo's
        # overflow-free stages won't overwrite
        for w in window[:bad]:
            if id(w) in count_of:
                self._record_observed(
                    w["stage"], count_of[id(w)],
                    [o.capacity for o in w["outs"]],
                )
            self._finalize_entry(w, results)
        for w in window[bad:]:
            for i in range(len(w["stage"].out_slots)):
                self._observed_rows.pop((w["stage"].id, i), None)
        redo = window[bad:]
        window.clear()
        first = redo[0]
        self.events.emit(
            "stage_overflow", stage=first["stage"].id,
            name=first["stage"].name, version=first["version"],
            boost=first["boost"],
        )
        # Windowed dispatches always ran at boost 1 (the speculative
        # branch returns on the first attempt); the synchronous redo's
        # own retry loop handles further escalation and the boost
        # ceiling.
        for j, w in enumerate(redo):
            self._run_stage(
                w["stage"], graph, bindings, results, binding_fps, stage_fps,
                boost0=2 if j == 0 else 1, window=None,
            )

    def _finalize_entry(self, w, results) -> None:
        """A speculative dispatch whose overflow flag came back clean:
        emit its completion, queue its dict-miss counter, and save its
        checkpoint (none of which may happen before the flag clears)."""
        stage = w["stage"]
        # dispatch-to-drain wall time covers the WHOLE window's
        # dispatches + the batched readback, so it must not feed the
        # straggler duration model (sync runs still do); it is reported
        # on the event for observability only.
        dt = time.time() - w["t0"]
        self.events.emit(
            "stage_complete", stage=stage.id, name=stage.name,
            version=w["version"], seconds=dt, deferred=True,
        )
        if _stage_has_miss_guard(stage):
            self._pending_miss.append((stage.name, w["miss"]))
        if not w.get("fan"):  # adapted layouts never persist (see sync)
            self._save_checkpoint(stage, w["fp"], w["outs"])

    def _save_checkpoint(self, stage, fp, outs) -> None:
        """Shared checkpoint save (sync + deferred paths).  Stages with
        a dense-domain miss guard DEFER their save to the job-end miss
        drain: saving now could persist a dropped-rows result that a
        later identical submission would load, silently bypassing the
        loud-failure guarantee (code-review r4)."""
        if self.checkpoints is None or fp is None:
            return
        if _stage_has_miss_guard(stage):
            self._pending_ckpt.append((stage, fp, outs))
            return
        self._write_checkpoint(stage, fp, outs)

    def _write_checkpoint(self, stage, fp, outs) -> None:
        if self.config.checkpoint_retain_seconds is not None:
            n = self.checkpoints.gc(self.config.checkpoint_retain_seconds)
            if n:
                self.events.emit("checkpoint_gc", removed=n)
        try:
            path = self.checkpoints.save(
                stage, fp, tuple(outs[: len(stage.out_slots)])
            )
            self.events.emit(
                "stage_checkpoint_saved", stage=stage.id,
                name=stage.name, path=path,
            )
        except OSError as e:
            log.warning(
                "checkpoint save failed for %s: %s", stage.name, e
            )

    @staticmethod
    def _publish(stage, outs, results) -> None:
        """Publish a stage's outputs.  Fused regions also alias each
        export under its ORIGINAL (member stage id, out idx) — callers
        (context/worker/out-of-core) resolve plan outputs against the
        PRE-fusion graph they lowered, and fusion must stay invisible
        to them."""
        for i in range(len(stage.out_slots)):
            results[(stage.id, i)] = outs[i]
        if isinstance(stage, FusedStage):
            for pos, (mi, oi) in enumerate(stage.exports):
                results[(stage.members[mi].id, oi)] = outs[pos]

    def _resolve_inputs(
        self,
        stage: Stage,
        bindings: Dict[int, ColumnBatch],
        results: Dict[Tuple[int, int], ColumnBatch],
    ) -> Tuple[ColumnBatch, ...]:
        ins: List[ColumnBatch] = []
        for ref, idx in stage.input_refs:
            if ref == "plan_input":
                ins.append(bindings[idx])
            else:
                ins.append(results[(ref, idx)])
        return tuple(ins)

    def _run_stage(
        self,
        stage: Stage,
        graph: StageGraph,
        bindings: Dict[int, ColumnBatch],
        results: Dict[Tuple[int, int], ColumnBatch],
        binding_fps: Dict[int, Optional[str]] = {},
        stage_fps: Dict[int, Optional[str]] = {},
        boost0: int = 1,
        window: Optional[List[Dict]] = None,
    ) -> None:
        inputs = self._resolve_inputs(stage, bindings, results)
        shape_key = self._shape_key(inputs)
        fp = None
        if self.checkpoints is not None:
            input_fps = tuple(
                (
                    binding_fps.get(idx)
                    if ref == "plan_input"
                    else (
                        f"{stage_fps.get(ref)}:{idx}"
                        if stage_fps.get(ref) is not None
                        else None
                    )
                )
                for ref, idx in stage.input_refs
            )
            fp = stage_fingerprint(stage, shape_key, input_fps)
            stage_fps[stage.id] = fp
            if fp is not None:
                hit = self.checkpoints.load(stage, fp, self.mesh)
                if hit is not None and len(hit) == len(stage.out_slots):
                    self.events.emit(
                        "stage_checkpoint_hit", stage=stage.id, name=stage.name
                    )
                    self._publish(stage, hit, results)
                    return
        st = self.stats.setdefault(stage.name, StageStatistics(self.config.outlier_sigmas))

        fan = [
            op.params.get("nparts") for op in stage.ops
            if op.params.get("nparts")
        ]
        # kernels disable fan reduction on hybrid meshes and clamp to
        # P; the event must describe what actually runs
        if fan and len(mesh_axes(self.mesh)) == 1 and min(fan) < self.P:
            # stage-level fan-out adaptation record (the rewired-graph
            # event of DrDynamicRangeDistributor.cpp:54-110)
            self.events.emit(
                "stage_fanout", stage=stage.id, name=stage.name,
                nparts=min(fan), of=self.P,
            )
        can_overflow = any(
            op.kind not in NON_OVERFLOW_OPS for op in stage.ops
        )
        adapt_fan = self._adapt_fan_for(stage)
        if adapt_fan:
            self.events.emit(
                "stage_width_adapt", stage=stage.id, name=stage.name,
                nparts=adapt_fan, of=self.P,
                observed_rows=sum(
                    self._observed_rows.get((r, i), 0)
                    for r, i in stage.input_refs
                ),
            )
        # counts ride readbacks that happen anyway: the sync overflow
        # flag, or the window's batched drain (where even overflow-free
        # stages' counts are free); only the async non-window path
        # never pays a readback for them
        want_count = stage.id in self._count_wanted and (
            can_overflow or bool(window)
        )
        boost = boost0
        if self.rewriter is not None and can_overflow:
            # proactive palette pre-widening: an overflow_loop diagnosis
            # raises this stage-name's starting tier so the NEXT
            # dispatch skips the doomed narrow attempt entirely
            floor = self.rewriter.boost_floor(stage.name)
            if floor > boost:
                boost = floor
                if (stage.name, floor) not in self._rewrites_applied:
                    self._rewrites_applied.add((stage.name, floor))
                    self.events.emit(
                        "plan_rewrite", phase="applied",
                        action="prewiden_palette", rule="overflow_loop",
                        subject=stage.name, stage=stage.name,
                        boost=floor,
                    )
        failures = 0
        version = 0
        attempts: List[Attempt] = []  # failed-attempt history (post-mortem)
        while True:
            version += 1
            self.events.emit(
                "stage_start", stage=stage.id, name=stage.name, version=version, boost=boost
            )
            if isinstance(stage, FusedStage):
                # one dispatch covering the whole region (the
                # dispatches-per-plan signal jobview/JobMetrics fold)
                self.events.emit(
                    "fused_dispatch", stage=stage.id, name=stage.name,
                    members=len(stage.members), version=version,
                    boost=boost,
                )
            t0 = time.time()
            try:
                faults.registry.maybe_fail(stage.name)
                if faults.registry.maybe_kill(stage.name):
                    # Gang chaos (FaultPlan.worker_kill_prob, installed
                    # on workers via the set_fault mailbox command):
                    # this PROCESS dies mid-stage, leaving gang peers
                    # inside the stage's collectives — the
                    # mid-collective-death scenario the driver's
                    # auto-recovery (rebuild_gang) must absorb.
                    self.events.emit(
                        "worker_killed_injected", stage=stage.id,
                        name=stage.name,
                    )
                    # os._exit skips atexit: the blackbox must be on
                    # disk BEFORE the process vanishes mid-collective
                    flightrec.dump_now(f"worker_killed:{stage.name}")
                    os._exit(113)
                inj_delay = faults.registry.maybe_delay(stage.name)
                if inj_delay:
                    self.events.emit(
                        "stage_delay_injected", stage=stage.id,
                        name=stage.name, seconds=inj_delay,
                    )
                    self._sleep(inj_delay)
                # escalated boosts drop the reduced width first: the
                # concentration itself may be what overflowed
                fn = self._get_compiled(
                    stage, boost, shape_key,
                    fan=adapt_fan if boost < 4 else None,
                )
                # The obs span (cat=execute): dispatch + any
                # rides-along readback, attributed to this attempt; in
                # the XLA profiler timeline it is the annotation
                # ``dryad:dispatch:<stage>`` (obs/span.py), which also
                # says how many bytes a chip puts on the ICI in the
                # stage's exchanges, how many exchanges the trace
                # skipped because the mesh has one partition, and how
                # many 4-byte words the widest row that a sort of the
                # stage carries has (trace-time constants: 0 at open on
                # the one dispatch that traces, whose event and
                # annotation get them at its close, from the add below).
                with self.tracer.span(
                    stage.name, cat="execute", stage=stage.id,
                    version=version, boost=boost,
                    xchg_ici_bytes=_ici_bytes(fn.xchg_rounds),
                    xchg_elided=fn.xchg_elided,
                    row_words=fn.row_words,
                    **_fold_stats(stage),
                ) as dispatch_span:
                    # OPERAND params ride the replicated slot: current
                    # table content from the pool (uploaded/scattered
                    # once per content, reused across dispatches)
                    outs, (overflow, dict_miss, seen) = fn(
                        inputs, self._stage_rep(stage)
                    )
                    # Static per-round exchange accounting (filled at
                    # trace time by kernels._exchange): every dispatch
                    # re-ships these bytes, so emit per attempt.
                    for rnd in fn.xchg_rounds:
                        self.events.emit(
                            "exchange_round", stage=stage.id,
                            name=stage.name,
                            qid=tracectx.current_qid(), **rnd,
                        )
                    dispatch_span.add(
                        xchg_ici_bytes=_ici_bytes(fn.xchg_rounds),
                        xchg_elided=fn.xchg_elided,
                        row_words=fn.row_words)
                    counts_dev = None
                    if want_count:
                        import jax.numpy as jnp

                        counts_dev = [
                            jnp.sum(outs[i].valid)
                            for i in range(len(stage.out_slots))
                        ]
                    if window is not None and (can_overflow or window):
                        # Speculative dispatch: publish the optimistic
                        # results so downstream stages can dispatch too,
                        # and defer the overflow sync to the window
                        # drain (one batched readback for the window).
                        # A non-overflow stage joins an OPEN window too:
                        # it may have consumed speculative inputs, so a
                        # redo must recompute it (flag None = never the
                        # overflow pivot).
                        self._publish(stage, outs, results)
                        window.append(dict(
                            stage=stage, version=version, boost=boost,
                            fp=fp, flag=overflow if can_overflow else None,
                            miss=dict_miss, outs=outs, t0=t0,
                            counts=counts_dev, seen=seen,
                            seen_log=fn.seen_log,
                            fan=adapt_fan if boost < 4 else None,
                        ))
                        self.events.emit(
                            "stage_dispatched", stage=stage.id,
                            name=stage.name, version=version, boost=boost,
                            inflight=len(window),
                        )
                        return
                    # Overflow-free stages skip the host sync: their
                    # flag is statically False, so the driver moves on
                    # and JAX async dispatch overlaps this stage's
                    # device time with independent stages (the GM
                    # message-pump concurrency, DrMessagePump.h:116).
                    if can_overflow:
                        # ONE readback for the flag, the observed
                        # counts and what the exchanges saw
                        with self.tracer.span(
                            "drain", cat="readback", inflight=1
                        ) as drain:
                            overflow, host_counts, seen = jax.device_get(
                                (overflow, counts_dev, seen)
                            )
                            overflow = bool(overflow)
                            self._exchange_observed(
                                drain, [(stage, boost, seen, fn.seen_log)],
                                overflow
                            )
                        if host_counts is not None:
                            self._record_observed(
                                stage, host_counts,
                                [o.capacity for o in outs],
                            )
                    else:
                        overflow = False
            except faults.InjectedFault as e:
                failures += 1
                kind = classify(e, attempts)
                exhausted = self.retry_policy.exhausted(failures)
                # deterministic repeats fail fast: identical class +
                # message means elsewhere/later cannot help
                terminal = exhausted or kind is FailureKind.DETERMINISTIC
                backoff = (
                    0.0 if terminal
                    else self.retry_policy.backoff(stage.name, failures)
                )
                attempts.append(Attempt(
                    number=version, error_type=type(e).__name__,
                    error=str(e), kind=kind.value, backoff=backoff,
                ))
                self.events.emit(
                    "stage_failed", stage=stage.id, name=stage.name,
                    version=version, error=str(e), failures=failures,
                    failure_kind=kind.value, backoff=round(backoff, 4),
                )
                if terminal:
                    self.events.emit(
                        "job_failed", stage=stage.id, name=stage.name,
                        failure_kind=kind.value, reason=str(e),
                    )
                    why = (
                        "failed deterministically (identical error "
                        "reproduced; retrying cannot help)"
                        if kind is FailureKind.DETERMINISTIC
                        and not exhausted
                        else "exceeded failure budget "
                        f"({self.config.max_stage_failures})"
                    )
                    flightrec.dump_now(f"job_failed:{stage.name}")
                    raise JobFailedError(
                        f"stage {stage.name!r} {why}: {e}",
                        stage=stage.name, attempts=attempts,
                    ) from e
                if backoff:
                    self._sleep(backoff)
                continue  # versioned re-execution (with backoff)

            dt = time.time() - t0
            st.record(dt)
            if st.is_outlier(dt):
                self.events.emit(
                    "stage_straggler", stage=stage.id, name=stage.name,
                    version=version, seconds=dt,
                    threshold=st.outlier_threshold(),
                )
            if overflow:
                self.events.emit(
                    "stage_overflow", stage=stage.id, name=stage.name,
                    version=version, boost=boost,
                )
                if boost >= 2 ** self.config.max_shuffle_retries:
                    self.events.emit(
                        "job_failed", stage=stage.id, name=stage.name,
                        failure_kind="resource",
                        reason="shuffle overflow at max boost",
                    )
                    # An expansion join that outgrows every boost is
                    # usually a hot-key quadratic blowup — point at the
                    # knob that actually bounds it.
                    join_exp = any(
                        "expansion" in op.params for op in stage.ops
                    )
                    hint = (
                        "raise the join's expansion= argument (hot keys "
                        "multiply pair counts quadratically), "
                        "shuffle_slack, or partition count"
                        if join_exp
                        else "raise shuffle_slack or partition count"
                    )
                    flightrec.dump_now(f"overflow_exhausted:{stage.name}")
                    raise StageFailedError(
                        f"stage {stage.name!r} still overflowing at "
                        f"boost {boost}; {hint}"
                    )
                boost *= 2
                continue  # adaptive re-shape

            self.events.emit(
                "stage_complete", stage=stage.id, name=stage.name,
                version=version, seconds=dt,
                # async stages report DISPATCH time; device time overlaps
                # downstream stages (jobview surfaces the distinction)
                **({} if can_overflow else {"async": True}),
            )
            if _stage_has_miss_guard(stage):
                # Deferred readback: checked after the job drains so the
                # dense fast path keeps its async dispatch.
                self._pending_miss.append((stage.name, dict_miss))
            self._publish(stage, outs, results)
            # a fan-adapted run's outputs sit in a reduced-width layout
            # the fingerprint doesn't describe — never persist them
            # under the full-width identity
            if not (adapt_fan and boost < 4):
                self._save_checkpoint(stage, fp, outs)
            return

    def _run_do_while(
        self,
        stage: Stage,
        graph: StageGraph,
        bindings: Dict[int, ColumnBatch],
        results: Dict[Tuple[int, int], ColumnBatch],
    ) -> None:
        """Driver-loop iteration (DoWhile, ``DryadLinqQueryNode.cs:4555``).

        Each iteration re-lowers and runs the body subplan on the current
        dataset; the cond subplan yields a host boolean to continue.
        """
        if self.subquery_runner is None:
            raise RuntimeError("do_while requires a subquery_runner (use DryadContext)")
        p = stage.ops[0].params
        (current,) = self._resolve_inputs(stage, bindings, results)
        # Device-side fixed point: EVERY do_while first tries the
        # lax.while_loop seam — the driver loop below costs one dispatch
        # round trip per iteration, the device loop costs one total.
        # Ineligible subplans (multi-stage body/cond, carry-shape
        # changes) fall back via the exception contract below, so
        # plans the lowerer rejects run exactly as before.
        if self.loop_lowerer is not None:
            try:
                results[(stage.id, 0)] = self._run_do_while_device(
                    stage, p, current
                )
                return
            except (ValueError, TypeError) as e:
                # ValueError: the lowerer rejected the subplan (multi-stage
                # body/cond).  TypeError: the body lowers to one stage but
                # changes the carry pytree shape (e.g. capacity resize with
                # slack), which lax.while_loop rejects at trace time.
                # Either way the driver loop below handles it.
                self.events.emit(
                    "do_while_device_fallback", stage=stage.id, reason=str(e)
                )
        max_iter = p["max_iter"]
        # Compact the loop state back to a STABLE capacity after every
        # body round: body plans grow capacity by their slack factors,
        # so feeding the output straight back re-compiles every
        # iteration against monotonically growing shapes (by iteration
        # ~20 the compiles dominate by orders of magnitude).  With
        # compaction, iteration 2+ reuse iteration 1's compiled stages;
        # a state that genuinely outgrows the capacity boosts it through
        # the bounded palette, same as stage overflow retries.
        base_pp = max(8, -(-current.capacity // self.P))
        boost = 1
        it = 0
        while True:
            it += 1
            if it > max_iter:
                self.events.emit("do_while_max_iter", stage=stage.id, iters=it - 1)
                break
            self.events.emit("do_while_iter", stage=stage.id, iter=it)
            current = self.subquery_runner(p["body"], p["schema"], current)
            while True:
                compacted, ovf = self._compact_loop_state(
                    current, base_pp * boost
                )
                if not ovf:
                    current = compacted
                    break
                if boost >= 2 ** self.config.max_shuffle_retries:
                    raise RuntimeError(
                        f"do_while state exceeded compaction capacity at "
                        f"boost {boost} (base {base_pp} rows/partition)"
                    )
                boost *= 2
                self.events.emit(
                    "do_while_state_boost", stage=stage.id, boost=boost
                )
            cont = self.subquery_runner(p["cond"], p["schema"], current, scalar=True)
            if not bool(cont):
                break
        results[(stage.id, 0)] = current

    def _compact_loop_state(self, batch: ColumnBatch, target_pp: int):
        """One cached SPMD program per (columns signature, target):
        per-partition compaction of valid rows to a fixed capacity,
        returning (batch, overflowed)."""
        import jax.numpy as jnp

        from dryad_tpu.exec.kernels import _round8
        from dryad_tpu.ops import shuffle as SH

        target_pp = _round8(target_pp)
        sig = (
            tuple(
                (n, str(a.dtype), a.shape[1:])
                for n, a in sorted(batch.data.items())
            ),
            batch.capacity, target_pp,
        )
        if sig not in self._compact_cache:
            axes = mesh_axes(self.mesh)

            def fn(shard, _rep):
                out, ovf = SH.resize(shard, target_pp)
                # reduce across the mesh: a device-local flag would
                # silently drop rows when only a non-primary partition
                # overflows (same rule as build_stage_fn's psum)
                ovf = jax.lax.psum(ovf.astype(jnp.int32), axes) > 0
                return out, (ovf,)

            self._compact_cache[sig] = compile_stage(self.mesh, fn)
        out, (ovf,) = self._compact_cache[sig](batch, ())
        return out, bool(ovf)

    def _run_apply_host(self, stage, bindings, results) -> None:
        """Host-callback Apply: pull each partition to host, run the
        user fn, push back sharded (the arbitrary-user-code escape
        hatch; device->host->device round trip per job — the documented
        perf cliff, SURVEY 7.3)."""
        import math

        from dryad_tpu.parallel.mesh import partition_sharding

        p = stage.ops[0].params
        (b,) = self._resolve_inputs(stage, bindings, results)
        self.events.emit("apply_host_start", stage=stage.id)
        P = self.P
        if jax.process_count() > 1:
            # a plain host fetch of a cross-process array raises in a
            # multi-controller gang; gather the batch first (apply_host
            # is already the documented device->host perf cliff) — every
            # process then computes all partitions deterministically
            from jax.experimental import multihost_utils as _mh

            valid = np.asarray(_mh.process_allgather(b.valid, tiled=True))
            host_cols = {
                n: np.asarray(_mh.process_allgather(v, tiled=True))
                for n, v in b.data.items()
            }
        else:
            # overlapped d2h copies, of the slots the valid rows reach
            valid, host_cols, _, _ = b.fetch_host(
                tracer=self.tracer, metrics=self.metrics
            )
        cap = len(valid) // P  # slots a partition, as fetched
        schema = p["schema"]
        phys = schema.device_names()
        expected = {n: _phys_np_dtype(n, schema) for n in phys}
        out_parts = []
        for i in range(P):
            sl = slice(i * cap, (i + 1) * cap)
            m = valid[sl]
            part = {n: v[sl][m] for n, v in host_cols.items()}
            out = p["fn"](part, i)
            if set(out.keys()) != set(phys):
                raise ValueError(
                    f"apply_host fn output columns {sorted(out)} != "
                    f"schema physical columns {phys} (partition {i})"
                )
            # Validate + cast against the declared schema up front so a
            # dtype drift fails here, not in a downstream compile.
            out = {n: np.asarray(v, expected[n]) for n, v in out.items()}
            lens = {len(v) for v in out.values()} or {0}
            if len(lens) != 1:
                raise ValueError(
                    f"apply_host fn returned ragged columns: { {n: len(v) for n, v in out.items()} }"
                )
            out_parts.append(out)
        new_cap = max(
            8,
            int(
                math.ceil(
                    max((len(next(iter(op.values()), [])) for op in out_parts),
                        default=1) / 8.0
                )
            ) * 8,
        )
        sh = partition_sharding(self.mesh)
        data = {}
        for n in phys:
            buf = np.zeros((P * new_cap,), expected[n])
            for i, op in enumerate(out_parts):
                v = op[n]
                buf[i * new_cap : i * new_cap + len(v)] = v
            data[n] = jax.device_put(buf, sh)
        vbuf = np.zeros((P * new_cap,), np.bool_)
        for i, op in enumerate(out_parts):
            nrows = len(next(iter(op.values()), []))
            vbuf[i * new_cap : i * new_cap + nrows] = True
        out_batch = ColumnBatch(data, jax.device_put(vbuf, sh))
        self.events.emit("apply_host_done", stage=stage.id)
        results[(stage.id, 0)] = out_batch

    def _run_do_while_device(self, stage, p, current: ColumnBatch) -> ColumnBatch:
        """On-device DoWhile: the WHOLE loop compiles as one
        ``lax.while_loop`` inside one shard_map program — no host
        round-trip per iteration (the TPU-first upgrade over the
        reference's GM-evaluated loop, ``DryadLinqQueryNode.cs:4555``).

        Requirements (else ValueError -> driver-loop fallback): body and
        cond each lower to one fused stage; the body preserves the batch
        pytree structure (same columns, same capacity).
        """
        import jax.numpy as jnp

        body_stage, body_schema = self.loop_lowerer(
            p["body"], p["schema"], current
        )
        cond_stage, cond_schema = self.loop_lowerer(
            p["cond"], body_schema, current
        )
        cond_col = cond_schema.device_names()[0]
        max_iter = int(p["max_iter"])
        axes = mesh_axes(self.mesh)
        axis_sizes = tuple(self.mesh.shape[a] for a in axes)

        boost = 1
        while True:
            body_fn = build_stage_fn(
                body_stage, self.P, self.config.shuffle_slack, boost,
                axes, axis_sizes,
            )
            cond_fn = build_stage_fn(
                cond_stage, self.P, self.config.shuffle_slack, boost,
                axes, axis_sizes,
            )

            def outer(sharded_inputs, _rep):
                (b0,) = sharded_inputs

                def cond(state):
                    i, b, ovf, _miss = state
                    couts, (covf, _cm, _) = cond_fn((b,), ())
                    go = couts[0].data[cond_col][0].astype(jnp.bool_)
                    return (i < max_iter) & go & ~(ovf | covf)

                def body(state):
                    i, b, ovf, miss = state
                    bouts, (bovf, bmiss, _) = body_fn((b,), ())
                    return (i + jnp.int32(1), bouts[0], ovf | bovf, miss + bmiss)

                # DoWhile runs the body BEFORE checking cond (reference
                # semantics, DryadLinqQueryNode.cs:4555; driver fallback
                # below mirrors it) — so seed the loop state with one body
                # application rather than letting lax.while_loop evaluate
                # cond on the un-iterated input.
                bouts0, (bovf0, bmiss0, _) = body_fn((b0,), ())
                it, bout, ovf, miss = jax.lax.while_loop(
                    cond, body, (jnp.int32(1), bouts0[0], bovf0, bmiss0)
                )
                # A cond-stage overflow terminates the loop (its `go` bit
                # is garbage) but lives only inside cond's trace; recover
                # it by re-evaluating cond on the final state so the host
                # retries with a larger boost instead of accepting a
                # result whose termination decision overflowed.
                _, (covf, _cm, _) = cond_fn((bout,), ())
                return (bout,), (ovf | covf, it, miss)

            # split_operands=False: these fns were built WITHOUT
            # operand plumbing (the loop body bakes table constants),
            # so the cache must key by table content, not tier.
            key = (
                "do_while_device",
                self._stage_key(body_stage, split_operands=False),
                self._stage_key(cond_stage, split_operands=False),
                self._shape_key((current,)),
                max_iter, boost,
            )
            fn = self._compiled.get(key)
            if fn is None:
                fn = compile_stage(self.mesh, outer)
                self._compiled[key] = fn
            self.events.emit(
                "do_while_device_start", stage=stage.id, boost=boost
            )
            (out,), (overflow, iters, miss) = fn((current,), ())
            if not bool(overflow):
                if any(
                    op.kind == "string_code"
                    for s in (body_stage, cond_stage)
                    for op in s.ops
                ):
                    self._pending_miss.append((stage.name, miss))
                self.events.emit(
                    "do_while_device_done", stage=stage.id, iters=int(iters)
                )
                return out
            self.events.emit(
                "stage_overflow", stage=stage.id, name=stage.name,
                version=1, boost=boost,
            )
            if boost >= 2 ** self.config.max_shuffle_retries:
                raise StageFailedError(
                    f"device do_while still overflowing at boost {boost}"
                )
            boost *= 2
