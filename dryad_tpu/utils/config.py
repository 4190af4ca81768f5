"""Config / flag system.

Mirrors the reference's two-level config: per-context knobs on
``DryadLinqContext`` (reference ``LinqToDryad/DryadLinqContext.cs:577-1107``)
and process-wide defaults in ``StaticConfig`` (reference
``LinqToDryad/DryadLinqGlobals.cs:36-74``).  An option is set ONE way:
by passing a ``DryadConfig``.  Only deployment settings — where a fleet
spills and dumps, how loud it logs, what a tenant may admit — also take
a default from the environment, read once at import.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return int(v) if v not in (None, "") else default


class StaticConfig:
    """Process-wide defaults (reference ``DryadLinqGlobals.cs:36-74``)."""

    # Logging level name for the framework logger.
    logging_level: str = os.environ.get("DRYAD_TPU_LOGGING_LEVEL", "INFO")


@dataclasses.dataclass
class DryadConfig:
    """Per-context configuration (reference ``DryadLinqContext`` properties).

    Attributes map to reference context knobs:
    - ``max_stage_failures``: GM failure budget (``DrGraph.h:42``
      ``m_maxActiveFailureCount``).
    - ``shuffle_slack`` / ``max_shuffle_retries``: padded-bucket shuffle
      capacity slack and the bounded shape palette for overflow retries
      (the adaptive-execution analog of ``DrDynamicDistributor.h:26``).
    - ``intermediate_compression``: channel compression transform
      (``dryadvertex.h:33-48`` TransformType).
    - ``sample_rate``: range-partition sampler rate (reference 0.1%%,
      ``DryadLinqSampler.cs:38-42``).
    """

    max_stage_failures: int = 3
    shuffle_slack: float = 2.0
    max_shuffle_retries: int = 3
    intermediate_compression: Optional[str] = None  # None | "zlib"
    sample_rate: float = 0.001
    # Event log directory (Calypso analog); None disables.
    event_log_dir: Optional[str] = None
    # XLA/JAX profiler output directory (SURVEY 5.1: profiler traces +
    # per-stage step markers); None disables tracing.
    profile_dir: Optional[str] = None
    # Stage-output checkpoint directory (durable DCT_File channel
    # analog, SURVEY §5.4); None disables checkpoint/resume.
    checkpoint_dir: Optional[str] = None
    # Checkpoint retention lease in seconds (channel-file
    # retain/lease-grace analog, DrProcess.h:80-89); None keeps forever.
    checkpoint_retain_seconds: Optional[float] = None
    # Outlier threshold in sigmas for speculative duplication
    # (reference DrStageStatistics.cpp:24-25: 3 sigma).
    outlier_sigmas: float = 3.0
    # Straggler-threshold floor (exec.stats.StageStatistics): with few
    # completed samples the trimmed-sigma fit degenerates (variance ~0
    # flags EVERY later attempt an outlier); the threshold is clamped
    # to floor_ratio x the trimmed mean.
    straggler_floor_ratio: float = 1.5
    # Coded stage redundancy (dryad_tpu.redundancy): a partitioned
    # aggregation whose combiner is LINEAR (sum/count/mean partials, or
    # Decomposable(linear=True)) runs as k systematic + up to r parity
    # coded vertices — ANY k of the k+r completions reconstruct the
    # stage output (exactly for integer accumulators), so stragglers
    # need no identification and killed vertices no re-execution.
    # Non-linear combiners keep the duplicate-on-straggle path.
    coded_redundancy: bool = True
    coded_parity_tasks: int = 2
    # Retry backoff (exec.failure.RetryPolicy): transient stage/vertex
    # failures wait base * 2^(failures-1) seconds (capped at max) plus
    # seeded jitter before re-executing — a crashing dependency gets
    # breathing room instead of an immediate retry storm.
    retry_backoff_base: float = 0.05
    retry_backoff_max: float = 2.0
    retry_jitter: float = 0.5  # backoff *= 1 + jitter * U(0,1), seeded
    retry_seed: int = 0
    # Broadcast-join threshold: with strategy='auto', a right side whose
    # TOTAL row capacity (per-partition capacity x P) is at or below this
    # is replicated via all_gather instead of co-hash-partitioned (the
    # dynamic broadcast decision of DynamicManager.cs:51 /
    # DrDynamicBroadcast.h:23, made trace-time from static capacities).
    broadcast_limit: int = 1 << 16
    # order_by+take(n) fuses into a shuffle-free distributed top-k when
    # n is at or below this (each partition gathers P*n head rows);
    # larger takes keep the full range-exchange sort.
    topk_limit: int = 1024
    # Auto-dense STRING group_by: a single-STRING-key group_by with
    # sum/count/mean aggs lowers to the MXU bucket path keyed on dense
    # dictionary codes (ops/stringcode.py) when the context dictionary
    # holds at most auto_dense_limit distinct strings — no shuffle at
    # all, vs the reference's full hash repartition for the same query.
    auto_dense_strings: bool = True
    # Int twin: a plain group_by over one INT32 key whose INGEST-time
    # range is [0, K), K <= auto_dense_limit, rides the same MXU bucket
    # path (with a range-miss guard for post-ingest fabrication).
    auto_dense_ints: bool = True
    auto_dense_limit: int = 1 << 17
    # Compile-once dictionary coding (static-vs-operand param split):
    # the string CodeTable/DecodeTable arrays ride the compiled program
    # as call-time DEVICE OPERANDS on a power-of-two shape palette —
    # the compile cache keys on the palette tier, a widening vocabulary
    # pays O(log vocab) compiles instead of O(widenings), and the
    # executor's operand pool scatters only the widened table delta to
    # the device.  Off = the legacy baked-constant path (each table
    # content is its own compile-cache key) kept as the differential
    # baseline.
    stringcode_runtime_tables: bool = True
    # Device-resident input cache budget in bytes (0 disables): ingested
    # host/store tables stay sharded in HBM across submits, LRU-evicted
    # by size — the on-device analog of the ProcessService LRU block
    # cache (Cache.cs:32) applied to ingest instead of channel files.
    # Repeated queries over one table skip the host->device transfer.
    device_cache_bytes: int = 2 * 1024 * 1024 * 1024
    # Target rows per independent vertex task: when a partitioned
    # submission doesn't pin nparts, the fan-out is computed from the
    # OBSERVED input size (the data-size-driven consumer-count
    # recomputation of DrDynamicRangeDistributor.cpp:54-110:
    # copies = sampledSize / dataPerVertex).
    rows_per_vertex: int = 1 << 18
    # Whole-DAG SPMD fusion (plan.fuse): maximal runs of consecutive
    # device-eligible stages — including their hash/range exchanges —
    # compile and dispatch as ONE shard_map region, dropping dispatches
    # per plan from O(stages) to O(fused regions) and keeping every
    # inter-stage intermediate in HBM.  Any seam's bucket-overflow flag
    # retries the WHOLE region at the next palette capacity (same
    # bounded-palette contract as single-stage overflow).  Off = the
    # driver-mediated per-stage path, kept as the differential baseline.
    plan_fuse: bool = True
    # How many overflow-capable stages may be DISPATCHED speculatively
    # before the driver syncs their overflow flags in one batched
    # readback (the GM pump's concurrent vertex management,
    # DrMessagePump.h:116-180).  A 5-shuffle pipeline pays one
    # control round-trip instead of five;
    # an overflow re-runs the affected suffix at a larger boost.
    # 1 = legacy per-stage sync.
    overflow_sync_depth: int = 4
    # Memory-bounded staged exchange (plan.xchgplan): hash/range/join
    # repartitions decompose into ppermute rounds shipping at most this
    # many destination buckets each, so peak extra HBM per device is
    # O(window * B) instead of the flat all_to_all's O(P * B) — ICI
    # hops staged first, all DCN-crossing traffic batched into one
    # round per remote slice (arxiv 2112.01075's decomposition over the
    # combinetree mesh model).  0 = the flat single-collective path,
    # kept as the differential baseline; -1 = auto policy — the
    # executor picks flat while the estimated all_to_all footprint
    # fits exchange_hbm_budget_mb, else the widest window that does
    # (plan.xchgplan.resolve_window; the runtime rewriter can pin the
    # auto choice via RewriteController.retune_exchange).
    exchange_window: int = 0
    # HBM the auto exchange-window policy may spend on one exchange's
    # staging buffers (only read when exchange_window == -1).
    exchange_hbm_budget_mb: int = 256
    # Stage-level fan-out adaptation (DrDynamicRangeDistributor.cpp:
    # 54-110: consumer copies = observed size / data-per-vertex): when a
    # stage's input row count is STATICALLY bounded at or below
    # tail_fanout_rows (post-aggregation tails, take(n) heads, dense-K
    # domains), its exchange concentrates rows onto
    # ceil(rows / tail_rows_per_partition) partitions instead of all P —
    # the remaining partitions run empty (masked) and per-partition
    # padding shrinks.  0 disables.
    tail_fanout_rows: int = 4096
    tail_rows_per_partition: int = 512
    # Out-of-core streaming (exec.outofcore; reference streaming channel
    # stack channelinterface.h:212): max rows a phase-2 bucket may hold
    # before it re-splits from observed volume, the partial-accumulator
    # compaction threshold, and the phase-1 spill fan-out.
    stream_bucket_rows: int = 1 << 21
    stream_combine_rows: int = 1 << 20
    stream_buckets: int = 32
    # Spill directory for streaming buckets (None: a fresh tempdir).
    stream_spill_dir: Optional[str] = os.environ.get(
        "DRYAD_TPU_STREAM_SPILL_DIR"
    ) or None
    # Chunk pipeline depth (exec.pipeline): how many chunks may be in
    # flight at once across ingest / device compute / readback — the
    # RChannelReader read-ahead budget (channelinterface.h:212).
    # 1 = the serial legacy driver (no prefetch thread, no background
    # spill writer, per-chunk host readback of partials).
    stream_pipeline_depth: int = 4
    # Topology- and distribution-aware combine trees (exec.combinetree):
    # streaming group_by partials accumulate into similarity-placed tree
    # groups whose level-0 merges ELIDE the hash exchange (partials are
    # already co-hash-partitioned, so equal keys are colocated and one
    # local reduce merges them — zero collective bytes), and only the
    # final fold pays a full exchange (on a hybrid mesh: one ICI hop +
    # exactly one DCN hop via the tree exchange).  Off = the flat
    # N-ary-merge combiner, kept as the differential baseline.
    combine_tree: bool = True
    # Max batches one tree-group flush folds in a single program
    # (stable fan-in -> stable shapes -> compile reuse).
    combine_tree_fan: int = 16
    # Tree groups (level-0 accumulators).  0 = auto: the DCN slice
    # count on a hybrid mesh, else 4.
    combine_tree_groups: int = 0
    # Host-degrade re-probe (flat combiner): after this many CONSECUTIVE
    # host combines that DO reduce below the device capacity check, the
    # device path is retried (the degrade decision is no longer sticky).
    # 0 disables re-probing.
    stream_host_reprobe: int = 2
    # Flight recorder (obs.flightrec): always-on bounded ring of recent
    # events + periodic health microsnapshots in every process, dumped
    # atomically to blackbox-<pid>.json on JobFailedError, unhandled
    # exceptions, and worker death (incl. the chaos os._exit path) —
    # crash forensics that survive the process.  Off = no ring, no
    # dump hooks.
    obs_flight_recorder: bool = True
    # Blackbox dump directory; None = the event_log_dir when set, else
    # the process working directory.
    flightrec_dir: Optional[str] = os.environ.get(
        "DRYAD_TPU_FLIGHTREC_DIR"
    ) or None
    # Online diagnosis engine (obs.diagnose): streaming folds over the
    # live event stream that detect named pathologies (recompile storm,
    # straggler, partition skew, stall dominance, quarantine churn,
    # combine-tree thrash, overflow loops) and emit schema-registered
    # ``diagnosis`` events; the straggler diagnosis seeds coded-spare
    # pre-launch.  Off = record-only observability (PR 3 behavior).
    obs_diagnosis: bool = True
    # Per-(rule, subject) re-diagnosis cooldown in seconds: a persistent
    # pathology re-announces at most this often instead of flooding the
    # stream it is diagnosing.
    diagnose_cooldown_s: float = 5.0
    # Async device-paced dispatch (exec.pipeline.DispatchWindow): how
    # many out-of-core chunk dispatches may be in flight before the
    # streaming driver blocks on its oldest readback.  The driver
    # thread only FEEDS (dispatch returns immediately); a background
    # collector thread drains readbacks strictly in submit order, so
    # chunk commit order — and therefore float accumulation order —
    # is identical to the serial loop and results stay byte-identical.
    # Overflow retries are detected at drain time and the retried
    # chunk re-enters the window.  1 = the serial dispatch-then-drain
    # legacy driver, kept as the differential baseline.
    dispatch_depth: int = 2
    # Cross-chunk plan fusion: the streaming driver lowers up to this
    # many chunk partial-plans as ONE multi-root program per dispatch
    # (api.context.DryadContext.run_many_to_host_async) — the chunk
    # chains land consecutively in the stage graph, so plan_fuse folds
    # them into a single dispatched region and K chunk round trips
    # collapse into one.  Each chunk remains its own computation inside
    # the region (per-chunk reduction order unchanged -> byte
    # identical).  1 = one chunk per dispatch (legacy).
    chunk_fuse: int = 1
    # Batched worker command streams (cluster.localjob/worker): up to
    # this many gang run commands ship per worker as ONE ``runbatch``
    # mailbox command with one aggregated status round trip (per-
    # command fault classification preserved in the aggregate).
    # 0 disables batching (one mailbox round trip per command).
    command_batch: int = 8
    # Worker-side combine, the gang tree's level -1 (cluster.localjob
    # submit_partitioned + cluster.worker ``combineparts``): after the
    # vertex wave, each gang worker pre-merges the un-finalized partial
    # state of the parts IT won (``exec.partial.merge_state_rows``) and
    # ships ONE folded partial plus its KeyRangeHistogram snapshot, so
    # driver ingress drops by the per-worker vertex fan-in and the
    # driver's level-0/1 tree merges per-WORKER partials.  Off = flat
    # per-vertex assembly, kept as the differential oracle.
    gang_combine_tree: bool = False
    # Overlapped gang command streams (cluster.gangwindow): how many
    # ``runbatch`` envelopes may be in flight per worker before
    # ``submit_many`` blocks on its oldest aggregated status.  The
    # driver only FEEDS; a collector drains statuses strictly in
    # submit order, so batch commit order is identical to the serial
    # loop.  1 = one blocking round trip per batch (the differential
    # baseline).
    gang_batch_depth: int = 1
    # Per-worker gang partition cache budget in host bytes
    # (cluster.partcache.PartitionCache): a worker keeps the result
    # partitions it wrote, content-fingerprint-keyed, so a later
    # sub-command referencing them (level -1 ``combineparts``) reads
    # from memory instead of the job root; entries LRU-evict by size
    # with spill-to-file (spilled entries stay servable).  0 disables.
    gang_partition_cache_bytes: int = 64 * 1024 * 1024
    # Serving tier (dryad_tpu.serve.QueryService): default per-tenant
    # admission quotas — max queries a tenant may have admitted-and-
    # unresolved at once, and the summed host-input bytes those admitted
    # queries may bind (0 = no byte budget).  Both are per-TENANT
    # defaults a session() call can override; admission past either
    # fails fast with a structured QueryRejected.
    serve_max_inflight: int = _env_int("DRYAD_TPU_SERVE_MAX_INFLIGHT", 32)
    serve_max_bytes: int = _env_int(
        "DRYAD_TPU_SERVE_MAX_BYTES", 1 << 30
    )
    # Plan-fingerprint result cache budget in host bytes (0 disables):
    # repeat queries whose lowered stage keys AND ingest binding
    # fingerprints match a resident entry resolve with ZERO device
    # dispatches; entries LRU-evict by size and invalidate on the
    # owning session's ingest-epoch bump.
    serve_result_cache_bytes: int = 256 * 1024 * 1024
    # Result-cache admission policy: "cost" admits an entry only when
    # its observed recompute time amortizes its bytes (at least
    # serve_cache_min_sec_per_gb seconds of saved work per cached GB),
    # so cheap-but-large results cannot evict expensive ones; "all" is
    # the legacy unconditional insert.
    serve_cache_admission: str = "cost"
    serve_cache_min_sec_per_gb: float = 0.5
    # Runtime plan rewriting (dryad_tpu.rewrite): the controller taps
    # the event stream, folds diagnosis events into RewriteActions,
    # and the drivers apply them at chunk/window boundaries.  Requires
    # obs_diagnosis; every rewrite is byte-identity-preserving (the
    # fuzz-differential suite runs this knob on vs off).
    plan_rewrite: bool = True
    # Continuous telemetry plane (dryad_tpu.obs.telemetry): a
    # ResourceMonitor taps the event stream and samples device HBM /
    # host RSS plus every shared flightrec probe on an interval,
    # feeding resource_sample events, rolling gauges, and the measured
    # HeadroomProvider that the adaptive exchange-window and
    # dispatch-depth policies consult.  Off = no sampler, adaptive
    # knobs fall back to configured budgets/defaults.
    obs_telemetry: bool = True
    # Query-scoped trace propagation (obs.tracectx): run_* entry
    # points mint a TraceContext so every span / exchange_round /
    # dispatch_gap / gang_window / diagnosis event is attributable to
    # one query (obs.critpath folds them into a critical-path
    # breakdown).  Off = events still flow, unstamped — no per-query
    # attribution.
    query_trace: bool = True

    def __post_init__(self) -> None:
        self.validate()

    def validate(self) -> None:
        if self.shuffle_slack < 1.0:
            raise ValueError("shuffle_slack must be >= 1.0")
        if self.intermediate_compression not in (None, "zlib"):
            raise ValueError("intermediate_compression must be None or 'zlib'")
        if not 0.0 < self.sample_rate <= 1.0:
            raise ValueError("sample_rate must be in (0, 1]")
        if self.max_shuffle_retries < 0:
            raise ValueError("max_shuffle_retries must be >= 0")
        if self.max_stage_failures < 1:
            raise ValueError("max_stage_failures must be >= 1")
        if self.outlier_sigmas <= 0:
            raise ValueError("outlier_sigmas must be > 0")
        if self.straggler_floor_ratio < 1.0:
            raise ValueError("straggler_floor_ratio must be >= 1.0")
        if self.coded_parity_tasks < 1:
            raise ValueError("coded_parity_tasks must be >= 1")
        if self.retry_backoff_base < 0:
            raise ValueError("retry_backoff_base must be >= 0")
        if self.retry_backoff_max < self.retry_backoff_base:
            raise ValueError(
                "retry_backoff_max must be >= retry_backoff_base"
            )
        if self.retry_jitter < 0:
            raise ValueError("retry_jitter must be >= 0")
        if self.rows_per_vertex < 1:
            raise ValueError("rows_per_vertex must be >= 1")
        if self.device_cache_bytes < 0:
            raise ValueError("device_cache_bytes must be >= 0")
        if self.overflow_sync_depth < 1:
            raise ValueError("overflow_sync_depth must be >= 1")
        if self.exchange_window < -1:
            raise ValueError(
                "exchange_window must be >= 0, or -1 for the auto policy"
            )
        if self.exchange_hbm_budget_mb < 1:
            raise ValueError("exchange_hbm_budget_mb must be >= 1")
        if self.tail_fanout_rows < 0:
            raise ValueError("tail_fanout_rows must be >= 0")
        if self.tail_rows_per_partition < 1:
            raise ValueError("tail_rows_per_partition must be >= 1")
        if self.stream_bucket_rows < 1:
            raise ValueError("stream_bucket_rows must be >= 1")
        if self.stream_combine_rows < 1:
            raise ValueError("stream_combine_rows must be >= 1")
        if self.stream_buckets < 2:
            raise ValueError("stream_buckets must be >= 2")
        if self.stream_pipeline_depth < 1:
            raise ValueError("stream_pipeline_depth must be >= 1")
        if self.diagnose_cooldown_s < 0:
            raise ValueError("diagnose_cooldown_s must be >= 0")
        if self.combine_tree_fan < 2:
            raise ValueError("combine_tree_fan must be >= 2")
        if self.combine_tree_groups < 0:
            raise ValueError("combine_tree_groups must be >= 0")
        if self.stream_host_reprobe < 0:
            raise ValueError("stream_host_reprobe must be >= 0")
        if self.dispatch_depth != -1 and self.dispatch_depth < 1:
            raise ValueError(
                "dispatch_depth must be >= 1, or -1 for the adaptive "
                "headroom policy"
            )
        if self.chunk_fuse < 1:
            raise ValueError("chunk_fuse must be >= 1")
        if self.command_batch < 0:
            raise ValueError("command_batch must be >= 0")
        if self.gang_batch_depth < 1:
            raise ValueError("gang_batch_depth must be >= 1")
        if self.gang_partition_cache_bytes < 0:
            raise ValueError("gang_partition_cache_bytes must be >= 0")
        if self.serve_max_inflight < 1:
            raise ValueError("serve_max_inflight must be >= 1")
        if self.serve_max_bytes < 0:
            raise ValueError("serve_max_bytes must be >= 0")
        if self.serve_result_cache_bytes < 0:
            raise ValueError("serve_result_cache_bytes must be >= 0")
        if self.serve_cache_admission not in ("cost", "all"):
            raise ValueError(
                "serve_cache_admission must be 'cost' or 'all'"
            )
        if self.serve_cache_min_sec_per_gb < 0:
            raise ValueError("serve_cache_min_sec_per_gb must be >= 0")


# Every ``DryadConfig`` field, one line each — THE documented key
# table.  The graftlint ``config-key`` rule cross-references this dict
# against the dataclass fields (both directions: every field is
# documented here; every documented key is a real field) AND against
# every ``config.<attr>`` / ``getattr(config, "attr", ...)`` use in the
# package, so a renamed or misspelled knob cannot silently read a
# default.
CONFIG_KEYS = {
    "max_stage_failures": "GM failure budget per stage before job failure",
    "shuffle_slack": "padded shuffle-bucket slack over uniform expectation",
    "max_shuffle_retries": "bounded shape palette for overflow retries",
    "intermediate_compression": "channel compression: None or 'zlib'",
    "sample_rate": "range-partition sampler rate (reference 0.1%)",
    "event_log_dir": "JSONL event-log directory (Calypso); None disables",
    "profile_dir": "XLA/JAX profiler output directory; None disables",
    "checkpoint_dir": "stage-output checkpoint directory; None disables",
    "checkpoint_retain_seconds": "checkpoint retention lease; None keeps",
    "outlier_sigmas": "speculative-duplication outlier threshold (sigmas)",
    "straggler_floor_ratio": "straggler-threshold floor over trimmed mean",
    "coded_redundancy": "k-of-n coded spares for linear partial aggregates",
    "coded_parity_tasks": "max parity spares r per coded stage",
    "retry_backoff_base": "transient-retry backoff base seconds",
    "retry_backoff_max": "transient-retry backoff cap seconds",
    "retry_jitter": "seeded retry-backoff jitter fraction",
    "retry_seed": "retry-jitter RNG seed",
    "broadcast_limit": "broadcast-join max replicated right-side rows",
    "topk_limit": "order_by+take fuses to shuffle-free top-k at or below",
    "auto_dense_strings": "single-STRING-key group_by lowers to MXU buckets",
    "auto_dense_ints": "bounded-INT32-key group_by rides the dense path",
    "auto_dense_limit": "dense-key domain cap for the MXU bucket path",
    "stringcode_runtime_tables": "code tables ship as palette operands",
    "device_cache_bytes": "device-resident ingest cache budget; 0 off",
    "rows_per_vertex": "target rows per independent vertex task",
    "plan_fuse": "whole-DAG SPMD fusion into one dispatched program",
    "overflow_sync_depth": "speculative dispatches per overflow readback",
    "exchange_window":
        "staged-exchange buckets per round (0 = flat, -1 = auto policy)",
    "exchange_hbm_budget_mb":
        "staging-buffer HBM budget for the auto exchange-window policy",
    "tail_fanout_rows": "static row bound enabling tail fan-out; 0 off",
    "tail_rows_per_partition": "rows per partition after tail fan-out",
    "stream_bucket_rows": "max rows per phase-2 bucket before re-split",
    "stream_combine_rows": "partial-accumulator compaction threshold",
    "stream_buckets": "phase-1 spill fan-out (bucket count)",
    "stream_spill_dir": "spill directory; None = fresh tempdir",
    "stream_pipeline_depth": "chunks in flight across the ooc pipeline",
    "combine_tree": "topology-aware hierarchical streaming combines",
    "combine_tree_fan": "max batches folded per tree-group flush",
    "combine_tree_groups": "level-0 tree groups; 0 = auto from topology",
    "stream_host_reprobe": "reducing host combines before device re-probe",
    "obs_flight_recorder": "crash-forensics ring + blackbox dump hooks",
    "flightrec_dir": "blackbox dump dir; None = event_log_dir or cwd",
    "obs_diagnosis": "online pathology detection over the live stream",
    "diagnose_cooldown_s": "per-(rule, subject) re-diagnosis cooldown",
    "dispatch_depth": "ooc chunk dispatches in flight; 1 = serial "
                      "driver, -1 = adaptive from measured headroom",
    "chunk_fuse": "chunk partial-plans lowered per dispatch; 1 = legacy",
    "command_batch": "gang run commands per runbatch round trip; 0 off",
    "gang_combine_tree": "worker-side level -1 partial pre-merge",
    "gang_batch_depth": "runbatch envelopes in flight per worker; 1 serial",
    "gang_partition_cache_bytes": "worker partition cache budget; 0 off",
    "serve_max_inflight": "per-tenant admitted-query cap (QueryRejected)",
    "serve_max_bytes": "per-tenant admitted host-input byte budget; 0 off",
    "serve_result_cache_bytes": "plan-fingerprint result cache; 0 off",
    "serve_cache_admission":
        "result-cache admission: 'cost' (amortizing only) or 'all'",
    "serve_cache_min_sec_per_gb":
        "cost admission floor: saved seconds per cached GB",
    "plan_rewrite": "runtime plan rewriter (dryad_tpu.rewrite); "
                    "diagnosis-driven, byte-identity-preserving",
    "obs_telemetry": "continuous resource sampler + measured headroom",
    "query_trace": "query-scoped trace propagation (obs.tracectx); "
                   "qid-stamps events for critical-path attribution",
}
