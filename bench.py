"""Benchmark: flagship WordCount/TeraSort pipelines on the accelerator.

``python bench.py [--lint-gate] [--obs-overhead] [names...]`` is ONE
process.  It takes the platform jax gives it — no probe, no fallback to
another platform, no child that needs the chip — stamps ``platform``,
``device_kind`` and ``device_count`` on every record, and exits
non-zero if any metric errored.  The CPU-pinned child cells
(``CPU_PINNED``) are stamped ``platform: cpu``: a number is recorded
under the device it ran on.  ``tests_tpu/`` is its own command, run
after this process has exited (one process holds a chip at a time).

Every metric is printed to stdout as its own JSON line the moment it
is computed, and an updated SUMMARY line (the `{"metric": ...,
"value": ..., "unit": ..., "vs_baseline": ...}` contract) is re-printed
after every metric, so a killed run still leaves every completed
number in the tail.

Structure:
- host NumPy baseline first (no device, seconds);
- each device metric: ONE compile, then 3 timed reps; we report the
  best rep, the per-rep list, and flag ``contended: true`` when the
  rep spread (max/min) exceeds 5x (a contended number is tagged, not
  trusted);
- a wall-clock budget (env DRYAD_BENCH_BUDGET, default 480s): before
  each metric we check remaining time against its cost estimate and
  skip-and-report instead of getting killed mid-compile.

Workload shapes: group-reduce core (the device kernel behind GroupBy),
WordCount end-to-end through DryadContext (reference
``DryadLinqTests/WordCount.cs:58-61``), TeraSort end-to-end
(``RangePartitionAPICoverageTests.cs``), and the dense-key MXU bucket
path (Pallas vs XLA).  Turning this file into sized cells with a
recorded ``why`` is ROADMAP D1.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

T_START = time.monotonic()
BUDGET = float(os.environ.get("DRYAD_BENCH_BUDGET", "480"))
# jax's platform for this process, set by run_metrics() and stamped
# into the records the metric functions build themselves.
_PLATFORM: str = "unset"

SUMMARY: dict = {
    "metric": "group_reduce_rows_per_sec",
    "value": 0.0,
    "unit": "rows/s",
    "vs_baseline": 0.0,
}


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic()-T_START:5.1f}s] {msg}",
          file=sys.stderr, flush=True)


def emit(record: dict) -> None:
    """One NDJSON record + an updated summary line (kill-safe tail).
    Every record carries a
    ``diagnoses`` block: the pathologies the online engine
    (obs.diagnose) caught while the metric ran — a benchmark number
    measured during a recompile storm or partition skew is not the
    number you think it is."""
    try:
        from dryad_tpu.obs.diagnose import drain_recent

        record.setdefault("diagnoses", [
            {"rule": d["rule"], "severity": d["severity"],
             "subject": d["subject"], "evidence": d["evidence"]}
            for d in drain_recent()
        ])
    except Exception:
        record.setdefault("diagnoses", [])
    print(json.dumps(record), flush=True)
    print(json.dumps(SUMMARY), flush=True)


def remaining() -> float:
    return BUDGET - (time.monotonic() - T_START)


def timed_reps(fn, reps: int = 3):
    """fn() must block on completion.  Returns (best_s, [rep_s...])."""
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times), times


def rep_record(name: str, rows: int, times, extra: dict = {}) -> dict:
    best = min(times)
    spread = max(times) / max(min(times), 1e-12)
    rec = {
        "metric": name,
        "value": round(rows / best, 1),
        "unit": "rows/s",
        "best_s": round(best, 5),
        "reps_s": [round(t, 5) for t in times],
        "spread": round(spread, 2),
        "contended": spread > 5.0,
        "rows": rows,
        "platform": _PLATFORM,
    }
    rec.update(extra)
    return rec


# -- metrics ----------------------------------------------------------------

def host_baseline_rows_per_sec(n: int = 1 << 20, keys: int = 1 << 12) -> float:
    """Single-core NumPy group-aggregate (bincount + the stable argsort a
    comparable engine pays for grouped output)."""
    rng = np.random.default_rng(0)
    k = rng.integers(0, keys, n).astype(np.int32)
    v = rng.standard_normal(n).astype(np.float32)

    def run():
        s = np.bincount(k, weights=v, minlength=keys)
        c = np.bincount(k, minlength=keys)
        order = np.argsort(k, kind="stable")
        _ = k[order]
        assert s.shape == c.shape

    best, times = timed_reps(run)
    emit(rep_record("host_baseline_rows_per_sec", n, times))
    return n / best


def group_reduce_metric(n: int, keys: int = 1 << 12, iters: int = 4):
    """The general sort-based segmented group-reduce (the kernel behind
    GroupBy on arbitrary keys): ONE compiled program running ``iters``
    on-device iterations (lax.fori_loop, checksum carry defeats DCE,
    per-iteration key mix defeats CSE)."""
    import jax
    import jax.numpy as jnp

    from dryad_tpu.columnar.batch import ColumnBatch
    from dryad_tpu.ops.segmented import AggSpec, group_reduce

    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.integers(0, keys, n).astype(np.int32))
    v = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    valid = jnp.ones((n,), jnp.bool_)

    @jax.jit
    def run(k, v, valid):
        def body(i, acc):
            b = ColumnBatch({"k": k ^ i, "v": v}, valid)
            out = group_reduce(
                b, ["k"], [AggSpec("sum", "v", "s"), AggSpec("count", None, "c")]
            )
            return acc + jnp.sum(jnp.where(out.valid, out.data["s"], 0.0))

        return jax.lax.fori_loop(0, iters, body, jnp.float32(0.0))

    t0 = time.perf_counter()
    float(run(k, v, valid))
    compile_s = time.perf_counter() - t0
    log(f"group_reduce compiled in {compile_s:.1f}s")
    best, times = timed_reps(lambda: float(run(k, v, valid)))
    rows = n * iters
    return rep_record(
        "group_reduce_rows_per_sec", rows, times,
        {"n": n, "keys": keys, "iters": iters,
         "compile_s": round(compile_s, 1)},
    )


def dense_path_metric(
    name: str, n: int, use_pallas: bool, keys: int = 1 << 12,
    iters: int = 32,
):
    """Dense-key MXU bucket reduce: Pallas kernel vs pure-XLA fallback
    (same math) — the GroupBy fast path for dictionary/categorical keys.

    ``iters`` on-device iterations run inside ONE program
    (lax.fori_loop, per-iteration key mix defeats CSE, scalar readback
    forces completion) so the fixed per-dispatch cost doesn't swamp a
    kernel that does the real work in single-digit milliseconds."""
    import jax
    import jax.numpy as jnp

    from dryad_tpu.ops.pallas_bucket import bucket_sum_count

    rng = np.random.default_rng(2)
    k = jnp.asarray(rng.integers(0, keys, n).astype(np.int32))
    v = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    valid = jnp.ones((n,), jnp.bool_)
    interp = None if use_pallas else False

    @jax.jit
    def run(k, v, valid):
        def body(i, acc):
            sums, cnt = bucket_sum_count(
                k ^ i, [v], valid, keys, interpret=interp
            )
            return acc + jnp.sum(sums[0]) + jnp.sum(cnt)

        return jax.lax.fori_loop(0, iters, body, jnp.float32(0.0))

    t0 = time.perf_counter()
    float(run(k, v, valid))
    compile_s = time.perf_counter() - t0
    log(f"{name} compiled in {compile_s:.1f}s")
    best, times = timed_reps(lambda: float(run(k, v, valid)))
    return rep_record(
        name, n * iters, times,
        {"keys": keys, "iters": iters, "compile_s": round(compile_s, 1)},
    )


def wordcount_metric(n: int, vocab_size: int = 1 << 14):
    """WordCount end-to-end THROUGH DryadContext on the device: token
    table (native-tokenized STRING column) -> group_by count ->
    order_by count -> collect.  The STRING group_by auto-lowers to the
    dense MXU bucket path (dictionary codes, no shuffle —
    ops/stringcode.py) when the vocabulary fits auto_dense_limit, which
    this shape does; ingest text is tokenized ONCE by the native
    runtime, and warm reps reuse the device-resident ingest.
    Reference shape: ``DryadLinqTests/WordCount.cs:58-61``."""
    import tempfile

    from dryad_tpu import DryadContext

    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab_size, n)
    text = " ".join(f"w{int(i):05d}" for i in ids)
    with tempfile.NamedTemporaryFile("w", suffix=".txt", delete=False) as fh:
        fh.write(text)
        path = fh.name
    try:
        ctx = DryadContext()
        q = ctx.from_text(path, column="word")

        def run():
            out = (
                q.group_by("word", {"count": ("count", None)})
                .order_by([("count", True)])
                .collect()
            )
            assert int(np.sum(out["count"])) == n

        # Warm reps reuse the device-resident ingest (context device
        # cache): they measure dispatch + device pipeline + egress, the
        # steady-state of repeated queries over a resident table.
        return compile_then_reps(
            "wordcount_rows_per_sec", run, n,
            {"vocab": vocab_size, "ingest_cached": True},
        )
    finally:
        os.unlink(path)


def wordcount_dense_metric(n: int, vocab_size: int = 1 << 14):
    """WordCount on the MXU path: REAL tokens dictionary-encode to
    dense categorical codes at ingest (np.unique over the token array,
    done ONCE — the same once-at-ingest policy as wordcount_metric's
    tokenization), then the count reduces via the one-hot-matmul bucket
    kernel + one psum_scatter (`group_by(dense=K)`) — no sort, no
    shuffle.  Reps measure the post-ingest device pipeline."""
    from dryad_tpu import DryadContext

    rng = np.random.default_rng(0)
    words = np.array(
        [f"w{int(i):05d}" for i in rng.integers(0, vocab_size, n)], object
    )
    _vocab, codes = np.unique(words, return_inverse=True)
    codes = codes.astype(np.int32)
    vocab_size = len(_vocab)
    ctx = DryadContext()
    q = ctx.from_arrays({"word": codes})

    def run():
        out = q.group_by(
            "word", {"count": ("count", None)}, dense=vocab_size
        ).collect()
        assert int(np.sum(out["count"])) == n

    return compile_then_reps(
        "wordcount_dense_rows_per_sec", run, n, {"vocab": vocab_size}
    )


def compile_then_reps(name: str, run, rows: int, extra: dict = {}):
    """Shared end-to-end measurement protocol: one warm run (compile +
    ingest, both cached), then timed reps of the steady state."""
    t0 = time.perf_counter()
    run()
    compile_s = time.perf_counter() - t0
    log(f"{name} compiled+warmed in {compile_s:.1f}s")
    best, times = timed_reps(run)
    return rep_record(
        name, rows, times, {"compile_s": round(compile_s, 1), **extra}
    )


def groupby_e2e_metric(n: int, keys: int = 1 << 12):
    """GroupBy end-to-end THROUGH DryadContext: ingest-bounded INT32
    keys ride the int auto-dense rewrite (MXU bucket / scatter path,
    no shuffle) — the engine's ACTUAL general-key group path for the
    common categorical shape, vs the raw sort-path kernel that
    ``group_reduce_rows_per_sec`` measures."""
    from dryad_tpu import DryadContext

    rng = np.random.default_rng(5)
    tbl = {
        "k": rng.integers(0, keys, n).astype(np.int32),
        "v": rng.standard_normal(n).astype(np.float32),
    }
    ctx = DryadContext()
    q = ctx.from_arrays(tbl).group_by(
        "k", {"c": ("count", None), "s": ("sum", "v")}
    )

    def run():
        out = q.collect()
        assert int(np.sum(out["c"])) == n

    return compile_then_reps(
        "groupby_e2e_rows_per_sec", run, n,
        {"keys": keys, "ingest_cached": True, "path": "int-auto-dense"},
    )


def hdfs_ingest_metric(n: int = 1 << 21):
    """Ingest through the REAL WebHDFS protocol (ranged OPEN with the
    namenode->datanode redirect, chunk-parallel reads): write a
    partitioned store to an in-tree stub namenode over loopback, then
    measure ``from_store("hdfs://...")`` -> collect end to end — the
    BASELINE 1TB-ingest north-star shape at bench scale
    (``DrHdfsClient.cpp:32-69`` / ``channelbufferhdfs.cpp`` parity)."""
    import tempfile

    from dryad_tpu import DryadContext
    from dryad_tpu.tools.webhdfs_stub import WebHdfsStubServer

    os.environ.pop("DRYAD_TPU_DFS_GATEWAY", None)
    rng = np.random.default_rng(3)
    tbl = {
        "k": rng.integers(0, 1 << 20, n).astype(np.int32),
        "v": rng.standard_normal(n).astype(np.float32),
    }
    nbytes = sum(a.nbytes for a in tbl.values())
    root = tempfile.mkdtemp(prefix="bench-hdfs-")
    with WebHdfsStubServer(root) as srv:
        uri = f"hdfs://{srv.host}:{srv.port}/bench/t1"
        ctx = DryadContext()
        t0 = time.perf_counter()
        ctx.from_arrays(tbl).to_store(uri)
        write_s = time.perf_counter() - t0
        log(f"hdfs egress {nbytes/1e6:.0f}MB in {write_s:.1f}s")

        def run():
            c = DryadContext()
            out = c.from_store(uri).count()
            assert out == n

        best, times = timed_reps(run, reps=3)
        rec = rep_record(
            "hdfs_ingest_rows_per_sec", n, times,
            {"mb": round(nbytes / 1e6, 1),
             "mb_per_s": round(nbytes / 1e6 / best, 1),
             "egress_s": round(write_s, 2),
             "protocol": "webhdfs", "redirects": srv.redirects},
        )
        return rec


def _terasort_inputs(n: int):
    """Shared generator so the e2e and device-verified terasort metrics
    measure the SAME sort on the SAME data."""
    from dryad_tpu import DryadContext

    rng = np.random.default_rng(1)
    keys = rng.integers(-(2 ** 31), 2 ** 31 - 1, n).astype(np.int32)
    payload = rng.standard_normal(n).astype(np.float32)
    return keys, payload, DryadContext()


def terasort_metric(n: int):
    """TeraSort end-to-end THROUGH DryadContext: random keys + payload ->
    sampled-splitter range partition -> local sort -> collect.
    Reference shape: ``RangePartitionAPICoverageTests.cs``."""
    keys, payload, ctx = _terasort_inputs(n)
    q = ctx.from_arrays({"key": keys, "payload": payload})

    def run():
        out = q.order_by(["key"]).collect()
        assert len(out["key"]) == n

    return compile_then_reps(
        "terasort_rows_per_sec", run, n, {"ingest_cached": True}
    )


def terasort_device_metric(n: int):
    """TeraSort with DEVICE-SIDE verification: the same range-partition
    + local-sort engine path, but the sorted output reduces to one
    rank-weighted checksum on device — a single scalar readback per
    rep.  Isolates chip sort throughput from egress bandwidth: the
    plain terasort metric ships EVERY sorted row to the driver, which
    measures egress bandwidth as much as the sort (real deployments
    write output worker-side, as the reference's vertices do —
    ``RangePartitionAPICoverageTests.cs`` outputs to partfiles)."""
    from dryad_tpu.columnar.schema import ColumnType, Schema

    keys, payload, ctx = _terasort_inputs(n)
    q = (
        ctx.from_arrays({"key": keys, "payload": payload})
        .order_by([("key", "asc")])
        .with_rank("r")
        .select(
            lambda c: {"w": c["r"].astype("float32") * c["payload"]},
            schema=Schema([("w", ColumnType.FLOAT32)]),
        )
        .aggregate_as_query({"chk": ("sum", "w")})
    )
    order = np.argsort(keys, kind="stable")
    ref = float(
        (np.arange(n, dtype=np.float64) * payload[order].astype(np.float64)).sum()
    )

    def run():
        got = float(q.collect()["chk"][0])
        assert abs(got - ref) <= 1e-3 * max(1.0, abs(ref)), (got, ref)

    return compile_then_reps(
        "terasort_device_rows_per_sec", run, n, {"ingest_cached": True}
    )


def _job_phases(ctx) -> dict:
    """Per-phase metric summary folded from the context's event stream
    (obs.metrics.JobMetrics): compile_s, stall seconds, spill bytes,
    padding-waste ratio — so BENCH records say where time went, not
    just rows/s."""
    from dryad_tpu.obs.metrics import JobMetrics

    return JobMetrics.from_events(ctx.events.events()).attribution()


def _ooc_sort_once(n: int, chunk_rows: int, depth=None, obs=True):
    """One timed out-of-core sort run; returns (seconds, phases).
    ``depth`` overrides ``stream_pipeline_depth`` (1 = the serial
    legacy driver, the pre-pipeline baseline); ``obs=False`` turns the
    always-on observability layer (flight recorder + diagnosis
    engine + continuous telemetry sampler + query trace propagation)
    off for the --obs-overhead A/B."""
    from dryad_tpu import DryadConfig, DryadContext

    rng = np.random.default_rng(3)
    nchunks = max(1, n // chunk_rows)
    chunks = [
        {"key": rng.integers(-(2 ** 31), 2 ** 31 - 1, chunk_rows).astype(
            np.int32)}
        for _ in range(nchunks)
    ]
    total = nchunks * chunk_rows
    bucket_rows = max(chunk_rows, 1 << 20)
    kw = {} if depth is None else {"stream_pipeline_depth": depth}
    if not obs:
        kw.update(
            obs_flight_recorder=False,
            obs_diagnosis=False,
            obs_telemetry=False,
            query_trace=False,
        )
    cfg = DryadConfig(
        stream_bucket_rows=bucket_rows * 2,
        stream_buckets=max(8, 2 * total // bucket_rows),
        **kw,
    )
    ctx = DryadContext(config=cfg)
    t0 = time.perf_counter()
    q = ctx.from_stream(
        iter([{k: v for k, v in c.items()} for c in chunks])
    ).order_by(["key"])
    out = q.collect()
    t = time.perf_counter() - t0
    assert len(out["key"]) == total
    assert (np.diff(out["key"]) >= 0).all()
    return t, _job_phases(ctx)


def ooc_sort_metric(n: int, chunk_rows: int = 1 << 21):
    """Out-of-core TeraSort at >= 16x the single-batch device capacity:
    chunked ingest -> range-bucket spill -> per-bucket device sort
    (exec.outofcore external distribution sort), through the chunk
    pipeline (exec.pipeline: prefetch / compute / background spill
    overlap, observed-size bucket capacities).  HBM held to the
    pipeline-depth chunk budget; the reference's streaming channel
    stack handles the same scale via bounded buffers
    (``channelbuffernativereader.cpp``)."""
    from dryad_tpu import DryadConfig

    nchunks = max(1, n // chunk_rows)
    total = nchunks * chunk_rows
    bucket_rows = max(chunk_rows, 1 << 20)
    t, phases = _ooc_sort_once(n, chunk_rows)
    return rep_record(
        "oocsort_rows_per_sec", total, [t],
        {"chunks": nchunks, "chunk_rows": chunk_rows,
         "bounded_hbm_rows": max(chunk_rows, 2 * bucket_rows),
         "capacity_multiple": nchunks,
         "pipeline_depth": DryadConfig().stream_pipeline_depth,
         "phases": phases},
    )


def ooc_pipeline_speedup_metric(n: int, chunk_rows: int = 1 << 20):
    """Pipelined vs serial out-of-core driver on the SAME sort
    workload: ``stream_pipeline_depth=1`` runs the pre-pipeline serial
    loop (fixed worst-case bucket layouts, per-chunk host readback,
    synchronous spill), the default depth runs the chunk pipeline.
    Value is the wall-clock ratio serial/pipelined — measured, both
    runs in this process.  ``cores`` is recorded because the overlap
    half of the win needs >1 host core; the work-elimination half
    (observed-size bucket capacities, cached chunk plans, device-
    resident partials) shows on any host."""
    from dryad_tpu import DryadConfig

    depth = DryadConfig().stream_pipeline_depth
    t_piped, phases_piped = _ooc_sort_once(n, chunk_rows)
    t_serial, phases_serial = _ooc_sort_once(n, chunk_rows, depth=1)
    ratio = t_serial / max(t_piped, 1e-9)
    return {
        "metric": "ooc_pipeline_speedup",
        "value": round(ratio, 3),
        "unit": "x",
        "depth": depth,
        "baseline": "serial legacy driver (stream_pipeline_depth=1)",
        "pipelined_s": round(t_piped, 3),
        "serial_s": round(t_serial, 3),
        "phases": phases_piped,
        "phases_serial": phases_serial,
        "rows": n,
        "chunk_rows": chunk_rows,
        "cores": os.cpu_count(),
        "platform": _PLATFORM,
        "contended": False,
        "spread": 1.0,
        "reps_s": [round(t_piped, 3)],
    }


def _asyncpipe_once(n: int, chunk_rows: int, depth: int):
    """One timed ooc sort at an explicit ``dispatch_depth`` (depth 1 =
    the serial pre-window baseline); prefetch pipelining is pinned OFF
    so the dispatch window is the only overlap mechanism under test.
    Returns (rows, wall_s, process_cpu_s, driver_thread_cpu_s,
    JobMetrics)."""
    import resource

    from dryad_tpu import DryadConfig, DryadContext
    from dryad_tpu.obs.metrics import JobMetrics

    rng = np.random.default_rng(3)
    nchunks = max(8, n // chunk_rows)
    chunks = [
        {"key": rng.integers(-(2 ** 31), 2 ** 31 - 1, chunk_rows).astype(
            np.int32)}
        for _ in range(nchunks)
    ]
    total = nchunks * chunk_rows
    bucket_rows = max(chunk_rows, 1 << 20)
    cfg = DryadConfig(
        stream_bucket_rows=bucket_rows * 2,
        stream_buckets=max(8, 2 * total // bucket_rows),
        stream_pipeline_depth=1,
        dispatch_depth=depth,
    )
    ctx = DryadContext(config=cfg)
    ru0 = resource.getrusage(resource.RUSAGE_SELF)
    tc0 = time.thread_time()
    t0 = time.perf_counter()
    q = ctx.from_stream(iter([dict(c) for c in chunks])).order_by(["key"])
    out = q.collect()
    wall = time.perf_counter() - t0
    drv_cpu = time.thread_time() - tc0
    ru1 = resource.getrusage(resource.RUSAGE_SELF)
    proc_cpu = (ru1.ru_utime + ru1.ru_stime) - (ru0.ru_utime + ru0.ru_stime)
    assert len(out["key"]) == total
    assert (np.diff(out["key"]) >= 0).all()
    return total, wall, proc_cpu, drv_cpu, JobMetrics.from_events(
        ctx.events.events()
    )


def _asyncpipe_batching(nrows: int = 20_000, nqueries: int = 6):
    """Batched vs one-command-per-round-trip gang submission of the
    SAME ``nqueries`` jobs on a 2-worker gang: byte-identical results,
    mailbox round trips counted on the driver side."""
    from dryad_tpu import DryadContext
    from dryad_tpu.cluster.localjob import LocalJobSubmission

    rng = np.random.default_rng(5)
    tbl = {
        "k": rng.integers(0, 64, nrows).astype(np.int32),
        "v": rng.integers(-1000, 1000, nrows).astype(np.int32),
    }
    with LocalJobSubmission(num_workers=2, devices_per_worker=1) as sub:
        # the driver context only builds the plan; its partition count
        # is capped by the devices THIS process can see, independent
        # of the 2-device worker mesh
        import jax

        ctx = DryadContext(
            num_partitions_=min(2, len(jax.devices()))
        )

        def mkq():
            return ctx.from_arrays(tbl).group_by(
                "k", {"c": ("count", None), "s": ("sum", "v")}
            )

        sub.submit(mkq())  # warm package/compile caches on both workers
        rt0 = sub.round_trips
        t0 = time.perf_counter()
        serial = sub.submit_many([mkq() for _ in range(nqueries)], batch=1)
        t_serial = time.perf_counter() - t0
        rt_serial = sub.round_trips - rt0
        rt0 = sub.round_trips
        t0 = time.perf_counter()
        batched = sub.submit_many(
            [mkq() for _ in range(nqueries)], batch=nqueries
        )
        t_batched = time.perf_counter() - t0
        rt_batched = sub.round_trips - rt0
        for a, b in zip(serial, batched):
            for cname in a:
                assert a[cname].tobytes() == b[cname].tobytes()
    return {
        "queries": nqueries,
        "workers": 2,
        "round_trips_unbatched": rt_serial,
        "round_trips_batched": rt_batched,
        "round_trip_reduction": round(
            rt_serial / max(rt_batched, 1), 2
        ),
        "unbatched_s": round(t_serial, 3),
        "batched_s": round(t_batched, 3),
    }


def asyncpipe_metric(n: int, chunk_rows: int = 1 << 17, nqueries: int = 6):
    """Async device-paced dispatch matrix on the oocsort-shaped stream:
    dispatch_depth {1, 2, 4} (1 = serial baseline), then gang command
    batching on/off on a 2-worker cluster.  Per depth: rows/s, window
    dispatches, summed device-idle gap between dispatches
    (``dispatch_gap_s``), the window's driver-thread CPU fraction
    (JobMetrics, thread_time-based), and whole-run driver-thread /
    process CPU via ``time.thread_time`` + ``resource.getrusage``.
    CPU-host caveat: the "device" compute shares the host with the
    driver here, so absolute CPU fractions are upper bounds — the
    depth-4-vs-1 DELTA is the signal, not the level."""
    depths = {}
    t_by_depth = {}
    for depth in (1, 2, 4):
        total, wall, proc_cpu, drv_cpu, m = _asyncpipe_once(
            n, chunk_rows, depth
        )
        t_by_depth[depth] = wall
        depths[str(depth)] = {
            "rows_per_sec": round(total / max(wall, 1e-9), 1),
            "wall_s": round(wall, 3),
            "window_dispatches": m.window_dispatches,
            "dispatch_gap_s": round(m.dispatch_gap_s, 4),
            "driver_cpu_fraction": round(m.driver_cpu_fraction, 4),
            "dispatch_retries": m.dispatch_retries,
            "driver_thread_cpu_fraction": round(
                min(drv_cpu / max(wall, 1e-9), 1.0), 4
            ),
            "process_cpu_s": round(proc_cpu, 3),
        }
    batching = _asyncpipe_batching(nqueries=nqueries)
    total = max(8, n // chunk_rows) * chunk_rows
    return {
        "metric": "asyncpipe_rows_per_sec",
        "value": round(total / max(t_by_depth[4], 1e-9), 1),
        "unit": "rows/s",
        "baseline": "dispatch_depth=1 serial driver loop",
        "speedup_vs_serial": round(
            t_by_depth[1] / max(t_by_depth[4], 1e-9), 3
        ),
        "rows": total,
        "chunk_rows": chunk_rows,
        "depths": depths,
        "command_batching": batching,
        "cores": os.cpu_count(),
        "platform": _PLATFORM,
        "contended": False,
        "spread": 1.0,
        "reps_s": [round(t_by_depth[4], 3)],
    }


def gangtree_metric(nrows: int = 1 << 16, nqueries: int = 8):
    """Gang hot path matrix on a 4-worker gang: worker-side combine
    tree off/on (submit_partitioned at fan-in 4 per worker) crossed
    with command-window depth {1, 2} (submit_many, J=``nqueries``
    queries at command_batch=2).  Per cell: rows/s plus the three
    ingress numbers the tree exists to shrink — driver-ingress wire
    bytes (assemble_fetch), mailbox round trips, and job-root re-read
    bytes on the workers (0 once the partition cache is warm) — and
    the window's peak envelopes in flight (>= 2 proves the overlap).
    Byte-identity against the flat/serial cell is asserted, not
    assumed.  Host-bound: the workers pin JAX_PLATFORMS=cpu on any
    backend, so the structure transfers while absolute rows/s is a
    CPU number."""
    from dryad_tpu import DryadConfig, DryadContext
    from dryad_tpu.cluster.localjob import LocalJobSubmission
    from dryad_tpu.obs.metrics import JobMetrics

    # fan-in 8 per worker: every part holds (almost) the full key set,
    # so the per-worker fold shrinks rows ~8x and ingress ~6x after
    # per-file header overhead
    workers, nparts = 4, 32
    rng = np.random.default_rng(7)
    tbl = {
        "k": rng.integers(0, 128, nrows).astype(np.int32),
        "v": rng.integers(-1000, 1000, nrows).astype(np.int32),
    }

    def mkq(**cfg):
        ctx = DryadContext(num_partitions_=1, config=DryadConfig(**cfg))
        return ctx.from_arrays(tbl).group_by(
            "k", {"c": ("count", None), "s": ("sum", "v"),
                  "mn": ("min", "v")}
        )

    def ingress(evs):
        return sum(
            int(e.get("wire_bytes", 0) or 0)
            for e in evs if e["kind"] == "assemble_fetch"
        )

    out = {"workers": workers, "nparts": nparts, "queries": nqueries}
    with LocalJobSubmission(
        num_workers=workers, devices_per_worker=1
    ) as sub:
        # -- worker-tree half: partitioned vertex tasks, tree off/on --
        sub.submit_partitioned(  # warm package/compile caches
            mkq(), nparts=nparts, coded=False
        )
        tree_cells = {}
        baseline = None
        for on in (False, True):
            n0 = len(sub.events.events())
            rt0 = sub.round_trips
            t0 = time.perf_counter()
            res = sub.submit_partitioned(
                mkq(gang_combine_tree=on), nparts=nparts, coded=False
            )
            wall = time.perf_counter() - t0
            evs = sub.events.events()[n0:]
            m = JobMetrics.from_events(evs)
            if baseline is None:
                baseline = res
            else:
                for c in baseline:
                    assert baseline[c].tobytes() == res[c].tobytes(), c
            tree_cells[f"tree_{'on' if on else 'off'}"] = {
                "rows_per_sec": round(nrows / max(wall, 1e-9), 1),
                "wall_s": round(wall, 3),
                "driver_ingress_bytes": ingress(evs),
                "round_trips": sub.round_trips - rt0,
                "job_root_read_bytes": m.gang_root_read_bytes,
                "cache_hits": m.gang_cache_hits,
                "premerged_parts": m.gang_premerge_parts,
            }
        out["tree"] = tree_cells
        out["ingress_reduction"] = round(
            tree_cells["tree_off"]["driver_ingress_bytes"]
            / max(tree_cells["tree_on"]["driver_ingress_bytes"], 1), 2
        )

        # -- window half: J queries through submit_many, depth 1 vs 2 --
        def many(depth):
            qs = [
                mkq(command_batch=2, gang_batch_depth=depth)
                for _ in range(nqueries)
            ]
            n0 = len(sub.events.events())
            rt0 = sub.round_trips
            t0 = time.perf_counter()
            res = sub.submit_many(qs)
            wall = time.perf_counter() - t0
            m = JobMetrics.from_events(sub.events.events()[n0:])
            return res, {
                "rows_per_sec": round(
                    nqueries * nrows / max(wall, 1e-9), 1
                ),
                "wall_s": round(wall, 3),
                "round_trips": sub.round_trips - rt0,
                "peak_in_flight": m.gang_peak_in_flight,
                "window_retries": m.gang_retries,
            }

        serial, cell1 = many(1)
        windowed, cell2 = many(2)
        for a, b in zip(serial, windowed):
            for c in a:
                assert a[c].tobytes() == b[c].tobytes(), c
        assert cell2["peak_in_flight"] >= 2, cell2
        out["window"] = {"depth_1": cell1, "depth_2": cell2}

    best = max(
        tree_cells["tree_on"]["rows_per_sec"],
        out["window"]["depth_2"]["rows_per_sec"],
    )
    out.update({
        "metric": "gangtree_rows_per_sec",
        "value": best,
        "unit": "rows/s",
        "baseline": "flat driver assembly + serial depth-1 windows",
        "rows": nrows,
        "cores": os.cpu_count(),
        "platform": _PLATFORM,
        "contended": False,
        "spread": 1.0,
        "reps_s": [out["window"]["depth_2"]["wall_s"]],
    })
    return out


# Child body for aggtree_metric: the hybrid (DCN x ICI) mesh needs 8
# virtual devices, and the parent process may already have initialized
# its backend with a different device count, so the whole matrix runs
# in a fresh subprocess that forces the mesh shape FIRST and prints one
# JSON result line.
_AGGTREE_CHILD = r"""
import json, os, sys, time
import numpy as np

from dryad_tpu.parallel.mesh import force_cpu_backend

force_cpu_backend(8)

import jax

from dryad_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()  # reruns skip the pow2-palette compiles

from dryad_tpu import DryadConfig, DryadContext

nchunks, chunk_rows = int(sys.argv[1]), int(sys.argv[2])


def chunks(skew):
    rng = np.random.default_rng(3)
    for _ in range(nchunks):
        if skew == "uniform":  # high cardinality, ~all-distinct
            k = rng.integers(0, 50 * chunk_rows, chunk_rows)
        elif skew == "zipf":  # heavy hitters + high-cardinality tail
            hot = rng.integers(0, 64, chunk_rows // 2)
            tail = rng.integers(
                64, 20 * chunk_rows, chunk_rows - chunk_rows // 2
            )
            k = np.concatenate([hot, tail])
            rng.shuffle(k)
        else:  # dense: every range collapses on device
            k = rng.integers(0, 4096, chunk_rows)
        yield {
            "k": k.astype(np.int64),
            "v": rng.integers(-1000, 1000, chunk_rows).astype(np.int64),
        }


def run(skew, tree):
    # combine threshold sized so BOTH paths must fold accumulated
    # partials mid-stream — the long-stream regime the tree targets
    # (the flat path's default threshold would defer everything to one
    # final merge and the comparison would measure nothing)
    ctx = DryadContext(
        dcn_slices=2,
        config=DryadConfig(
            combine_tree=tree, stream_combine_rows=chunk_rows
        ),
    )

    def once():
        return (
            ctx.from_stream(chunks(skew))
            .group_by("k", {"c": ("count", None), "s": ("sum", "v")})
            .collect()
        )

    once()  # warm: pays every compile at this shape palette
    mark = len(ctx.executor.events.events())
    t0 = time.perf_counter()
    out = once()
    dt = time.perf_counter() - t0
    ev = ctx.executor.events.events()[mark:]
    comb = [e for e in ev if e["kind"] == "stream_combine"]
    lev = [e for e in ev if e["kind"] == "combine_tree_level"]
    deg = [e for e in ev if e["kind"] == "combine_tree_degrade"]
    return {
        "rows_per_sec": round(nchunks * chunk_rows / dt, 1),
        "seconds": round(dt, 3),
        "out_rows": int(len(out["k"])),
        "combines": len(comb) + len(lev),
        "depth": max((e["level"] for e in lev), default=0),
        "ici_bytes": int(sum(e.get("ici_bytes", 0) for e in comb + lev)),
        "dcn_bytes": int(sum(e.get("dcn_bytes", 0) for e in comb + lev)),
        "degraded_fraction": deg[-1]["fraction"] if deg else 0.0,
    }


res = {}
for skew in ("dense", "zipf", "uniform"):
    on, off = run(skew, True), run(skew, False)
    assert on["out_rows"] == off["out_rows"]
    res[skew] = {"tree": on, "flat": off}
print(json.dumps(res))
"""


def aggtree_metric(n: int, chunk_rows: int = 1 << 14):
    """Topology- and distribution-aware combine tree vs the flat merge
    (exec/combinetree.py) on a hybrid 2-slice DCN x ICI mesh: one
    streaming high-cardinality group_by at three key-skew levels, tree
    on vs off.  Reports rows/s per skew, combine count and tree depth,
    estimated DCN vs ICI combine bytes (the tree's contract: elided
    intermediate merges, exactly one DCN-crossing fold at the root),
    and the host-degraded key-range fraction.  Runs on 8 virtual CPU
    devices in a subprocess (the hybrid mesh needs a device count the
    parent's backend may not have) — byte accounting and merge
    structure are platform-independent; rows/s is host-relative."""
    import subprocess

    nchunks = max(3, n // chunk_rows)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _AGGTREE_CHILD,
         str(nchunks), str(chunk_rows)],
        capture_output=True, text=True, timeout=max(remaining(), 120),
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"aggtree child rc={out.returncode}: {out.stderr[-2000:]}"
        )
    res = json.loads(out.stdout.strip().splitlines()[-1])
    uni = res["uniform"]["tree"]
    rows = nchunks * chunk_rows
    extra = {"skews": res, "chunks": nchunks, "chunk_rows": chunk_rows,
             "dcn_slices": 2, "devices": 8}
    for skew, pair in res.items():
        t, f = pair["tree"], pair["flat"]
        extra[f"{skew}_speedup"] = round(
            t["rows_per_sec"] / max(f["rows_per_sec"], 1e-9), 3
        )
        extra[f"{skew}_dcn_bytes_saved"] = f["dcn_bytes"] - t["dcn_bytes"]
    return rep_record(
        "aggtree_rows_per_sec", rows, [uni["seconds"]], extra
    )


# Child body for rewrite_metric: the runtime plan rewriter only pays
# off against genuinely adversarial inputs — a stream whose key
# distribution drifts AFTER the range splitters were sampled (the hot
# bucket then eats most rows), and an overflow-prone skewed join rerun
# on one context (the static plan re-discovers the overflow every run;
# the rewriter's boost floor pre-widens from run 2).  8 virtual CPU
# devices in a subprocess; both runs assert byte-identity first.
_REWRITE_CHILD = r"""
import json, os, sys, time
import numpy as np

from dryad_tpu.parallel.mesh import force_cpu_backend

force_cpu_backend(8)

import jax

from dryad_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()

from dryad_tpu import DryadConfig, DryadContext
from dryad_tpu.obs.metrics import JobMetrics

nchunks, chunk_rows = int(sys.argv[1]), int(sys.argv[2])


def sort_chunks():
    # chunk 0 is uniform (splitters sample it); the rest collapse onto
    # 1/50th of the key range — the static partition's low bucket goes
    # hot and must be recursively re-spilled at phase 2, while the
    # rewriter splits it mid-stream off the live spill histogram
    rng = np.random.default_rng(7)
    out = [{
        "x": rng.integers(0, 1_000_000, chunk_rows).astype(np.int64),
        "v": rng.random(chunk_rows).astype(np.float32),
    }]
    for _ in range(nchunks - 1):
        out.append({
            "x": rng.integers(0, 20_000, chunk_rows).astype(np.int64),
            "v": rng.random(chunk_rows).astype(np.float32),
        })
    return out


SORT = sort_chunks()


def sort_ctx(rw):
    return DryadContext(config=DryadConfig(
        stream_bucket_rows=2 * chunk_rows, stream_buckets=8,
        plan_rewrite=rw, diagnose_cooldown_s=0.0,
    ))


def sort_once(ctx):
    out = ctx.from_stream(
        iter([{k: v.copy() for k, v in c.items()} for c in SORT])
    ).order_by(["x", "v"]).collect()
    assert len(out["x"]) == nchunks * chunk_rows
    return out


def sort_leg(rw):
    sort_once(sort_ctx(rw))  # warm: pays the shape-palette compiles
    ctx = sort_ctx(rw)  # fresh controller state for the measured run
    t0 = time.perf_counter()
    out = sort_once(ctx)
    dt = time.perf_counter() - t0
    ev = ctx.executor.events.events()
    return out, {
        "seconds": round(dt, 3),
        "rows_per_sec": round(nchunks * chunk_rows / dt, 1),
        "rewrites_applied": sum(
            1 for e in ev
            if e["kind"] == "plan_rewrite" and e["phase"] == "applied"
        ),
        "spill_bytes": JobMetrics.from_events(ev).spill_bytes,
    }


def join_tables():
    rng = np.random.default_rng(11)
    n = nchunks * chunk_rows
    k = rng.integers(0, n, n).astype(np.int32)
    k[rng.random(n) < 0.3] = 7  # hot probe key: one partition overloads
    return (
        {"k": k, "a": rng.integers(0, 1000, n).astype(np.int32)},
        {"k": np.arange(n, dtype=np.int32),
         "b": rng.integers(0, 1000, n).astype(np.int32)},
    )


LTBL, RTBL = join_tables()


def join_leg(rw):
    # ONE context reused: the adaptive run learns the overflow on the
    # first query and pre-widens every later dispatch
    ctx = DryadContext(config=DryadConfig(
        shuffle_slack=1.0, plan_rewrite=rw, diagnose_cooldown_s=0.0,
    ))

    def once():
        return ctx.from_arrays(
            {k: v.copy() for k, v in LTBL.items()}
        ).join(
            ctx.from_arrays({k: v.copy() for k, v in RTBL.items()}),
            ["k"], ["k"],
        ).collect()

    once(); once()  # warm compiles AND let the overflow loop be seen
    mark = len(ctx.executor.events.events())
    t0 = time.perf_counter()
    out = once()
    dt = time.perf_counter() - t0
    ev = ctx.executor.events.events()[mark:]
    return out, {
        "seconds": round(dt, 3),
        "rows_per_sec": round(len(LTBL["k"]) / dt, 1),
        "overflow_retries": sum(
            1 for e in ev if e["kind"] == "stage_overflow"
        ),
        "prewidened": any(
            e["kind"] == "plan_rewrite" and e["phase"] == "applied"
            and e["action"] == "prewiden_palette"
            for e in ctx.executor.events.events()
        ),
    }


def canon(t):
    names = sorted(t)
    order = np.lexsort([np.asarray(t[n]) for n in names])
    return {n: np.asarray(t[n])[order] for n in names}


res = {}
for leg, fn, ordered in (("sort", sort_leg, True),
                         ("join", join_leg, False)):
    out_off, static = fn(False)
    out_on, adaptive = fn(True)
    a = out_on if ordered else canon(out_on)
    b = out_off if ordered else canon(out_off)
    assert set(a) == set(b)
    for c in a:  # the rewrite changed shape, never bytes
        assert a[c].tobytes() == b[c].tobytes(), (leg, c)
    res[leg] = {
        "static": static, "adaptive": adaptive, "byte_identical": True,
        "speedup": round(
            static["seconds"] / max(adaptive["seconds"], 1e-9), 3
        ),
    }
print(json.dumps(res))
"""


def rewrite_metric(n: int, chunk_rows: int = 1 << 14):
    """Runtime plan rewriter (dryad_tpu/rewrite) on adversarial inputs:
    a drift-skewed out-of-core sort (splitters sampled before the
    distribution collapses -> partition_skew -> mid-stream hot-bucket
    split) and an overflow-prone skewed join rerun on one context
    (overflow_loop -> pre-widened boost palette).  Static plan vs
    rewriter per leg, byte-identity asserted in the child; headline is
    the adaptive sort leg, speedups ride extra."""
    import subprocess

    nchunks = max(4, n // chunk_rows)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _REWRITE_CHILD,
         str(nchunks), str(chunk_rows)],
        capture_output=True, text=True, timeout=max(remaining(), 120),
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"rewrite child rc={out.returncode}: {out.stderr[-2000:]}"
        )
    res = json.loads(out.stdout.strip().splitlines()[-1])
    srt, jn = res["sort"], res["join"]
    extra = {
        "legs": res, "devices": 8, "chunks": nchunks,
        "chunk_rows": chunk_rows,
        "sort_speedup": srt["speedup"],
        "join_speedup": jn["speedup"],
        "rewrites_applied": srt["adaptive"]["rewrites_applied"],
        "static_overflow_retries": jn["static"]["overflow_retries"],
        "adaptive_overflow_retries": jn["adaptive"]["overflow_retries"],
    }
    return rep_record(
        "rewrite_rows_per_sec", nchunks * chunk_rows,
        [srt["adaptive"]["seconds"]], extra,
    )


# Child body for serve_metric: closed-loop multi-tenant clients
# multiplexed on ONE resident engine (serve/service.py).  Runs on 8
# virtual CPU devices in a fresh subprocess like the aggtree matrix:
# the parent's backend may have a different device count, and
# admission / fair-share / cache behavior is platform-free anyway.
_SERVE_CHILD = r"""
import json, os, sys, threading, time
import numpy as np

from dryad_tpu.parallel.mesh import force_cpu_backend

force_cpu_backend(8)

import jax

from dryad_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()  # reruns skip the pow2-palette compiles

from dryad_tpu import DryadContext
from dryad_tpu.serve import QueryRejected, QueryService

n, per_client = int(sys.argv[1]), int(sys.argv[2])
cells = [int(c) for c in sys.argv[3].split(",")]
TENANTS = 4

rng = np.random.default_rng(11)
ctx = DryadContext(num_partitions_=8)

plans = []
for t in range(TENANTS):
    words = np.asarray(
        [f"t{t}w{i:04d}" for i in rng.integers(0, 1024, n)], object
    )
    tab = ctx.from_arrays({
        "k": words,
        "v": rng.integers(0, 1000, n).astype(np.int64),
        "w": rng.random(n).astype(np.float32),
    })
    # mixed prepared shapes, all value-hashable params: repeated
    # submissions share compile keys AND result-cache keys
    plans.append([
        tab.group_by("k", {"s": ("sum", "v")}),
        tab.group_by("k", {"c": ("count", None), "m": ("mean", "w")}),
        tab.distinct("k"),
        tab.order_by("v").take(64),
    ])

for ps in plans:  # warm: pay every compile before the timed cells
    for q in ps:
        ctx.run_to_host(q)


def run_cell(clients, cache_on):
    ctx.config.serve_result_cache_bytes = (256 << 20) if cache_on else 0
    svc = QueryService(ctx)
    lat = [[] for _ in range(clients)]
    fin = [0.0] * clients
    errors = []

    def client(i):
        tenant = i % TENANTS
        sess = svc.session(f"tenant{tenant}")
        try:
            for j in range(per_client):
                q = plans[tenant][(i // TENANTS + j) % len(plans[tenant])]
                t0 = time.perf_counter()
                while True:
                    try:
                        sess.run(q, timeout=600)
                        break
                    except QueryRejected:
                        time.sleep(0.002)  # closed loop: back off on quota
                lat[i].append(time.perf_counter() - t0)
            fin[i] = time.perf_counter()
        except BaseException as e:
            errors.append(repr(e))

    t_start = time.perf_counter()
    ths = [
        threading.Thread(target=client, args=(i,)) for i in range(clients)
    ]
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    elapsed = time.perf_counter() - t_start
    stats = svc.stats()
    svc.close()
    if errors:
        raise RuntimeError(errors[0])
    all_lat = sorted(x for ls in lat for x in ls)
    queries = clients * per_client
    tput = []
    per_tenant = {}
    for t in range(TENANTS):
        done = stats["tenants"][f"tenant{t}"]["completed"]
        el = max(
            fin[i] for i in range(clients) if i % TENANTS == t
        ) - t_start
        per_tenant[f"tenant{t}"] = {
            "completed": done, "seconds": round(el, 3),
        }
        tput.append(done / max(el, 1e-9))
    cache = stats["cache"]
    looked = cache["hits"] + cache["misses"]
    return {
        "clients": clients,
        "queries": queries,
        "seconds": round(elapsed, 3),
        "queries_per_sec": round(queries / elapsed, 1),
        "rows_per_sec": round(queries * n / elapsed, 1),
        "p50_ms": round(1e3 * all_lat[len(all_lat) // 2], 3),
        "p99_ms": round(
            1e3 * all_lat[min(len(all_lat) - 1, int(len(all_lat) * 0.99))],
            3,
        ),
        "cache_hit_rate": (
            round(cache["hits"] / looked, 4) if looked else 0.0
        ),
        "fairness_spread": round(max(tput) / max(min(tput), 1e-9), 3),
        "rejected": sum(
            s["rejected"] for s in stats["tenants"].values()
        ),
        "per_tenant": per_tenant,
    }


res = {"n": n, "per_client": per_client, "cells": []}
for clients in cells:
    res["cells"].append({"cache": "off", **run_cell(clients, False)})
    res["cells"].append({"cache": "on", **run_cell(clients, True)})
print(json.dumps(res))
"""


def serve_metric(n: int, per_client: int = 6, cells=(16, 64)):
    """Serving tier (serve/service.py): 4 tenants x {16, 64} concurrent
    closed-loop clients over one resident DryadContext, mixed prepared
    plan shapes.  Each concurrency cell runs twice — result cache OFF
    (every query really dispatches through the shared window: p50/p99
    latency, rows/s, DRR fairness spread) and ON (hit rate and
    cached-serving speedup).  Runs on 8 virtual CPU devices in a
    subprocess; scheduling, admission, and cache behavior are
    platform-free, rows/s is host-relative."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _SERVE_CHILD,
         str(n), str(per_client), ",".join(str(c) for c in cells)],
        capture_output=True, text=True, timeout=max(remaining(), 120),
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"serve child rc={out.returncode}: {out.stderr[-2000:]}"
        )
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # headline: the widest cache-off cell (every query dispatches)
    wide = [c for c in res["cells"] if c["cache"] == "off"][-1]
    cached = [c for c in res["cells"] if c["cache"] == "on"][-1]
    extra = {
        "cells": res["cells"], "tenants": 4, "devices": 8,
        "clients": wide["clients"], "queries": wide["queries"],
        "p50_ms": wide["p50_ms"], "p99_ms": wide["p99_ms"],
        "queries_per_sec": wide["queries_per_sec"],
        "fairness_spread": wide["fairness_spread"],
        "cache_hit_rate": cached["cache_hit_rate"],
        "cached_p50_ms": cached["p50_ms"],
        "cached_speedup": round(
            cached["queries_per_sec"]
            / max(wide["queries_per_sec"], 1e-9), 3
        ),
    }
    return rep_record(
        "serve_rows_per_sec", wide["queries"] * res["n"],
        [wide["seconds"]], extra,
    )


# Child body for matview_metric: continuous ingest + incremental
# materialized views (views/matview.py) vs recompute-per-query vs the
# pre-views epoch-nuke.  One resident engine, a "hot" tenant whose
# table takes appends while its plans are read closed-loop, and an
# "other" tenant whose unrelated plan SHOULD stay cached across the
# hot table's appends (the per-binding invalidation claim).  Runs on 8
# virtual CPU devices in a fresh subprocess like the serve child.
_MATVIEW_CHILD = r"""
import json, os, sys, threading, time
import numpy as np

from dryad_tpu.parallel.mesh import force_cpu_backend

force_cpu_backend(8)

import jax

from dryad_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()  # reruns skip the pow2-palette compiles

from dryad_tpu import DryadContext
from dryad_tpu.serve import QueryService

n = int(sys.argv[1])
readers, per_reader, appends = (int(a) for a in sys.argv[2].split(","))
CHUNK = 2048


def mk(rows, rng):
    return {
        "k": np.asarray(
            [f"h{i:03d}" for i in rng.integers(0, 512, rows)], object
        ),
        "v": rng.integers(0, 1_000_000, rows).astype(np.int64),
        # integer-valued float32: the view's host fold and the device
        # recompute agree to the byte (exact arithmetic)
        "w": rng.integers(0, 64, rows).astype(np.float32),
    }


def run_cell(mode):
    ctx = DryadContext(num_partitions_=8)
    ctx.config.serve_result_cache_bytes = 256 << 20
    svc = QueryService(ctx)
    hot = svc.session("hot")
    hot_t = hot.ingest(mk(n, np.random.default_rng(1)))
    hot_plans = [
        hot_t.group_by("k", {"s": ("sum", "v")}),
        hot_t.group_by("k", {"c": ("count", None), "m": ("mean", "w")}),
    ]
    other = svc.session("other")
    other_q = other.ingest(mk(n, np.random.default_rng(2))).group_by(
        "k", {"s": ("sum", "v")}
    )
    if mode == "views":
        for q in hot_plans:
            hot.register_view(q, max_staleness_s=0.05)
    for q in hot_plans:  # warm: compiles + first snapshot / cache fill
        hot.run(q)
    other.run(other_q)
    errors = []

    def writer():
        wrng = np.random.default_rng(3)
        try:
            for _ in range(appends):
                hot.append(hot_t, mk(CHUNK, wrng))
                if mode == "epoch":
                    # the pre-views write path: stop the world
                    hot.bump_epoch()
                    other.bump_epoch()
                time.sleep(0.02)
        except BaseException as e:
            errors.append(repr(e))

    def reader(i, sess, q, counts):
        try:
            for _ in range(per_reader):
                sess.run(q, timeout=600)
                counts[i] += 1
        except BaseException as e:
            errors.append(repr(e))

    hot_counts = [0] * readers
    oth_counts = [0] * (readers // 2)
    ths = [threading.Thread(target=writer)]
    ths += [
        threading.Thread(
            target=reader,
            args=(i, hot, hot_plans[i % len(hot_plans)], hot_counts),
        )
        for i in range(readers)
    ]
    ths += [
        threading.Thread(target=reader, args=(i, other, other_q, oth_counts))
        for i in range(readers // 2)
    ]
    t_start = time.perf_counter()
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    elapsed = time.perf_counter() - t_start
    stats = svc.stats()
    stal = sorted(
        e["staleness_s"]
        for e in svc.events.events()
        if e["kind"] == "view_snapshot"
    )
    svc.close()
    if errors:
        raise RuntimeError(errors[0])
    hot_reads = sum(hot_counts)
    oth = stats["tenants"]["other"]
    return {
        "mode": mode,
        "seconds": round(elapsed, 3),
        "hot_reads": hot_reads,
        "reads_per_sec": round(hot_reads / elapsed, 1),
        "rows_per_sec": round(hot_reads * n / elapsed, 1),
        "dispatches": stats["dispatches"],
        "unrelated_hit_rate": round(
            oth["cache_hits"] / max(oth["completed"], 1), 4
        ),
        "staleness_p95_ms": (
            round(1e3 * stal[min(len(stal) - 1, int(len(stal) * 0.95))], 3)
            if stal else 0.0
        ),
        "delta_fold_bytes": stats["views"]["delta_bytes"],
        "snapshots_fresh": stats["views"]["snapshots_fresh"],
        "snapshots_finalized": stats["views"]["snapshots_finalized"],
    }


res = {"n": n, "cells": [run_cell(m) for m in ("views", "recompute", "epoch")]}
print(json.dumps(res))
"""


def matview_metric(n: int, readers: int = 8, per_reader: int = 12,
                   appends: int = 6):
    """Materialized views under continuous ingest (views/matview.py):
    8 closed-loop readers on two hot plans + 4 readers on an unrelated
    cached plan while a writer appends 2048-row chunks.  Three cells —
    views on (bounded-staleness snapshots), recompute-per-query (every
    post-append read re-aggregates the grown table), and the pre-views
    epoch-nuke (appends evict EVERY tenant's cache).  Headline is the
    views cell's read throughput; the extra block carries the speedup
    over recompute and the unrelated tenant's hit rate per mode (the
    per-binding invalidation claim: ~1.0 except under epoch-nuke)."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _MATVIEW_CHILD,
         str(n), f"{readers},{per_reader},{appends}"],
        capture_output=True, text=True, timeout=max(remaining(), 120),
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"matview child rc={out.returncode}: {out.stderr[-2000:]}"
        )
    res = json.loads(out.stdout.strip().splitlines()[-1])
    cells = {c["mode"]: c for c in res["cells"]}
    views, rec = cells["views"], cells["recompute"]
    extra = {
        "cells": res["cells"], "devices": 8,
        "readers": readers, "appends": appends, "chunk_rows": 2048,
        "reads_per_sec": views["reads_per_sec"],
        "views_speedup": round(
            views["reads_per_sec"] / max(rec["reads_per_sec"], 1e-9), 3
        ),
        "staleness_p95_ms": views["staleness_p95_ms"],
        "delta_fold_bytes": views["delta_fold_bytes"],
        "unrelated_hit_rate": {
            m: cells[m]["unrelated_hit_rate"] for m in cells
        },
    }
    return rep_record(
        "matview_rows_per_sec", views["hot_reads"] * res["n"],
        [views["seconds"]], extra,
    )


# Closed-loop fleet client: a SEPARATE OS process that speaks the raw
# mailbox HTTP wire with nothing but the stdlib — no jax, no numpy, no
# dryad import (the import alone would cost more than the queries it
# sends, and 64 of them importing jax on one host would bench the
# loader, not the fleet).  Results are checked via the frame HEADER
# only: the header pickles separately from the table precisely so a
# routing-tier consumer never deserializes payload arrays.
_FLEET_CLIENT = r"""
import http.client, json, os, pickle, struct, sys, time

host, port = sys.argv[1], int(sys.argv[2])
payload_path, tenant, tier = sys.argv[3], sys.argv[4], sys.argv[5]
per_client, idx = int(sys.argv[6]), int(sys.argv[7])

with open(payload_path, "rb") as fh:
    items = pickle.load(fh)[tenant]  # [(package_bytes, fingerprint)]

conn = http.client.HTTPConnection(host, port, timeout=180)
nonce = os.urandom(6).hex()


def post(name, body):
    conn.request("POST", "/prop/fleet/" + name, body=body)
    r = conn.getresponse()
    r.read()
    assert r.status == 200, r.status


def poll(name, timeout):
    conn.request(
        "GET", "/prop/fleet/%s?after=0&timeout=%s" % (name, timeout)
    )
    r = conn.getresponse()
    body = r.read()
    return body if r.status == 200 else None


lat, rejected, cached = [], 0, 0
t_start = time.perf_counter()
for j in range(per_client):
    blob, fp = items[(idx + j) % len(items)]
    qid = "%s-%s-%d" % (tenant, nonce, j)
    env = {"qid": qid, "tenant": tenant, "tier": tier, "weight": 1,
           "package": blob, "fingerprint": fp,
           "trace": {"qid": qid, "tenant": tenant}}
    t0 = time.perf_counter()
    post("rq/" + qid, pickle.dumps(env, protocol=pickle.HIGHEST_PROTOCOL))
    body = poll("res/" + qid, 120)
    dt = time.perf_counter() - t0
    assert body is not None and body[:2] == b"F1", "no result for " + qid
    hlen = struct.unpack("<II", body[2:10])[0]
    header = pickle.loads(body[10:10 + hlen])
    if header.get("rejected") is not None:
        rejected += 1
        time.sleep(0.002)  # closed loop: back off on quota
        continue
    assert header.get("ok"), header.get("error")
    cached += 1 if header.get("cached") else 0
    lat.append(dt)
print(json.dumps({
    "tenant": tenant, "tier": tier, "lat": lat, "rejected": rejected,
    "cached": cached, "elapsed": time.perf_counter() - t_start,
}))
"""


# Orchestrator for serve_fleet_metric: builds the fleet (front door +
# N engine-replica PROCESSES), packs the plan set, warms each plan
# onto its rendezvous owner, then fans out the stdlib client
# processes.  Runs as a subprocess of the bench for the same backend
# isolation as the other serve children.  argv: n replicas clients
# per_client; extra argv[5] is the client script path written by the
# parent.
_FLEET_ORCH = r"""
import json, os, pickle, subprocess, sys, tempfile
import threading, time
import numpy as np

from dryad_tpu.parallel.mesh import force_cpu_backend

force_cpu_backend(8)

import jax

from dryad_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()  # reruns skip the pow2-palette compiles

from dryad_tpu import DryadContext
from dryad_tpu.obs.telemetry import quantiles_from_hist
from dryad_tpu.serve import QueryService
from dryad_tpu.serve.fleet import ServeFleet, pack_for_fleet
from dryad_tpu.tools.metricsd import merge_snapshots

n, n_replicas = int(sys.argv[1]), int(sys.argv[2])
n_clients, per_client = int(sys.argv[3]), int(sys.argv[4])
client_script = sys.argv[5]
TENANTS = 4  # tenants 0,1 -> latency tier; 2,3 -> batch tier

_T0 = time.perf_counter()


def note(msg):
    print(f"[fleet t+{time.perf_counter() - _T0:.0f}s] {msg}",
          file=sys.stderr, flush=True)


def tier_of(t):
    return "latency" if t < TENANTS // 2 else "batch"


rng = np.random.default_rng(11)
ctx = DryadContext(num_partitions_=8)

plans, packs = {}, {}
for t in range(TENANTS):
    words = np.asarray(
        [f"t{t}w{i:04d}" for i in rng.integers(0, 1024, n)], object
    )
    tab = ctx.from_arrays({
        "k": words,
        "v": rng.integers(0, 1000, n).astype(np.int64),
        "w": rng.random(n).astype(np.float32),
    })
    plans[t] = [
        tab.group_by("k", aggs={"s": ("sum", "v")}),
        tab.group_by("k", aggs={"c": ("count", None),
                                "m": ("mean", "w")}),
        tab.distinct("k"),
        tab.order_by("v").take(64),
    ]
    packs[f"tenant{t}"] = [pack_for_fleet(q) for q in plans[t]]
note(f"packed {sum(len(v) for v in packs.values())} plans")

td = tempfile.mkdtemp(prefix="dryad-fleet-bench-")
bootstrap = os.path.join(td, "bootstrap.py")
with open(bootstrap, "w") as fh:
    fh.write(
        "from dryad_tpu.parallel.mesh import force_cpu_backend\n"
        "force_cpu_backend(8)\n"
        "from dryad_tpu.utils.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "from dryad_tpu import DryadContext\n"
        "def build_context():\n"
        "    return DryadContext(num_partitions_=8)\n"
    )
payload = os.path.join(td, "payload.pkl")
with open(payload, "wb") as fh:
    pickle.dump(packs, fh, protocol=pickle.HIGHEST_PROTOCOL)

fleet = ServeFleet(hb_interval=0.5, stale_after=600.0)
# a crashed orchestrator must still reap its replica processes — they
# inherit our captured stdout/stderr pipes, and a survivor polling a
# dead port keeps the parent's communicate() from ever seeing EOF
import atexit
atexit.register(fleet.close)
spawn_errs = []


def _spawn(rid):
    try:
        fleet.spawn_process(rid, bootstrap, timeout=600.0)
    except BaseException as e:
        spawn_errs.append(repr(e))


ths = [
    threading.Thread(target=_spawn, args=(f"r{i}",))
    for i in range(n_replicas)
]
t_boot = time.perf_counter()
for th in ths:
    th.start()
for th in ths:
    th.join()
if spawn_errs:
    raise RuntimeError(spawn_errs[0])
boot_s = time.perf_counter() - t_boot
note(f"{n_replicas} replica processes up in {boot_s:.0f}s")

# warm every plan onto its rendezvous owner: prepared-statement load,
# compile, and the first (cache-filling) execution
t_warm = time.perf_counter()
for t in range(TENANTS):
    tenant = f"tenant{t}"
    for blob, fp in packs[tenant]:
        qid = fleet.submit(tenant=tenant, package=blob, fingerprint=fp,
                           tier=tier_of(t))
        fleet.result(qid, timeout=600)
    note(f"warmed {tenant}")
warm_s = time.perf_counter() - t_warm

# timed fleet cell: closed-loop stdlib client PROCESSES
procs = []
t_run = time.perf_counter()
for i in range(n_clients):
    t = i % TENANTS
    procs.append(subprocess.Popen(
        [sys.executable, client_script, fleet.host, str(fleet.port),
         payload, f"tenant{t}", tier_of(t), str(per_client),
         str(i // TENANTS)],
        stdout=subprocess.PIPE, text=True,
    ))
reports = []
for p in procs:
    out, _ = p.communicate(timeout=900)
    assert p.returncode == 0, f"client rc={p.returncode}"
    reports.append(json.loads(out.strip().splitlines()[-1]))
elapsed = time.perf_counter() - t_run
note(f"{n_clients} clients done in {elapsed:.1f}s")


def pct(xs, q):
    if not xs:
        return None
    xs = sorted(xs)
    return round(1e3 * xs[min(len(xs) - 1, int(len(xs) * q))], 3)


by_tier = {"latency": [], "batch": []}
for r in reports:
    by_tier[r["tier"]].extend(r["lat"])
completed = sum(len(r["lat"]) for r in reports)
cached = sum(r["cached"] for r in reports)
rejected = sum(r["rejected"] for r in reports)

time.sleep(2 * 0.5)  # let each replica post one more stats beat
stats = fleet.stats()
per_replica_hits = {}
for rid, s in stats["replicas"].items():
    if not s:
        continue
    c = s.get("cache", {})
    looked = c.get("hits", 0) + c.get("misses", 0)
    per_replica_hits[rid] = (
        round(c.get("hits", 0) / looked, 4) if looked else None
    )
rates = [v for v in per_replica_hits.values() if v is not None]
# fleet-wide latency fold: merge the per-tenant pow2 histograms the
# replicas posted, then re-derive quantiles (the only commutative fold)
merged = merge_snapshots(fleet.replica_snapshots())
hist = {}
for rec in merged.get("latencies", []):
    if rec["name"] != "query_latency_s":
        continue
    for e, cnt in (rec.get("buckets") or {}).items():
        hist[int(e)] = hist.get(int(e), 0) + int(cnt)
fleet_lat = quantiles_from_hist(hist) or {}
router = stats["router"]
fleet.close()

# single-process ceiling: the SAME plans closed-loop on one in-process
# QueryService (no wire, no pickle, no fan-out) — the front door this
# fleet exists to out-scale
svc = QueryService(ctx)
single_done = [0]
lock = threading.Lock()


def single_client(i):
    t = i % TENANTS
    sess = svc.session(f"s{i}", tier=tier_of(t))
    for j in range(per_client):
        sess.run(plans[t][(i + j) % len(plans[t])], timeout=600)
        with lock:
            single_done[0] += 1


sths = [threading.Thread(target=single_client, args=(i,))
        for i in range(min(n_clients, 16))]
t_single = time.perf_counter()
for th in sths:
    th.start()
for th in sths:
    th.join()
single_s = time.perf_counter() - t_single
single_qps = round(single_done[0] / single_s, 1)
svc.close()
note(f"single-process ceiling cell done in {single_s:.1f}s")

print(json.dumps({
    "n": n, "replicas": n_replicas, "clients": n_clients,
    "queries": completed, "seconds": round(elapsed, 3),
    "queries_per_sec": round(completed / elapsed, 1),
    "boot_s": round(boot_s, 2), "warm_s": round(warm_s, 2),
    "rejected": rejected,
    "client_cache_hit_rate": round(cached / max(completed, 1), 4),
    "latency_p50_ms": pct(by_tier["latency"], 0.50),
    "latency_p95_ms": pct(by_tier["latency"], 0.95),
    "latency_p99_ms": pct(by_tier["latency"], 0.99),
    "batch_p50_ms": pct(by_tier["batch"], 0.50),
    "batch_p95_ms": pct(by_tier["batch"], 0.95),
    "batch_p99_ms": pct(by_tier["batch"], 0.99),
    "per_replica_cache_hit": per_replica_hits,
    "cache_hit_spread_points": (
        round(100 * (max(rates) - min(rates)), 2) if rates else None
    ),
    "fleet_fold_p95_ms": (
        round(1e3 * fleet_lat["p95"], 3) if "p95" in fleet_lat else None
    ),
    "routed": router["routed"], "delivered": router["delivered"],
    "fast_rejects": router["fast_rejects"],
    "replayed": router["replayed"], "failed": router["failed"],
    "single_process_queries_per_sec": single_qps,
    "fleet_vs_single": round(
        (completed / elapsed) / max(single_qps, 1e-9), 3
    ),
}))
"""


def serve_fleet_metric(
    n: int = 1 << 13, replicas: int = 4, clients: int = 64,
    per_client: int = 6,
):
    """Fleet serving plane (serve/fleet.py): a multi-process front
    door, ``replicas`` engine-replica PROCESSES (each its own
    DryadContext on 8 virtual CPU devices), and ``clients`` closed-loop
    client PROCESSES that speak the raw envelope wire with only the
    stdlib.  Tenants split across priority tiers (latency/batch);
    repeat plans route fingerprint-affine, so the steady state serves
    from each owner replica's result cache.  Reports fleet q/s,
    per-tier p50/p95/p99, per-replica cache-hit spread, and the
    single-process in-process ceiling for comparison."""
    import subprocess
    import tempfile

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with tempfile.NamedTemporaryFile(
        "w", suffix="_fleet_client.py", delete=False
    ) as fh:
        fh.write(_FLEET_CLIENT)
        client_script = fh.name
    try:
        out = subprocess.run(
            [sys.executable, "-c", _FLEET_ORCH,
             str(n), str(replicas), str(clients), str(per_client),
             client_script],
            capture_output=True, text=True,
            timeout=max(remaining(), 180),
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    finally:
        os.unlink(client_script)
    if out.returncode != 0:
        raise RuntimeError(
            f"fleet child rc={out.returncode}: {out.stderr[-2000:]}"
        )
    res = json.loads(out.stdout.strip().splitlines()[-1])
    extra = {
        k: v for k, v in res.items()
        if k not in ("queries", "seconds", "n")
    }
    return rep_record(
        "serve_fleet_rows_per_sec", res["queries"] * res["n"],
        [res["seconds"]], extra,
    )


# Child body for ooc_exchange_metric: the staged exchange only does
# anything on a multi-device mesh (P=1 short-circuits to the flat
# path), so the window sweep runs on 8 virtual CPU devices in a fresh
# subprocess — same reasoning as the aggtree child.
_OOCXCHG_CHILD = r"""
import json, os, sys, time
import numpy as np

from dryad_tpu.parallel.mesh import force_cpu_backend

force_cpu_backend(8)

import jax

from dryad_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()  # reruns skip the pow2-palette compiles

from dryad_tpu import DryadConfig, DryadContext
from dryad_tpu.obs.metrics import JobMetrics

nchunks, chunk_rows = int(sys.argv[1]), int(sys.argv[2])


def chunks():
    rng = np.random.default_rng(7)
    for _ in range(nchunks):
        yield {
            "key": rng.integers(
                -(2 ** 31), 2 ** 31 - 1, chunk_rows
            ).astype(np.int32),
            "v": rng.integers(-1000, 1000, chunk_rows).astype(np.int64),
        }


def run(bucket_rows, window):
    ctx = DryadContext(config=DryadConfig(
        stream_bucket_rows=bucket_rows, stream_buckets=8,
        exchange_window=window,
    ))

    def once():
        return ctx.from_stream(chunks()).order_by(["key"]).collect()

    once()  # warm: pays every compile at this shape palette
    mark = len(ctx.executor.events.events())
    t0 = time.perf_counter()
    out = once()
    dt = time.perf_counter() - t0
    assert len(out["key"]) == nchunks * chunk_rows
    assert (np.diff(out["key"]) >= 0).all()
    ev = ctx.executor.events.events()[mark:]
    m = JobMetrics.from_events(ev)
    return {
        "rows_per_sec": round(nchunks * chunk_rows / dt, 1),
        "seconds": round(dt, 3),
        "window": window,
        "bucket_rows": bucket_rows,
        "dispatches": sum(1 for e in ev if e["kind"] == "stage_start"),
        "exchange_rounds": m.exchange_rounds,
        "peak_exchange_bytes": m.peak_exchange_bytes,
        "spill_bytes": m.spill_bytes,
    }


res = {}
for bucket_rows in (chunk_rows, 4 * chunk_rows):
    res[str(bucket_rows)] = {
        str(w): run(bucket_rows, w) for w in (0, 2, 4)
    }
print(json.dumps(res))
"""


def ooc_exchange_metric(n: int, chunk_rows: int = 1 << 15):
    """Memory-bounded exchange planner on the out-of-core range sort
    (plan/xchgplan.py): window in {0, 2, 4} x two stream-bucket sizes
    on an 8-device virtual mesh.  window=0 is the flat all_to_all
    (peak send buffer P*B*row_bytes per device); a positive window
    stages the exchange into ppermute rounds bounded at
    window*B*row_bytes, and the streaming driver spends the reclaimed
    HBM on larger buckets (exec/outofcore chunk sizing) — fewer device
    dispatches and spill pieces at equal-or-better rows/s.  Reports
    rows/s, dispatch count, exchange_round count, peak per-device
    exchange bytes, and spill bytes per (bucket_rows, window) cell."""
    import subprocess

    nchunks = max(3, n // chunk_rows)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _OOCXCHG_CHILD,
         str(nchunks), str(chunk_rows)],
        capture_output=True, text=True, timeout=max(remaining(), 120),
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"oocxchg child rc={out.returncode}: {out.stderr[-2000:]}"
        )
    res = json.loads(out.stdout.strip().splitlines()[-1])
    rows = nchunks * chunk_rows
    small = res[str(chunk_rows)]
    extra = {"cells": res, "chunks": nchunks, "chunk_rows": chunk_rows,
             "devices": 8}
    flat, staged = small["0"], small["2"]
    # same config: staged spends the reclaimed HBM on 4x buckets, so it
    # dispatches and spills less while the peak exchange buffer shrinks
    extra["dispatch_reduction"] = round(
        flat["dispatches"] / max(staged["dispatches"], 1), 2
    )
    extra["spill_reduction"] = round(
        flat["spill_bytes"] / max(staged["spill_bytes"], 1), 2
    )
    # same EFFECTIVE bucket rows (flat at 4x buckets vs staged whose
    # chunk sizing auto-raises 1x to 4x): the pure peak-HBM bound,
    # P/window at matched capacity
    flat_big = res[str(4 * chunk_rows)]["0"]
    extra["peak_exchange_reduction"] = round(
        flat_big["peak_exchange_bytes"]
        / max(staged["peak_exchange_bytes"], 1), 2
    )
    return rep_record(
        "oocxchg_rows_per_sec", rows, [staged["seconds"]], extra
    )


def ooc_wordcount_metric(
    n_words: int, vocab: int = 1 << 14, chunk_bytes: int = 1 << 22
):
    """Out-of-core WordCount: a corpus file streamed in byte chunks
    through the native tokenizer, per-chunk partial group_by, and the
    DEVICE-RESIDENT combine of the chunk pipeline (partials accumulate
    in HBM; one N-ary merge per combine threshold; one D2H total)."""
    import tempfile

    from dryad_tpu import DryadConfig, DryadContext

    rng = np.random.default_rng(4)
    words = np.array([f"w{i:05d}" for i in range(vocab)])
    parts = []
    left = n_words
    while left > 0:
        take = min(left, 1 << 20)
        parts.append(" ".join(rng.choice(words, take).tolist()))
        left -= take
    corpus = " ".join(parts)
    with tempfile.NamedTemporaryFile(
        "w", suffix=".txt", delete=False
    ) as fh:
        fh.write(corpus)
        path = fh.name
    nbytes = len(corpus)
    del corpus, parts
    cfg = DryadConfig()
    ctx = DryadContext(config=cfg)

    def run():
        out = (
            ctx.text_stream(path, chunk_bytes=chunk_bytes)
            .group_by("word", {"c": ("count", None)})
            .collect()
        )
        assert int(np.asarray(out["c"]).sum()) == n_words

    try:
        t0 = time.perf_counter()
        run()
        t = time.perf_counter() - t0
    finally:
        os.unlink(path)
    return rep_record(
        "oocwordcount_rows_per_sec", n_words, [t],
        {"corpus_bytes": nbytes, "vocab": vocab,
         "chunk_bytes": chunk_bytes,
         "pipeline_depth": cfg.stream_pipeline_depth,
         "phases": _job_phases(ctx)},
    )


def ooc_vocab_metric(
    n_words: int, chunk_rows: int = 1 << 15, vocab_step: int = 1 << 9,
    runtime_tables=None,
):
    """Out-of-core WordCount over a WIDENING vocabulary: every chunk
    introduces new words, so the dense-string coding tables grow the
    whole stream.  With ``stringcode_runtime_tables`` (default on) the
    tables ride the compiled program as runtime operands on a pow2
    shape palette — compiles are bounded by palette tiers
    (O(log vocab)) and per-chunk table H2D traffic shrinks to the
    widened delta; off re-bakes the tables per widen (O(chunks)
    compiles — the ROADMAP vocab-recompile open item's failure mode).
    The record carries ``dense_compiles`` and the phases' compile_s /
    compile_count so the compile-amortization win tracks in the perf
    trajectory."""
    from dryad_tpu import DryadConfig, DryadContext

    rng = np.random.default_rng(7)
    nchunks = max(2, n_words // chunk_rows)
    final_vocab = nchunks * vocab_step
    words = np.array([f"w{j:06d}" for j in range(final_vocab)])

    def chunks():
        for i in range(nchunks):
            hi = (i + 1) * vocab_step
            yield {"word": rng.choice(words[:hi], chunk_rows)}

    kw = {} if runtime_tables is None else {
        "stringcode_runtime_tables": runtime_tables
    }
    cfg = DryadConfig(**kw)
    ctx = DryadContext(config=cfg)
    t0 = time.perf_counter()
    out = (
        ctx.from_stream(chunks())
        .group_by("word", {"c": ("count", None)})
        .collect()
    )
    t = time.perf_counter() - t0
    assert int(np.asarray(out["c"]).sum()) == nchunks * chunk_rows
    dense_compiles = sum(
        1 for e in ctx.executor.events.events()
        if e["kind"] == "xla_compile" and "group_by" in e.get("stage", "")
    )
    pool = ctx.executor.operand_pool
    return rep_record(
        "oocvocab_rows_per_sec", nchunks * chunk_rows, [t],
        {"chunks": nchunks, "chunk_rows": chunk_rows,
         "final_vocab": final_vocab,
         "runtime_tables": cfg.stringcode_runtime_tables,
         "dense_compiles": dense_compiles,
         "operand_uploads": pool.full_uploads,
         "operand_delta_scatters": pool.delta_scatters,
         "phases": _job_phases(ctx)},
    )


def fusedpipe_metric(n: int):
    """Whole-DAG SPMD fusion (plan/fuse.py): a 4+ stage plan — select
    -> hash group_by -> join -> join -> range-sort tail — run with
    ``plan_fuse`` on vs off.  Reports rows/s plus the TPU-relevant
    control-plane numbers: program DISPATCHES per plan (stage_start
    events) and XLA compile count (one key per region vs one per
    stage).
    ``tail_fanout_rows=0`` disables the observed-volume width adapter
    on both sides so the comparison isolates fusion itself."""
    from dryad_tpu import DryadContext
    from dryad_tpu.utils.config import DryadConfig

    rng = np.random.default_rng(7)
    tbl = {
        # wide key domain: keeps the int auto-dense rewrite off so the
        # group_by pays its hash exchange (a real seam collective)
        "k": rng.integers(0, 1 << 20, n).astype(np.int32),
        "v": rng.standard_normal(n).astype(np.float32),
    }
    dk = np.unique(tbl["k"][: 1 << 12])
    dim1 = {"k": dk, "w": np.arange(len(dk), dtype=np.int32)}
    dim2 = {"k": dk[::2].copy(),
            "u": np.arange(len(dk[::2]), dtype=np.int32)}

    def build(ctx):
        a = (
            ctx.from_arrays(tbl)
            .select(lambda c: {"k": c["k"], "v": c["v"] * 2.0})
            .group_by("k", {"s": ("sum", "v"), "c": ("count", None)})
        )
        j1 = a.join(ctx.from_arrays(dim1), "k")
        j2 = j1.join(ctx.from_arrays(dim2), "k")
        return j2.order_by([("s", True), ("k", False)])

    def run_mode(plan_fuse):
        ctx = DryadContext(
            config=DryadConfig(plan_fuse=plan_fuse, tail_fanout_rows=0)
        )
        q = build(ctx)
        out = q.collect()  # warmup: pays every compile
        rows = len(out["k"])
        ev = ctx.events.events()
        compiles = sum(1 for e in ev if e["kind"] == "xla_compile")
        mark = len(ev)
        best, times = timed_reps(lambda: q.collect(), reps=3)
        steady = ctx.events.events()[mark:]
        reps = 3
        dispatches = sum(
            1 for e in steady if e["kind"] == "stage_start"
        ) / reps
        regions = sum(
            1 for e in steady if e["kind"] == "fused_dispatch"
        ) / reps
        return dict(
            rows=rows, times=times, compiles=compiles,
            dispatches=dispatches, fused_regions=regions,
        )

    fused = run_mode(True)
    staged = run_mode(False)
    rec = rep_record(
        "fusedpipe_rows_per_sec", n, fused["times"],
        {
            "dispatches_fused": fused["dispatches"],
            "dispatches_staged": staged["dispatches"],
            "fused_regions": fused["fused_regions"],
            "compiles_fused": fused["compiles"],
            "compiles_staged": staged["compiles"],
            "staged_rows_per_sec": round(n / min(staged["times"]), 1),
            "speedup_vs_staged": round(
                min(staged["times"]) / min(fused["times"]), 3
            ),
            "out_rows": fused["rows"],
        },
    )
    return rec


def codedagg_metric(nrows: int = 60_000, nparts: int = 2, delay: float = 6.0):
    """Coded k-of-n vs duplicate-on-straggle under an injected straggler
    (dryad_tpu.redundancy): one worker stalls its vertex ``delay``
    seconds; the duplicate baseline must IDENTIFY the straggler with a
    robust outlier model (>= 3 completed samples — with k=2 shards it
    can never converge, so the stall runs to completion), while the
    coded path needs only the coarse any-k-of-n spare trigger
    (exec.stats.spare_threshold) and reconstructs the stage output from
    the fast worker's systematic + parity completions, bit-exactly for
    the integer accumulators.  Value = duplicate/coded makespan ratio."""
    from dryad_tpu import DryadContext
    from dryad_tpu.cluster.localjob import LocalJobSubmission

    rng = np.random.default_rng(11)
    tbl = {
        "k": rng.integers(0, 64, nrows).astype(np.int32),
        "v": rng.integers(-1000, 1000, nrows).astype(np.int32),
    }
    with LocalJobSubmission(num_workers=2, devices_per_worker=1) as sub:
        ctx = DryadContext(num_partitions_=1)
        q = ctx.from_arrays(tbl).group_by(
            "k", {"c": ("count", None), "s": ("sum", "v")}
        )
        # warm package/compile caches on both paths and both workers
        base = sub.submit_partitioned(q, nparts=nparts, coded=False)
        coded_out = sub.submit_partitioned(q, nparts=nparts, coded=True)
        assert sorted(
            zip(base["k"].tolist(), base["c"].tolist(), base["s"].tolist())
        ) == sorted(
            zip(coded_out["k"].tolist(), coded_out["c"].tolist(),
                coded_out["s"].tolist())
        )

        sub.inject_delay(worker=1, seconds=delay, count=1)
        t0 = time.perf_counter()
        sub.submit_partitioned(q, nparts=nparts, coded=False)
        t_dup = time.perf_counter() - t0

        sub.inject_delay(worker=1, seconds=delay, count=1)
        t0 = time.perf_counter()
        out = sub.submit_partitioned(q, nparts=nparts, coded=True)
        t_coded = time.perf_counter() - t0
        assert out["c"].tobytes() == coded_out["c"].tobytes()
        assert out["s"].tobytes() == coded_out["s"].tobytes()

        evs = sub.events.events()
        rec = [e for e in evs if e["kind"] == "coded_reconstruct"][-1]
        waste = sum(
            e.get("bytes", 0) for e in evs
            if e["kind"] == "coded_waste_bytes"
        )
    ratio = t_dup / max(t_coded, 1e-9)
    return {
        "metric": "codedagg_makespan_speedup",
        "value": round(ratio, 3),
        "unit": "x",
        "baseline": "duplicate-on-straggle (speculative duplication)",
        "duplicate_s": round(t_dup, 3),
        "coded_s": round(t_coded, 3),
        "injected_delay_s": delay,
        "rows": nrows,
        "nparts": nparts,
        "parity_used": rec.get("parity_used", 0),
        "exact_reconstruct": bool(rec.get("exact")),
        "coded_waste_bytes": waste,
        "platform": _PLATFORM,
        "contended": False,
        "spread": 1.0,
        "reps_s": [round(t_coded, 3)],
    }


# -- main ------------------------------------------------------------------

# Cells whose work runs in child processes pinned to CPU devices
# (JAX_PLATFORMS=cpu / force_cpu_backend) on ANY parent backend: their
# records are stamped with where they ran, not with the parent's chip.
CPU_PINNED = frozenset({
    "codedagg_makespan_speedup", "gangtree_rows_per_sec",
    "aggtree_rows_per_sec", "oocxchg_rows_per_sec",
    "rewrite_rows_per_sec", "serve_rows_per_sec",
    "matview_rows_per_sec", "serve_fleet_rows_per_sec",
})


def run_metrics(only) -> int:
    """Run the metric plan in THIS process on the platform jax gives
    it; returns the number of metrics that errored.  Every record
    carries ``platform``, ``device_kind`` and ``device_count``."""
    import traceback

    import jax

    from dryad_tpu.utils.compile_cache import enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    stamp = {
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
    cpu_stamp = {
        "platform": "cpu", "device_kind": "cpu (pinned child processes)",
        "device_count": None,
    }
    global _PLATFORM
    _PLATFORM = platform
    SUMMARY.update(stamp)
    cache_dir, cache_from = enable_compile_cache()
    log(f"platform={platform} device_kind={stamp['device_kind']} "
        f"devices={len(devices)}; compile cache at {cache_dir} "
        f"({cache_from})")

    baseline = host_baseline_rows_per_sec()
    log(f"host baseline: {baseline:.3e} rows/s")

    accel = platform != "cpu"
    # (name, builder, est cost seconds, updates_summary) — ordered so
    # the highest-value metrics land before the budget runs out.
    plan = [
        ("group_reduce_rows_per_sec",
         lambda: group_reduce_metric(1 << 22 if accel else 1 << 19),
         60 if accel else 15, True),
        ("groupby_e2e_rows_per_sec",
         lambda: groupby_e2e_metric(1 << 22 if accel else 1 << 20),
         60 if accel else 20, False),
        ("wordcount_dense_rows_per_sec",
         lambda: wordcount_dense_metric(1 << 22 if accel else 1 << 17),
         60 if accel else 15, False),
        ("dense_xla_rows_per_sec",
         lambda: dense_path_metric(
             "dense_xla_rows_per_sec", 1 << 22 if accel else 1 << 19,
             use_pallas=False, iters=32 if accel else 4),
         45 if accel else 15, False),
        # terasort_device before hdfs_ingest: the DFS metric is
        # loopback-host-bound (any backend measures it the same), while
        # the device sort needs the chip.
        ("terasort_device_rows_per_sec",
         lambda: terasort_device_metric(1 << 21 if accel else 1 << 16),
         100 if accel else 15, False),
        ("hdfs_ingest_rows_per_sec",
         lambda: hdfs_ingest_metric(1 << 21 if accel else 1 << 19),
         60 if accel else 25, False),
        ("wordcount_rows_per_sec",
         lambda: wordcount_metric(1 << 21 if accel else 1 << 16),
         100 if accel else 25, False),
        ("terasort_rows_per_sec",
         lambda: terasort_metric(1 << 21 if accel else 1 << 16),
         80 if accel else 15, False),
        # out-of-core: >=16x single-batch capacity in bounded HBM
        ("oocsort_rows_per_sec",
         lambda: ooc_sort_metric(
             1 << 26 if accel else 1 << 21,
             chunk_rows=1 << 22 if accel else 1 << 17),
         240 if accel else 60, False),
        ("oocwordcount_rows_per_sec",
         lambda: ooc_wordcount_metric(
             1 << 24 if accel else 1 << 21,
             chunk_bytes=1 << 24 if accel else 1 << 21),
         200 if accel else 60, False),
        # widening-vocab stream: compile-once dictionary coding
        # (runtime-operand tables; dense_compiles bounded by palette
        # tiers instead of chunks)
        ("oocvocab_rows_per_sec",
         lambda: ooc_vocab_metric(
             1 << 22 if accel else 1 << 19,
             chunk_rows=1 << 18 if accel else 1 << 15,
             vocab_step=1 << 11 if accel else 1 << 9),
         200 if accel else 75, False),
        # whole-DAG fusion: one dispatch + one compile key per fused
        # region vs one per stage (plan_fuse on vs off, same plan)
        ("fusedpipe_rows_per_sec",
         lambda: fusedpipe_metric(1 << 21 if accel else 1 << 18),
         90 if accel else 40, False),
        # coded k-of-n vs duplicate-on-straggle makespan under an
        # injected straggler (2 worker processes; host-bound — the
        # workers pin JAX_PLATFORMS=cpu on any backend)
        ("codedagg_makespan_speedup",
         lambda: codedagg_metric(),
         90, False),
        # pipelined vs serial out-of-core driver (same workload, same
        # process): the depth=1 run IS the pre-pipeline baseline
        ("ooc_pipeline_speedup",
         lambda: ooc_pipeline_speedup_metric(
             1 << 24 if accel else 1 << 20,
             chunk_rows=1 << 22 if accel else 1 << 17),
         200 if accel else 75, False),
        # async device-paced dispatch: depth {1,2,4} window matrix on
        # the ooc sort + gang command batching on/off (round-trip count)
        ("asyncpipe_rows_per_sec",
         lambda: asyncpipe_metric(
             1 << 23 if accel else 1 << 20,
             chunk_rows=1 << 20 if accel else 1 << 17),
         240 if accel else 90, False),
        # gang hot path: worker-side combine tree off/on x command
        # window depth {1,2} on a 4-worker gang (host-bound — the
        # workers pin JAX_PLATFORMS=cpu on any backend)
        ("gangtree_rows_per_sec",
         lambda: gangtree_metric(1 << 16),
         240, False),
        # combine tree vs flat merge over a hybrid DCN x ICI mesh
        # (8 virtual CPU devices in a subprocess on any backend:
        # merge structure and byte accounting are platform-free)
        ("aggtree_rows_per_sec",
         lambda: aggtree_metric(1 << 16, chunk_rows=1 << 13),
         300, False),
        # memory-bounded staged exchange vs flat all_to_all on the
        # out-of-core range sort (8 virtual CPU devices in a
        # subprocess; peak-byte accounting is platform-free)
        ("oocxchg_rows_per_sec",
         lambda: ooc_exchange_metric(1 << 18, chunk_rows=1 << 14),
         300, False),
        # runtime plan rewriter vs static plan on adversarial inputs
        # (drift-skewed ooc sort + overflow-prone skewed join; 8
        # virtual CPU devices in a subprocess, byte-identity asserted)
        ("rewrite_rows_per_sec",
         lambda: rewrite_metric(1 << 17, chunk_rows=1 << 13),
         300, False),
        # serving tier: 4 tenants x {16,64} closed-loop clients
        # multiplexed on one resident engine, cache off/on per cell
        # (8 virtual CPU devices in a subprocess; admission,
        # fair-share, and cache behavior are platform-free)
        ("serve_rows_per_sec",
         lambda: serve_metric(1 << 13),
         300, False),
        # materialized views under continuous ingest: views-on vs
        # recompute-per-query vs epoch-nuke on one resident engine
        # (8 virtual CPU devices in a subprocess; snapshot/cache
        # behavior is platform-free)
        ("matview_rows_per_sec",
         lambda: matview_metric(1 << 13),
         240, False),
        # fleet serving plane: multi-process front door + 4 engine
        # replica processes + 64 stdlib client processes,
        # fingerprint-affine routing (vs the single-process ceiling)
        ("serve_fleet_rows_per_sec",
         lambda: serve_fleet_metric(1 << 13),
         420, False),
    ]
    if platform == "tpu":
        # The Pallas kernel only truly runs on TPU; elsewhere the number
        # would be the XLA scan's, so it isn't reported.
        plan.insert(1, (
            "dense_pallas_rows_per_sec",
            lambda: dense_path_metric(
                "dense_pallas_rows_per_sec", 1 << 22, use_pallas=True),
            45, False,
        ))

    errors = 0
    for name, fn, est, is_core in plan:
        if only and not any(w in name for w in only):
            continue
        where = cpu_stamp if name in CPU_PINNED else stamp
        if remaining() < est:
            log(f"skipping {name}: {remaining():.0f}s left < {est}s estimate")
            emit({"metric": name, "skipped": True, **where,
                  "reason": f"budget: {remaining():.0f}s left, need ~{est}s"})
            continue
        try:
            rec = fn()
        except Exception as e:  # noqa: BLE001 - reported, counted, exit != 0
            traceback.print_exc(file=sys.stderr)
            errors += 1
            emit({"metric": name, "error": f"{type(e).__name__}: {e}",
                  **where})
            continue
        rec.update(where)
        rec["vs_baseline"] = round(rec["value"] / baseline, 3)
        if is_core:
            SUMMARY["value"] = rec["value"]
            SUMMARY["vs_baseline"] = rec["vs_baseline"]
            SUMMARY["contended"] = rec["contended"]
            SUMMARY["reps_s"] = rec["reps_s"]
        else:
            SUMMARY[name] = rec["value"]
        emit(rec)
        log(f"{name}: {rec['value']:.3e} rows/s "
            f"(spread {rec['spread']}x{', CONTENDED' if rec['contended'] else ''})")

    SUMMARY["errors"] = errors
    print(json.dumps(SUMMARY), flush=True)
    return errors


def lint_gate() -> None:
    """--lint-gate: refuse to record numbers from a dirty tree.  A
    bench result from a tree with unsuppressed graftlint findings is
    unreproducible evidence (e.g. a host transfer silently serializing
    the very dispatch loop being measured), so the gate runs the whole
    static-analysis registry first and exits 2 on any finding."""
    from dryad_tpu.analysis import engine

    report = engine.run_repo()
    if not report.ok:
        for f in report.unsuppressed():
            print(f.render(), file=sys.stderr)
        print(
            f"bench: refusing to record — {len(report.unsuppressed())} "
            "unsuppressed graftlint finding(s); fix or suppress with a "
            "reason (python -m dryad_tpu.tools.lint)",
            file=sys.stderr,
        )
        sys.exit(2)


OBS_OVERHEAD_LIMIT = 0.02  # always-on observability budget: 2%


def obs_overhead_gate(n: int = 1 << 22, chunk_rows: int = 1 << 20) -> None:
    """--obs-overhead: prove the always-on observability layer (event
    taps -> flight-recorder ring + diagnosis folds + the continuous
    telemetry sampler and its rolling store + query-scoped trace
    propagation) costs < 2% on the out-of-core sort, the
    event-densest workload in the suite.  A/B in
    one process — warmup run first (XLA compile), then interleaved
    off/on pairs, best-of each so scheduler noise cancels.  Emits one
    NDJSON record either way; exits 2 on breach, 0 on pass."""
    from dryad_tpu.obs import flightrec

    _ooc_sort_once(n, chunk_rows)  # warmup: compile + page caches
    on_s, off_s = [], []
    for _ in range(2):
        flightrec.uninstall_recorder()
        off_s.append(_ooc_sort_once(n, chunk_rows, obs=False)[0])
        on_s.append(_ooc_sort_once(n, chunk_rows)[0])
    overhead = min(on_s) / max(min(off_s), 1e-9) - 1.0
    ok = overhead < OBS_OVERHEAD_LIMIT
    emit({
        "metric": "obs_overhead_oocsort",
        "value": round(overhead * 100, 3),
        "unit": "%",
        "limit_pct": OBS_OVERHEAD_LIMIT * 100,
        "ok": ok,
        "obs_on_s": [round(t, 4) for t in on_s],
        "obs_off_s": [round(t, 4) for t in off_s],
        "telemetry": True,
        "query_trace": True,
        "rows": n,
        "chunk_rows": chunk_rows,
        "platform": _PLATFORM,
    })
    if not ok:
        print(
            f"bench: obs overhead {overhead:.2%} exceeds the "
            f"{OBS_OVERHEAD_LIMIT:.0%} budget on oocsort",
            file=sys.stderr,
        )
        sys.exit(2)


def main() -> None:
    """``python bench.py [--lint-gate] [--obs-overhead] [names...]``:
    ONE process on the platform jax gives it.  Positional args select
    metrics by substring (``bench.py serve_fleet``).  Exits non-zero if
    any metric errored.  ``tests_tpu/`` is its own command, run after
    this process has let go of the chip."""
    if "--lint-gate" in sys.argv:
        sys.argv.remove("--lint-gate")
        lint_gate()
    if "--obs-overhead" in sys.argv:
        sys.argv.remove("--obs-overhead")
        obs_overhead_gate()
        sys.exit(0)
    wanted = [a for a in sys.argv[1:] if not a.startswith("-")]
    sys.exit(1 if run_metrics(wanted) else 0)


if __name__ == "__main__":
    main()
