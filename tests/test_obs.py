"""Observability subsystem: spans, metrics, Perfetto export, gang
telemetry, and the EventLog normalization/ring-buffer fixes.

The tracing acceptance bar: concurrent span emission from pipeline
threads is safe and correctly parented; a fixed synthetic event stream
exports to a golden Chrome trace with prefetch / compute / spill on
distinct tracks; worker telemetry merges into one driver-side stream;
jobview grows ``--trace`` and a time-attribution summary.
"""

import json
import threading

import numpy as np
import pytest

from dryad_tpu.exec.events import EventLog
from dryad_tpu.obs.metrics import JobMetrics, MetricsRegistry
from dryad_tpu.obs.span import Tracer
from dryad_tpu.obs.trace import chrome_trace


# -- EventLog fixes ---------------------------------------------------------


class TestEventLog:
    def test_numpy_scalars_normalize_to_native(self, tmp_path):
        """Satellite: numpy scalars/arrays must reach JSON as numbers,
        not ``default=str`` strings that corrupt numeric folds."""
        path = str(tmp_path / "ev.jsonl")
        log = EventLog(path)
        log.emit(
            "stream_chunk",
            rows=np.int64(7),
            frac=np.float32(0.5),
            flag=np.bool_(True),
            arr=np.array([1, 2, 3], np.int32),
        )
        log.close()
        [ev] = EventLog.load(path)
        assert ev["rows"] == 7 and isinstance(ev["rows"], int)
        assert ev["frac"] == 0.5 and isinstance(ev["frac"], float)
        assert ev["flag"] is True
        assert ev["arr"] == [1, 2, 3]
        # in-memory mirror sees the same native values
        [mem] = log.events()
        assert isinstance(mem["rows"], int) and mem["arr"] == [1, 2, 3]

    def test_mono_field_alongside_wall_clock(self):
        log = EventLog(None)
        log.emit("job_start")
        log.emit("job_complete")
        a, b = log.events()
        assert "mono" in a and "ts" in a
        # monotonic never goes backwards even if wall clock steps
        assert b["mono"] >= a["mono"]

    def test_mem_ring_buffer_cap(self, tmp_path):
        """Satellite: the in-memory mirror is bounded; the file sink
        keeps the full stream."""
        path = str(tmp_path / "ev.jsonl")
        log = EventLog(path, mem_cap=4)
        for i in range(10):
            log.emit("stream_chunk", i=i)
        mem = log.events()
        # the ring evicts the oldest; an events_dropped marker flags
        # the truncation so consumers can tell it from quiet history
        data = [e for e in mem if e["kind"] == "stream_chunk"]
        assert [e["i"] for e in data] == list(range(10 - len(data), 10))
        assert len(mem) <= 4
        assert log.dropped >= 10 - len(data)  # markers evict too
        assert any(e["kind"] == "events_dropped" for e in mem)
        log.close()
        loaded = [e for e in EventLog.load(path) if e["kind"] == "stream_chunk"]
        assert [e["i"] for e in loaded] == list(range(10))

    def test_drain_and_absorb(self):
        src, dst = EventLog(None), EventLog(None)
        src.emit("span", name="x", dur=0.5)
        batch = src.drain()
        assert src.events() == [] and len(batch) == 1
        ev = dict(batch[0], worker=1)
        dst.absorb(ev)
        [got] = dst.events()
        assert got["worker"] == 1 and got["ts"] == batch[0]["ts"]


# -- spans ------------------------------------------------------------------


class TestSpans:
    def test_nesting_and_fields(self):
        log = EventLog(None)
        tr = Tracer(log)
        with tr.span("job", cat="driver") as outer:
            with tr.span("stage", cat="execute", stage=3) as inner:
                inner.add(rows=10)
            assert tr.current_id() == outer.span_id
        evs = log.filter("span")
        assert [e["name"] for e in evs] == ["stage", "job"]  # close order
        stage, job = evs
        assert stage["parent_id"] == job["span_id"]
        assert stage["rows"] == 10 and stage["stage"] == 3
        assert job["parent_id"] is None
        assert stage["dur"] >= 0 and "mono" in stage

    def test_a_call_in_a_span_and_disabled_tracer(self):
        log = EventLog(None)
        tr = Tracer(log)

        def work():
            with tr.span("work", cat="execute"):
                return 42

        assert work() == 42
        assert log.filter("span")[0]["name"] == "work"
        off = Tracer(None)
        with off.span("nope") as sp:
            sp.add(x=1)
        assert off.current_id() is None

    def test_a_stack_of_open_spans_is_its_tracers_own(self):
        """Another ``Tracer``'s open span is no parent: a module whose
        spans must nest under the job's is handed the job's tracer (the
        telemetry sampler, ``api/context.py``)."""
        log = EventLog(None)
        mine, theirs = Tracer(log), Tracer(log)
        with mine.span("collect", cat="job") as root:
            assert theirs.current_id() is None
            with theirs.span("save", cat="checkpoint"):
                assert mine.current_id() == root.span_id
            with mine.span("resource_sample", cat="obs"):
                pass
        save, sample, collect = log.filter("span")
        assert save["parent_id"] is None
        assert sample["parent_id"] == collect["span_id"] == root.span_id

    @pytest.mark.parametrize("account", [False, True])
    def test_a_span_accounts_for_itself_only_when_asked(self, account):
        log = EventLog(None)
        tr = Tracer(log)
        with tr.span("encode", cat="ingest", account=account, rows=4) as outer:
            with tr.span("pack", cat="ingest", account=True):
                made = bytearray(8 << 20)
            outer.add(bytes_out=len(made))
        pack, encode = log.filter("span")
        assert encode["rows"] == 4 and encode["bytes_out"] == 8 << 20
        assert "account" not in encode  # the keyword is no field
        stats = {"user_s", "sys_s"}
        assert stats <= set(pack)
        if account:  # inclusive of its children, like ``dur``
            assert encode["user_s"] >= pack["user_s"] - 1e-6
            assert encode["sys_s"] >= pack["sys_s"] - 1e-6
        else:
            assert not stats & set(encode)

    def test_stamped_fields_ride_every_span_of_the_block(self):
        """What a layer knows of the work below it (which of a job's
        answers a fetch is) is stamped once, on this thread's spans of
        this tracer, and the layers below take no parameter for it."""
        log = EventLog(None)
        tr = Tracer(log)
        seen = []

        def elsewhere():
            with tr.span("other_thread"):
                pass
            seen.append(log.filter("span")[-1])

        with tr.span("collect", cat="job"):
            with tr.stamped(output=1):
                with tr.span("fetch_copy", cat="readback", bytes=9):
                    with tr.span("inner"):
                        pass
                with tr.stamped(shard=2):
                    with tr.span("decode", output=7):  # its own wins
                        pass
                with tr.span("drop"):
                    pass
                t = threading.Thread(target=elsewhere)
                t.start()
                t.join()
            with tr.span("release"):
                pass
        by_name = {e["name"]: e for e in log.filter("span")}
        assert by_name["fetch_copy"]["output"] == 1 == by_name["inner"]["output"]
        assert by_name["fetch_copy"]["bytes"] == 9
        assert (by_name["decode"]["output"], by_name["decode"]["shard"]) == (7, 2)
        assert by_name["drop"]["output"] == 1 and "shard" not in by_name["drop"]
        for name in ("release", "collect", "other_thread"):
            assert "output" not in by_name[name], name
        assert seen and Tracer(None).stamped(output=0)  # a disabled tracer too

    def test_error_recorded_on_exception(self):
        log = EventLog(None)
        tr = Tracer(log)
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("bad")
        [ev] = log.filter("span")
        assert "ValueError: bad" in ev["error"]

    def test_concurrent_emission_from_threads(self):
        """Satellite: thread safety + per-thread nesting + explicit
        cross-thread parenting (the pipeline-thread contract)."""
        log = EventLog(None)
        tr = Tracer(log)
        NT, NS = 8, 50
        with tr.span("job", cat="driver") as root:
            root_id = root.span_id

            def worker(t):
                for i in range(NS):
                    with tr.span(
                        f"outer{t}", cat="chunk", parent=root_id, t=t
                    ):
                        with tr.span(f"inner{t}", cat="execute", t=t):
                            pass

            threads = [
                threading.Thread(target=worker, args=(t,))
                for t in range(NT)
            ]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
        spans = log.filter("span")
        assert len(spans) == NT * NS * 2 + 1
        ids = [e["span_id"] for e in spans]
        assert len(set(ids)) == len(ids), "span ids must be unique"
        by_id = {e["span_id"]: e for e in spans}
        for e in spans:
            if e["name"].startswith("inner"):
                parent = by_id[e["parent_id"]]
                # nested under ITS OWN thread's outer span, never
                # another thread's
                assert parent["name"] == f"outer{e['t']}"
                assert parent["thread"] == e["thread"]
            elif e["name"].startswith("outer"):
                assert e["parent_id"] == root_id


# -- metrics ----------------------------------------------------------------


class TestMetrics:
    def test_counters_and_histograms(self):
        m = MetricsRegistry()
        m.add("rows_out", 10, stage="s1")
        m.add("rows_out", 5, stage="s1")
        m.add("rows_out", 7, stage="s2")
        assert m.counter("rows_out", stage="s1") == 15
        assert m.total("rows_out") == 22
        for v in (1, 3, 900):
            m.observe("partition_rows", v, depth=0)
        snap = m.snapshot()
        [h] = snap["hists"]
        assert h["n"] == 3 and h["min"] == 1 and h["max"] == 900
        assert sum(h["buckets"].values()) == 3  # pow2 skew buckets

    def test_concurrent_adds(self):
        m = MetricsRegistry()

        def add():
            for _ in range(1000):
                m.add("c", 1)

        ts = [threading.Thread(target=add) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert m.counter("c") == 8000

    def test_job_metrics_fold_and_attribution(self):
        evs = [
            {"kind": "span", "cat": "execute", "dur": 1.0},
            {"kind": "span", "cat": "prefetch", "dur": 0.25},
            {"kind": "span", "cat": "spill", "dur": 0.5, "bytes": 100},
            {"kind": "span", "cat": "chunk", "dur": 99.0},  # structural
            {"kind": "xla_compile", "compile_s": 2.0, "trace_s": 0.1},
            {"kind": "stream_pipeline", "consumer_wait_s": 0.5,
             "producer_wait_s": 0.125},
            {"kind": "stage_failed"},
            {"kind": "computer_quarantined"},
        ]
        m = JobMetrics.from_events(evs)
        assert m.execute_s == 1.0
        assert m.ingest_s == 0.25
        assert m.spill_write_s == 0.5 and m.spill_bytes == 100
        assert m.compile_count == 1 and m.compile_s == 2.0
        assert m.ingest_stall_s == 0.5 and m.compute_stall_s == 0.125
        assert m.retries == 1 and m.quarantines == 1
        attr = m.attribution()
        assert attr["compile_s"] == 2.0 and attr["execute_s"] == 1.0

    def test_cumulative_metrics_events_do_not_double_count(self):
        """Registry snapshots are cumulative: only the LAST per source
        counts."""
        reg = MetricsRegistry()
        log = EventLog(None)
        reg.add("d2h_bytes", 100)
        reg.emit(log)
        reg.add("d2h_bytes", 50)
        reg.emit(log)  # cumulative: 150
        m = JobMetrics.from_events(log.events())
        assert m.d2h_bytes == 150

    def test_padding_waste(self):
        m = JobMetrics(layout_rows=100, valid_rows=75)
        assert m.padding_waste == 0.25
        assert JobMetrics().padding_waste == 0.0


# -- Perfetto export --------------------------------------------------------


def _golden_stream():
    """Fixed synthetic event stream: a prefetch pull, a compute span,
    a spill write (each on its own thread), an occupancy sample, and
    an instant marker — plus one worker-merged span."""
    return [
        {"ts": 100.0, "mono": 5.0, "kind": "job_start", "stages": 1},
        {"ts": 100.2, "mono": 5.2, "kind": "span", "name": "ingest",
         "cat": "prefetch", "span_id": 1, "parent_id": None,
         "dur": 0.2, "thread": "dryad-ingest"},
        {"ts": 100.25, "mono": 5.25, "kind": "stream_prefetch",
         "pipeline": "ingest", "queued": 1, "in_flight": 2},
        {"ts": 100.5, "mono": 5.5, "kind": "span", "name": "sort",
         "cat": "execute", "span_id": 2, "parent_id": None,
         "dur": 0.3, "thread": "MainThread"},
        {"ts": 100.6, "mono": 5.6, "kind": "span", "name": "spill_piece",
         "cat": "spill", "span_id": 3, "parent_id": None,
         "dur": 0.1, "thread": "dryad-spill-writer", "bytes": 64},
        {"ts": 100.7, "mono": 5.7, "kind": "span", "name": "runpart",
         "cat": "worker", "span_id": 4, "parent_id": None,
         "dur": 0.4, "thread": "MainThread", "worker": 1},
        {"ts": 100.9, "mono": 5.9, "kind": "job_complete"},
    ]


class TestChromeTrace:
    def test_golden_export(self):
        tr = chrome_trace(_golden_stream())
        evs = tr["traceEvents"]
        # distinct tracks: prefetch, compute (MainThread), spill
        names = {
            (e["pid"], e["args"]["name"])
            for e in evs if e["ph"] == "M" and e["name"] == "thread_name"
        }
        assert (0, "dryad-ingest") in names
        assert (0, "MainThread") in names
        assert (0, "dryad-spill-writer") in names
        procs = {
            e["pid"]: e["args"]["name"]
            for e in evs if e["ph"] == "M" and e["name"] == "process_name"
        }
        assert procs[0] == "driver" and procs[2] == "worker1"
        # spans: complete events with ts rebased to the stream start
        # (base = min span start = 100.0 = job_start ts)
        xs = {e["name"]: e for e in evs if e["ph"] == "X"}
        assert set(xs) == {"ingest", "sort", "spill_piece", "runpart"}
        assert xs["ingest"]["ts"] == 0.0  # 100.2 - 0.2 dur - base
        assert xs["ingest"]["dur"] == 0.2e6
        assert xs["sort"]["ts"] == 0.2e6 and xs["sort"]["dur"] == 0.3e6
        assert xs["spill_piece"]["args"]["bytes"] == 64
        assert xs["runpart"]["pid"] == 2  # worker 1 -> own process
        # counter track for pipeline occupancy
        [c] = [e for e in evs if e["ph"] == "C"]
        assert c["args"]["in_flight"] == 2
        # instants for the state transitions
        inst = {e["name"] for e in evs if e["ph"] == "i"}
        assert {"job_start", "job_complete"} <= inst
        # the whole thing is JSON-serializable as-is
        json.dumps(tr)

    def test_empty_stream(self):
        assert chrome_trace([])["traceEvents"] == []


# -- gang telemetry ---------------------------------------------------------


class TestGangTelemetry:
    def test_ship_and_drain_with_offset(self):
        from dryad_tpu.cluster.service import Mailbox
        from dryad_tpu.parallel.multihost import ControlPlane

        mb = Mailbox()
        worker = ControlPlane("job", 0, mailbox=mb)
        driver = ControlPlane("job", -1, mailbox=mb)

        wlog = EventLog(None)
        wtr = Tracer(wlog)
        with wtr.span("runpart", cat="worker", part=3):
            pass
        worker.ship_telemetry(wlog.drain())
        # a second batch on the numbered channel must not be lost
        wlog.emit("stream_chunk", rows=5)
        worker.ship_telemetry(wlog.drain())

        dlog = EventLog(None)
        state = {}
        n = driver.drain_telemetry(2, state, dlog)
        assert n == 2
        spans = dlog.filter("span")
        assert spans and spans[0]["worker"] == 0
        assert "clock_offset" in spans[0]
        chunk = dlog.filter("stream_chunk")[0]
        assert chunk["worker"] == 0 and chunk["rows"] == 5
        [merged] = dlog.filter("telemetry_merged")
        assert merged["events"] == 2
        # idempotent: cursors advanced, nothing re-absorbed
        assert driver.drain_telemetry(2, state, dlog) == 0

    def test_empty_batch_is_noop(self):
        from dryad_tpu.cluster.service import Mailbox
        from dryad_tpu.parallel.multihost import ControlPlane

        mb = Mailbox()
        cp = ControlPlane("job", 0, mailbox=mb)
        cp.ship_telemetry([])
        dlog = EventLog(None)
        assert cp.drain_telemetry(1, {}, dlog) == 0
        assert dlog.events() == []


# -- end to end: streaming job -> jobview --trace ---------------------------


@pytest.fixture
def ooc_events(tmp_path):
    """One small pipelined out-of-core sort with a file-backed event
    log; returns the log path."""
    from dryad_tpu import DryadConfig, DryadContext

    rng = np.random.default_rng(0)
    chunks = [
        {"key": rng.integers(0, 1000, 4000).astype(np.int32)}
        for _ in range(3)
    ]
    cfg = DryadConfig(
        stream_buckets=8, event_log_dir=str(tmp_path / "evlog")
    )
    ctx = DryadContext(config=cfg)
    out = ctx.from_stream(iter(chunks)).order_by(["key"]).collect()
    assert (np.diff(out["key"]) >= 0).all()
    import glob

    [path] = glob.glob(str(tmp_path / "evlog" / "*.jsonl"))
    ctx.events.close()
    return path


def test_jobview_trace_export_cli(ooc_events, tmp_path, capsys):
    from dryad_tpu.tools import jobview

    trace_out = str(tmp_path / "trace.json")
    rc = jobview.main(["--trace", trace_out, ooc_events])
    assert rc == 0
    with open(trace_out) as fh:
        tr = json.load(fh)
    evs = tr["traceEvents"]
    assert evs, "trace must not be empty"
    tracks = {
        e["args"]["name"]
        for e in evs if e["ph"] == "M" and e["name"] == "thread_name"
    }
    # prefetch / spill threads render as their own tracks; compute
    # spans ride the thread that dispatched the engine jobs
    assert any(t.startswith("dryad-") for t in tracks)
    assert "dryad-spill-writer" in tracks
    assert any(e["ph"] == "X" and e["cat"] == "execute" for e in evs)
    assert any(e["ph"] == "C" for e in evs), "occupancy counter track"
    out = capsys.readouterr().out
    assert "time attribution" in out and "compile=" in out


def test_job_metrics_snapshot_from_live_context():
    """Programmatic JobMetrics: the acceptance-criteria snapshot
    (compile vs execute vs stalls vs spill) from a live run."""
    from dryad_tpu import DryadConfig, DryadContext

    rng = np.random.default_rng(1)
    chunks = [
        {"k": rng.integers(0, 50, 2000).astype(np.int32),
         "v": rng.standard_normal(2000).astype(np.float32)}
        for _ in range(3)
    ]
    ctx = DryadContext(config=DryadConfig())
    out = (
        ctx.from_stream(iter(chunks))
        .group_by("k", {"s": ("sum", "v")})
        .collect()
    )
    assert len(out["k"]) == 50
    m = JobMetrics.from_events(ctx.events.events())
    assert m.compile_count >= 1 and m.compile_s > 0
    assert m.execute_s > 0
    assert m.h2d_bytes > 0 and m.d2h_bytes > 0
    assert 0.0 <= m.padding_waste < 1.0
    assert m.spans > 0
    for key in ("compile_s", "ingest_stall_s", "spill_bytes",
                "padding_waste"):
        assert key in m.attribution()


def test_the_drain_span_carries_what_the_exchange_saw():
    """The counts that ride the overflow flag's readback are fields of
    the job's ``drain`` span (the list a chip on the event; numbers
    alone reach a profiler annotation, so its largest entry beside it)
    and of one ``exchange_observed`` event a dispatch, both under the
    job's qid; on one partition nothing is exchanged and neither says
    anything."""
    from dryad_tpu import DryadContext
    from dryad_tpu.obs import critpath

    rng = np.random.default_rng(41)
    table = {"k": (rng.zipf(1.3, 6000) % 97 - 1).astype(np.int32),
             "v": rng.standard_normal(6000).astype(np.float32)}
    for P in (4, 1):
        ctx = DryadContext(num_partitions_=P)
        ctx.from_arrays(table).group_by("k", {"s": ("sum", "v")}).collect()
        events = ctx.events.events()
        (qid,) = critpath.query_ids(events)
        drains = [e for e in events if e["kind"] == "span" and e["name"] == "drain"]
        seen = [e for e in events if e["kind"] == "exchange_observed"]
        assert len(drains) == 1 and len(seen) == (P > 1)
        if P == 1:
            assert "combine_rows_in" not in drains[0]
            continue
        drain, event = drains[0], seen[0]
        assert drain["qid"] == event["qid"] == qid
        assert drain["combine_rows_in"] == event["combine_rows_in"] == 6000
        assert 97 <= drain["combine_rows_out"] < 4 * 97 + 1
        assert drain["recv_rows"] == event["recv_rows"]
        assert sum(drain["recv_rows"]) == drain["combine_rows_out"]
        assert drain["recv_rows_max"] == max(drain["recv_rows"])
        assert (drain["boost"], drain["overflows"], drain["exchanges"]) == (1, 0, 1)
        numeric = {k for k, v in drain.items() if isinstance(v, (int, float))}
        assert {"combine_rows_in", "combine_rows_out", "recv_rows_max", "boost",
                "overflows"} <= numeric
