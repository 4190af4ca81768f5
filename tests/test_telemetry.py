"""Continuous telemetry plane tests (obs.telemetry + tools.metricsd):
rolling-window golden values under a fake clock (exact percentile
readouts across window rotation), Prometheus/JSON export roundtrips,
the live resource sampler with injected memory readings, the
measured-headroom adaptive depth policy, the ``hbm_pressure``
diagnosis -> rewriter fold, and the metricsd CLI.

Everything here is deterministic: the store's clock, the sampler's
clock, and its device-memory reader are all injected — no sleeps, no
real HBM.
"""

import json

import pytest

from dryad_tpu.exec.events import EventLog
from dryad_tpu.exec.pipeline import DispatchWindow
from dryad_tpu.obs import flightrec
from dryad_tpu.obs.diagnose import DiagnosisEngine
from dryad_tpu.obs.telemetry import (
    METRIC_KEYS,
    HeadroomProvider,
    ResourceMonitor,
    RollingStore,
    bucket_upper,
    latency_bucket,
    percentile_of,
    prometheus_text,
    resolve_depth,
)
from dryad_tpu.rewrite.controller import RewriteController
from dryad_tpu.tools import metricsd


class FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t


@pytest.fixture(autouse=True)
def _no_shared_probe_leak():
    """Tests registering shared flightrec probes must not leak them."""
    yield
    for name in list(flightrec._SHARED_PROBES):
        flightrec.unprobe(name)


# -- pow2 latency bucketing ---------------------------------------------------


def test_latency_bucket_pow2_bounds():
    # 2^(e-1) <= v < 2^e; readout is the bucket UPPER bound
    assert bucket_upper(latency_bucket(0.3)) == 0.5
    assert bucket_upper(latency_bucket(0.25)) == 0.5
    assert bucket_upper(latency_bucket(1.0)) == 2.0
    assert bucket_upper(latency_bucket(0.0)) == 0.0
    assert bucket_upper(latency_bucket(-3.0)) == 0.0


def test_percentile_of_offline_twin():
    assert percentile_of([], 0.5) is None
    assert percentile_of([0.3, 1.0], 0.5) == 0.5
    assert percentile_of([0.3, 1.0], 0.95) == 2.0
    assert percentile_of([0.25], 0.99) == 0.5


# -- RollingStore golden values ----------------------------------------------


def test_percentile_goldens_two_observations():
    clk = FakeClock()
    st = RollingStore(window_s=60.0, buckets=6, clock=clk)
    st.observe_latency("query_latency_s", 0.3, tenant="a")
    st.observe_latency("query_latency_s", 1.0, tenant="a")
    assert st.percentiles("query_latency_s", tenant="a") == {
        "n": 2, "p50": 0.5, "p95": 2.0, "p99": 2.0,
    }
    # single observation: every quantile reads its bucket's upper bound
    st.observe_latency("query_latency_s", 0.25, tenant="b")
    assert st.percentiles("query_latency_s", tenant="b") == {
        "n": 1, "p50": 0.5, "p95": 0.5, "p99": 0.5,
    }
    # unseen label set: None, not zeros
    assert st.percentiles("query_latency_s", tenant="zz") is None


def test_window_rotation_expires_counters_and_histograms():
    clk = FakeClock(0.0)
    st = RollingStore(window_s=6.0, buckets=3, clock=clk)  # 2s sub-windows
    st.incr("queries_admitted", tenant="a")
    st.observe_latency("query_latency_s", 0.3, tenant="a")
    clk.t = 3.0
    st.incr("queries_admitted", tenant="a")
    # both sub-windows still live at t=5
    clk.t = 5.0
    assert st.counter_total("queries_admitted", tenant="a") == 2
    assert st.percentiles("query_latency_s", tenant="a")["n"] == 1
    # t=7: the t=0 sub-window aged out; the t=3 write survives
    clk.t = 7.0
    assert st.counter_total("queries_admitted", tenant="a") == 1
    assert st.percentiles("query_latency_s", tenant="a") is None
    # t=100: everything aged out
    clk.t = 100.0
    assert st.counter_total("queries_admitted", tenant="a") == 0


def test_gauges_are_point_in_time_not_windowed():
    clk = FakeClock(0.0)
    st = RollingStore(window_s=6.0, buckets=3, clock=clk)
    st.set_gauge("serve_queue_depth", 4)
    st.set_gauge("serve_queue_depth", 2)  # last write wins
    clk.t = 1000.0  # far past the window: gauges do not decay
    assert st.gauge("serve_queue_depth") == 2
    assert st.gauge("hbm_used_bytes") is None


def test_labels_separate_series_and_label_sets():
    st = RollingStore(clock=FakeClock())
    st.incr("queries_admitted", tenant="a")
    st.incr("queries_admitted", n=3, tenant="b")
    assert st.counter_total("queries_admitted", tenant="a") == 1
    assert st.counter_total("queries_admitted", tenant="b") == 3
    assert st.counter_total("queries_admitted") == 0  # unlabeled differs
    assert st.label_sets("queries_admitted") == [
        {"tenant": "a"}, {"tenant": "b"},
    ]


def test_store_rejects_degenerate_shapes():
    with pytest.raises(ValueError):
        RollingStore(window_s=0.0)
    with pytest.raises(ValueError):
        RollingStore(buckets=0)


# -- export surfaces ----------------------------------------------------------


def _golden_store():
    st = RollingStore(clock=FakeClock())
    st.incr("queries_admitted", tenant="a")
    st.observe_latency("query_latency_s", 0.25, tenant="a")
    st.set_gauge("serve_queue_depth", 2)
    return st


def test_prometheus_text_golden():
    text = prometheus_text(_golden_store().snapshot())
    assert text == (
        "# HELP dryad_queries_admitted_total "
        "queries past admission, windowed, per tenant\n"
        "# TYPE dryad_queries_admitted_total counter\n"
        'dryad_queries_admitted_total{tenant="a"} 1\n'
        "# HELP dryad_serve_queue_depth "
        "queued-and-unpicked queries across tenants\n"
        "# TYPE dryad_serve_queue_depth gauge\n"
        "dryad_serve_queue_depth 2\n"
        "# HELP dryad_query_latency_s "
        "admission->completion latency, per tenant\n"
        "# TYPE dryad_query_latency_s summary\n"
        'dryad_query_latency_s{tenant="a",quantile="0.5"} 0.5\n'
        'dryad_query_latency_s{tenant="a",quantile="0.95"} 0.5\n'
        'dryad_query_latency_s{tenant="a",quantile="0.99"} 0.5\n'
        'dryad_query_latency_s_count{tenant="a"} 1\n'
    )


def test_json_snapshot_roundtrip():
    snap = _golden_store().snapshot()
    back = json.loads(json.dumps(snap))
    assert back == snap
    assert back["counters"] == [
        {"name": "queries_admitted", "labels": {"tenant": "a"}, "total": 1}
    ]
    assert back["latencies"][0]["p50"] == 0.5
    assert back["gauges"] == [
        {"name": "serve_queue_depth", "labels": {}, "value": 2.0}
    ]


def test_every_metric_key_documented_one_line():
    for name, doc in METRIC_KEYS.items():
        assert doc.strip() and "\n" not in doc, name


# -- measured headroom -> adaptive policies -----------------------------------


def test_headroom_provider_latest_measurement_wins():
    p = HeadroomProvider()
    assert p.headroom_bytes() is None
    p.update(1 << 30)
    assert p.headroom_bytes() == 1 << 30
    p.update(None)  # host fallback: measurement withdrawn, not stale
    assert p.headroom_bytes() is None


def test_resolve_depth_tiers_and_static_passthrough():
    p = HeadroomProvider()
    # adaptive with no measurement: the default tier
    assert resolve_depth(-1, None) == 2
    assert resolve_depth(-1, p) == 2
    # measured tiers
    p.update(8 << 30)
    assert resolve_depth(-1, p) == 4
    p.update(2 << 30)
    assert resolve_depth(-1, p) == 3
    p.update(512 << 20)
    assert resolve_depth(-1, p) == 2
    p.update(100)
    assert resolve_depth(-1, p) == 1
    # static values return VERBATIM — including invalid ones, so the
    # caller's own validation still rejects them
    assert resolve_depth(3, p) == 3
    assert resolve_depth(0, p) == 0


def test_dispatch_window_adaptive_depth_from_fake_provider():
    p = HeadroomProvider()
    p.update(2 << 30)
    w = DispatchWindow(-1, headroom=p)
    try:
        assert w.depth == 3
    finally:
        w.close()
    # no measurement -> the default adaptive depth
    w = DispatchWindow(-1)
    try:
        assert w.depth == 2
    finally:
        w.close()
    # static zero still rejected (adaptive mode never masks it)
    with pytest.raises(ValueError):
        DispatchWindow(0)


# -- ResourceMonitor ----------------------------------------------------------


def test_sampler_device_path_feeds_headroom_gauges_and_events():
    clk = FakeClock()
    log = EventLog(None)
    st = RollingStore(clock=clk)
    mon = ResourceMonitor(
        interval_s=1.0, events=log, store=st, clock=clk,
        device_memory_fn=lambda: (3 << 30, 4 << 30),
    )
    flightrec.probe("serve:queue", lambda: {"queued": 5})
    snap = mon.sample()
    assert snap["source"] == "device"
    assert snap["hbm_headroom_bytes"] == 1 << 30
    assert snap["probes"]["serve:queue"] == {"queued": 5}
    assert mon.headroom.headroom_bytes() == 1 << 30
    assert st.gauge("hbm_used_bytes") == 3 << 30
    assert st.gauge("hbm_limit_bytes") == 4 << 30
    evs = log.filter("resource_sample")
    assert len(evs) == 1 and evs[0]["hbm_used_bytes"] == 3 << 30


def test_sampler_host_fallback_withdraws_headroom():
    clk = FakeClock()
    st = RollingStore(clock=clk)
    mon = ResourceMonitor(
        interval_s=1.0, store=st, clock=clk, device_memory_fn=lambda: None
    )
    mon.headroom.update(1 << 30)  # a stale device reading must not survive
    snap = mon.sample()
    assert snap["source"] == "host"
    assert mon.headroom.headroom_bytes() is None
    if "rss_kb" in snap:  # /proc present on linux hosts
        assert snap["rss_kb"] > 0
        assert st.gauge("host_rss_kb") == snap["rss_kb"]


def test_tap_paces_samples_and_ignores_its_own_events():
    clk = FakeClock(0.0)
    log = EventLog(None)
    mon = ResourceMonitor(
        interval_s=1.0, events=log, clock=clk,
        device_memory_fn=lambda: (1, 2),
    )
    log.add_tap(mon.observe)
    log.emit("note", text="a")  # first event: samples immediately
    log.emit("note", text="b")  # same instant: paced out
    assert len(log.filter("resource_sample")) == 1
    clk.t = 0.5
    log.emit("note", text="c")  # under the interval: paced out
    assert len(log.filter("resource_sample")) == 1
    clk.t = 1.5
    log.emit("note", text="d")
    assert len(log.filter("resource_sample")) == 2
    # the sample's own event re-enters the tap without self-feedback,
    # and a poisoned reader never raises through the tap
    clk.t = 10.0
    mon._device_memory = lambda: (_ for _ in ()).throw(RuntimeError("x"))
    log.emit("note", text="e")
    assert len(log.filter("resource_sample")) == 2


def test_the_sample_is_a_span_under_the_span_whose_event_found_it_due():
    from dryad_tpu.obs import critpath
    from dryad_tpu.obs.span import Tracer

    class Ticking:  # every reading a minute later: a sample is always due
        t = 0.0

        def __call__(self):
            self.t += 60.0
            return self.t

    log = EventLog(None)
    tracer = Tracer(log)
    mon = ResourceMonitor(
        interval_s=1.0, events=log, clock=Ticking(),
        device_memory_fn=lambda: (1, 2),
    )
    mon.tracer = tracer  # as ``DryadContext`` hands it its own
    log.add_tap(mon.observe)
    with tracer.span("collect", cat="job") as job:
        with tracer.span("drain", cat="readback"):
            pass
    # drain's event sampled while ``collect`` was the thread's open span;
    # collect's own event sampled with no span open.  Neither the sample's
    # event nor its span's re-entered the sampler, due as it always was.
    assert [(e["kind"], e.get("name")) for e in log.events()] == [
        ("span", "drain"), ("resource_sample", None), ("span", "resource_sample"),
        ("span", "collect"), ("resource_sample", None), ("span", "resource_sample"),
    ]
    first, second = [e for e in log.filter("span") if e["name"] == "resource_sample"]
    assert first["parent_id"] == job.span_id and second["parent_id"] is None
    assert first["cat"] == "obs"
    assert critpath.phase_of("resource_sample", "obs") == "other"
    assert "user_s" not in first  # it writes no table: seconds alone
    # a monitor with no event log samples under no span and emits nothing
    quiet = ResourceMonitor(interval_s=1.0, device_memory_fn=lambda: (1, 2))
    assert quiet.sample()["source"] == "device"
    assert len(log.events()) == 6
    # left with its own tracer, the monitor opens its span on that:
    # under no span of another tracer's
    alone = EventLog(None)
    mon = ResourceMonitor(
        interval_s=1.0, events=alone, device_memory_fn=lambda: (1, 2))
    with Tracer(alone).span("collect", cat="job"):
        mon.sample()
    assert alone.filter("span")[0]["parent_id"] is None


def test_sample_ring_is_bounded():
    mon = ResourceMonitor(
        interval_s=1.0, clock=FakeClock(), history=4,
        device_memory_fn=lambda: (1, 2),
    )
    for _ in range(10):
        mon.sample()
    assert len(mon.recent()) == 4


# -- hbm_pressure: diagnosis -> rewriter hint ---------------------------------


def _pressure_ev(used, limit):
    return {
        "kind": "resource_sample", "source": "device",
        "hbm_used_bytes": used, "hbm_limit_bytes": limit,
        "hbm_headroom_bytes": max(0, limit - used),
    }


def test_hbm_pressure_diagnosis_fires_at_ratio():
    log = EventLog(None, mem_cap=256)
    eng = DiagnosisEngine(events=log)
    log.add_tap(eng.observe)
    log.emit(**_pressure_ev(used=90, limit=100))  # 0.90 < 0.92: quiet
    assert not eng.diagnoses()
    log.emit(**_pressure_ev(used=95, limit=100))
    d = next(x for x in eng.diagnoses() if x["rule"] == "hbm_pressure")
    assert d["evidence"]["ratio"] == 0.95
    assert d["evidence"]["headroom"] == 5
    # host-fallback samples (no device limit) fold nowhere
    log.emit(kind="resource_sample", source="host", rss_kb=123)


def test_hbm_pressure_pins_exchange_window_once():
    c = RewriteController()
    ev = {
        "kind": "diagnosis", "rule": "hbm_pressure",
        "evidence": {"used": 95, "limit": 100, "ratio": 0.95, "headroom": 5},
    }
    c.observe(ev)
    assert c.exchange_window_hint() == 1
    n = len(c.actions())
    c.observe(ev)  # pressure persists: the pin stays, no re-decision
    assert c.exchange_window_hint() == 1
    assert len(c.actions()) == n


# -- metricsd CLI -------------------------------------------------------------


def _write_log(path, events):
    with open(path, "w") as fh:
        for ev in events:
            fh.write(json.dumps(ev) + "\n")


_SERVE_EVENTS = [
    {"kind": "query_admitted", "tenant": "a"},
    {"kind": "query_admitted", "tenant": "b"},
    {"kind": "query_complete", "tenant": "a", "seconds": 0.3},
    {"kind": "query_complete", "tenant": "b", "seconds": 1.0},
    {"kind": "result_cache_hit", "tenant": "a"},
    {"kind": "query_rejected", "tenant": "b"},
    {"kind": "resource_sample", "source": "device",
     "hbm_used_bytes": 10, "hbm_limit_bytes": 100,
     "hbm_headroom_bytes": 90,
     "probes": {"serve:queue": {"queued": 3}}},
]


def test_load_events_offset_and_torn_tail(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    with open(path, "w") as fh:
        fh.write(json.dumps({"kind": "note", "n": 1}) + "\n")
        fh.write('{"kind": "note", "n": 2')  # torn mid-write
    evs, off = metricsd.load_events(path)
    assert [e["n"] for e in evs] == [1]
    # the producer finishes the line; the next poll picks it up alone
    with open(path, "a") as fh:
        fh.write(', "x": 0}\n')
    evs, off = metricsd.load_events(path, off)
    assert [e["n"] for e in evs] == [2]
    assert metricsd.load_events(path, off) == ([], off)
    assert metricsd.load_events(str(tmp_path / "nope"), 7) == ([], 7)


def test_fold_events_matches_live_plane_series():
    st = metricsd.fold_events(_SERVE_EVENTS)
    assert st.counter_total("queries_admitted", tenant="a") == 1
    assert st.counter_total("queries_rejected", tenant="b") == 1
    assert st.counter_total("result_cache_hits", tenant="a") == 1
    assert st.percentiles("query_latency_s", tenant="a")["p50"] == 0.5
    assert st.percentiles("query_latency_s", tenant="b")["p99"] == 2.0
    assert st.gauge("hbm_headroom_bytes") == 90
    assert st.gauge("serve_queue_depth") == 3


def test_metricsd_oneshot_prometheus_and_json(tmp_path, capsys):
    path = str(tmp_path / "ev.jsonl")
    _write_log(path, _SERVE_EVENTS)
    assert metricsd.main([path]) == 0
    out = capsys.readouterr().out
    assert 'dryad_queries_admitted_total{tenant="a"} 1' in out
    assert 'dryad_query_latency_s{tenant="b",quantile="0.99"} 2.0' in out
    assert "dryad_serve_queue_depth 3" in out
    assert metricsd.main([path, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert any(
        rec["labels"] == {"tenant": "a"} and rec["p95"] == 0.5
        for rec in doc["latencies"]
    )


def test_metricsd_file_sinks_and_errors(tmp_path, capsys):
    path = str(tmp_path / "ev.jsonl")
    _write_log(path, _SERVE_EVENTS)
    prom = str(tmp_path / "out.prom")
    jout = str(tmp_path / "out.json")
    assert metricsd.main([path, "--prom", prom, "--json-out", jout]) == 0
    assert capsys.readouterr().out == ""  # sinks given: nothing printed
    with open(prom) as fh:
        assert "dryad_queries_completed_total" in fh.read()
    with open(jout) as fh:
        assert json.load(fh)["counters"]
    assert metricsd.main([]) == 2  # usage
    assert metricsd.main([str(tmp_path / "missing.jsonl")]) == 1


def test_metricsd_glob_oneshot_folds_every_match(tmp_path, capsys):
    """A fleet writes one event log per replica; a glob input folds
    them all into the one fleet view."""
    _write_log(str(tmp_path / "r0.jsonl"), _SERVE_EVENTS[:3])
    _write_log(str(tmp_path / "r1.jsonl"), _SERVE_EVENTS[3:6])
    assert metricsd.main([str(tmp_path / "r*.jsonl"), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    admitted = {
        rec["labels"]["tenant"]: rec["total"]
        for rec in doc["counters"]
        if rec["name"] == "queries_admitted"
    }
    assert admitted == {"a": 1, "b": 1}  # one from each file
    completed = sum(
        rec["total"] for rec in doc["counters"]
        if rec["name"] == "queries_completed"
    )
    assert completed == 2


def test_cursorset_reexpands_glob_and_tails_per_path(tmp_path):
    """Follow-mode contract: a replica log that APPEARS after the
    first poll (respawn after a chaos kill) is picked up with its own
    cursor, and existing cursors never re-read folded bytes."""
    cs = metricsd.CursorSet([str(tmp_path / "*.jsonl")])
    _write_log(str(tmp_path / "r0.jsonl"), [{"kind": "note", "n": 1}])
    assert [e["n"] for e in cs.poll()] == [1]
    assert cs.poll() == []  # nothing new
    # a second replica appears; the first appends
    _write_log(str(tmp_path / "r1.jsonl"), [{"kind": "note", "n": 10}])
    with open(str(tmp_path / "r0.jsonl"), "a") as fh:
        fh.write(json.dumps({"kind": "note", "n": 2}) + "\n")
    got = sorted(e["n"] for e in cs.poll())
    assert got == [2, 10]
    assert sorted(cs.paths()) == [
        str(tmp_path / "r0.jsonl"), str(tmp_path / "r1.jsonl"),
    ]


def test_expand_inputs_literal_paths_pass_through(tmp_path):
    lit = str(tmp_path / "does-not-exist.jsonl")
    assert metricsd.expand_inputs([lit]) == [lit]
    _write_log(str(tmp_path / "a.jsonl"), [])
    _write_log(str(tmp_path / "b.jsonl"), [])
    got = metricsd.expand_inputs(
        [str(tmp_path / "*.jsonl"), str(tmp_path / "a.jsonl")]
    )
    # sorted matches, deduped against the literal repeat
    assert got == [str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")]


# -- jobview telemetry panel --------------------------------------------------


def test_jobview_telemetry_panel():
    from dryad_tpu.tools.jobview import render_telemetry

    events = _SERVE_EVENTS + [
        {"kind": "resource_sample", "source": "host", "rss_kb": 2048},
    ]
    text = render_telemetry(events)
    assert "-- telemetry (2 samples) --" in text
    assert "hbm: used=0MB/0MB" in text  # tiny fixture bytes floor to 0MB
    assert "host rss: last=2MB  peak=2MB" in text
    assert "slo a: n=1  p50<=0.5s  p95<=0.5s  p99<=0.5s" in text
    assert "slo b: n=1" in text and "p99<=2s" in text
    # streams with no samples render nothing (existing goldens intact)
    assert render_telemetry([{"kind": "stage_start", "ts": 0.0}]) == ""
