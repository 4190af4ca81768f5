"""``program_spans``: the program's ``dryad:*`` spans and ``dryad.``
scopes from hand-built planes whose every number can be counted by
hand (as ``test_trace_reduce`` does), each new metric file's
arithmetic on them, and one traced run on the CPU end to end."""

import json

import pytest

import program_spans as PS
import run
import trace_reduce as TR

SCOPE = "jit(dryad_stage)/shard_map/"


def span(name, start, end, span_id, parent_id=0, **stats):
    return (name, start, end, {"span_id": span_id, "parent_id": parent_id, **stats})


def hand_planes(spans=True, scopes=True):
    """One chip, a 20 s window: a fresh job 0-10 (bind-time spans 0-1.5,
    ``collect`` 2-10, device busy 4.5-7.5) and a requery 10-18 (busy
    10.5-14).  ``spans=False`` / ``scopes=False``: the same run by a
    program without them."""
    q1, q2 = {"qid": "q-1"}, {"qid": "q-2"}
    host = [
        ("bench:window", 0.0, 20.0, {}),
        ("bench:fresh", 0.0, 10.0, {}),
        ("bench:requery", 10.0, 18.0, {}),
    ]
    if spans:
        host += [
            span("dryad:ingest:tokenize", 0.0, 1.0, 1, bytes=700),
            span("dryad:ingest:vocab", 1.0, 1.5, 2, rows=100),
            span("dryad:other:collect", 2.0, 10.0, 3, **q1),
            span("dryad:plan:lower", 2.0, 2.1, 4, 3, **q1),
            span("dryad:ingest:bind", 2.1, 4.0, 5, 3, node=7, **q1),
            span("dryad:ingest:encode", 2.1, 3.0, 6, 5, rows=100, capacity=128, **q1),
            span("dryad:ingest:h2d", 3.0, 4.0, 7, 5, bytes=1000, **q1),
            span("dryad:dispatch:input+order_by", 4.0, 4.5, 8, 3, stage=0, **q1),
            span("dryad:readback:drain", 4.5, 7.0, 9, 3, inflight=1, **q1),
            span("dryad:readback:fetch_wait", 7.0, 7.5, 10, 3, **q1),
            span("dryad:readback:fetch_copy", 7.5, 9.0, 11, 3, bytes=1800,
                 capacity=200, columns=2, **q1),
            span("dryad:decode:decode", 9.0, 10.0, 12, 3, rows=100, capacity=200, **q1),
            span("dryad:other:collect", 10.0, 18.0, 13, **q2),
            span("dryad:plan:lower", 10.0, 10.1, 14, 13, **q2),
            span("dryad:dispatch:input+order_by", 10.1, 10.5, 15, 13, stage=1, **q2),
            span("dryad:readback:drain", 10.5, 14.0, 16, 13, inflight=1, **q2),
            span("dryad:readback:fetch_wait", 14.0, 14.5, 17, 13, **q2),
            span("dryad:readback:fetch_copy", 14.5, 16.0, 18, 13, bytes=1800,
                 capacity=200, columns=2, **q2),
            span("dryad:decode:decode", 16.0, 17.5, 19, 13, rows=100, capacity=200, **q2),
            # before the window opened (the warm pair): not of the window
            span("dryad:other:collect", -5.0, -1.0, 20),
        ]

    def op(path, start, end):
        stats = {"hlo_category": "fusion"}
        if path is not None:
            stats["tf_op"] = (SCOPE + path if scopes
                              else SCOPE + path.rsplit("/", 1)[-1])
        return ("%fusion = f32[8]{0} fusion()", start, end, stats)

    layout = "dryad.exchange_range/dryad.exchange.layout/sort:"
    ops = [
        op(layout, 4.5, 6.0),
        op("dryad.local_sort/while:", 6.0, 7.0),
        op("dryad.local_sort/dryad.sort.carry/while/body/gather:", 6.2, 6.8),
        op("convert_element_type:", 7.0, 7.25),
        op(None, 7.25, 7.5),  # a copy: no tf_op at all
        op(layout, 10.5, 12.0),
        op("dryad.exchange_range/dryad.exchange.collective/all_to_all:", 12.0, 13.0),
        op("psum:", 13.0, 14.0),
    ]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit_fn", 4.5, 7.5, {})]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": host},
            {"name": "pjrt", "events": [("other:event", 1.0, 2.0, {})]}]},
    ]


def test_scope_of():
    assert PS.scope_of(
        "jit(dryad_stage)/shard_map/dryad.exchange_hash/dryad.exchange.layout/sort:"
    ) == "dryad.exchange_hash/dryad.exchange.layout"
    assert PS.scope_of("jit(dryad_stage)/shard_map/iota:") == PS.UNSCOPED
    assert PS.scope_of("") == PS.UNSCOPED


def test_spans_are_placed_in_their_job_and_cut_to_the_window():
    s = PS.reduce(hand_planes())
    assert s.jobs == {"bench:fresh": [(0.0, 10.0)], "bench:requery": [(10.0, 18.0)]}
    assert len(s.spans) == 19  # the warm pair's collect lies outside
    fresh, = s.of_job("bench:fresh")
    requery, = s.of_job("bench:requery")
    assert len(fresh) == 12 and len(requery) == 7
    # bind-time spans have no parent and no qid: placed by time alone
    assert [x.name for x in fresh[:2]] == [
        "dryad:ingest:tokenize", "dryad:ingest:vocab"]
    assert all(x.job == ("bench:requery", 0) for x in requery)


def test_self_time_is_duration_minus_children():
    s = PS.reduce(hand_planes())
    fresh, = s.of_job("bench:fresh")
    self_s = {x.name: x.self_s for x in fresh}
    assert self_s["dryad:other:collect"] == pytest.approx(0.0)
    assert self_s["dryad:ingest:bind"] == pytest.approx(0.0)  # encode + h2d
    assert self_s["dryad:ingest:encode"] == pytest.approx(0.9)
    assert self_s["dryad:readback:drain"] == pytest.approx(2.5)
    requery, = s.of_job("bench:requery")
    assert {x.name: x.self_s for x in requery}[
        "dryad:other:collect"] == pytest.approx(0.5)  # 17.5-18


def test_busy_by_scope_takes_a_loops_body_out_of_the_loop():
    s = PS.reduce(hand_planes())
    assert s.busy_s == pytest.approx(6.5)
    assert s.scope_s == pytest.approx({
        "dryad.exchange_range/dryad.exchange.layout": 3.0,
        "dryad.exchange_range/dryad.exchange.collective": 1.0,
        "dryad.local_sort": 0.4,
        "dryad.local_sort/dryad.sort.carry": 0.6,
        PS.UNSCOPED: 1.5,
    })
    assert sum(s.scope_s.values()) == pytest.approx(s.busy_s)
    assert PS.under(s, "dryad.exchange") == pytest.approx(100 * 4.0 / 6.5)
    assert PS.under(s, "dryad.sort.carry") == pytest.approx(100 * 0.6 / 6.5)
    assert PS.under(s, "dryad.") == pytest.approx(100 * 5.0 / 6.5)
    assert PS.under(s, "dryad.string_code") == 0.0


def test_idle_goes_to_the_innermost_span_open():
    s = PS.reduce(hand_planes())
    assert s.idle_s == pytest.approx(13.5)
    assert s.idle_by_span == pytest.approx({
        "dryad:ingest:tokenize": 1.0, "dryad:ingest:vocab": 0.5,
        "dryad:plan:lower": 0.2, "dryad:ingest:encode": 0.9,
        "dryad:ingest:h2d": 1.0, "dryad:dispatch:input+order_by": 0.9,
        "dryad:readback:fetch_wait": 0.5, "dryad:readback:fetch_copy": 3.0,
        "dryad:decode:decode": 2.5, "dryad:other:collect": 0.5,
        PS.UNNAMED: 2.5,  # 1.5-2 between bind and collect, 18-20
    })
    assert sum(s.idle_by_span.values()) == pytest.approx(s.idle_s)


def test_two_chips_are_averaged():
    planes = hand_planes()
    second = {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [
        ("%f", 4.5, 6.5, {"tf_op": SCOPE + "dryad.local_sort/sort:"})]}]}
    s = PS.reduce(planes + [second])
    assert s.busy_s == pytest.approx((6.5 + 2.0) / 2)
    assert s.scope_s["dryad.local_sort"] == pytest.approx((0.4 + 2.0) / 2)
    assert s.idle_s == pytest.approx((13.5 + 18.0) / 2)
    assert sum(s.idle_by_span.values()) == pytest.approx(s.idle_s)


def test_a_program_without_spans_or_scopes_gives_none_not_zero():
    s = PS.reduce(hand_planes(spans=False, scopes=False))
    assert s.spans == [] and s.scope_s is None and s.idle_by_span is None
    assert s.busy_s == pytest.approx(6.5) and s.idle_s == pytest.approx(13.5)
    assert PS.under(s, "dryad.") is None
    assert PS.median_over_jobs(s, "bench:fresh", lambda job: 1.0) is None
    # spans without scopes (a program from a cache written before them)
    s = PS.reduce(hand_planes(scopes=False))
    assert s.scope_s is None and s.idle_by_span is not None
    assert PS.under(s, "dryad.exchange") is None
    with pytest.raises(ValueError, match="bench:window"):
        PS.reduce([{"name": "/host:CPU", "lines": []}])


# -- the metric files --------------------------------------------------------

NEW = ("ingest_encode_s", "ingest_bytes_per_s", "dispatch_s", "decode_s",
       "d2h_bytes_a_row", "exchange_dev_share", "string_code_dev_share",
       "scoped_dev_share", "idle_named_share")


def read_all(monkeypatch, summary, trace=True):
    monkeypatch.setattr(PS, "of", lambda cell, metric_file: summary)
    cell = run.load_cell("sort-1c")
    return {name: run.load_module("metrics", name).read(
        {"busy_s": 1.0} if trace else None, {"pairs": []}, {}, cell)
        for name in NEW}


def test_each_metrics_arithmetic(monkeypatch):
    got = read_all(monkeypatch, PS.reduce(hand_planes()))
    assert got["ingest_encode_s"] == pytest.approx(1.0 + 0.5 + 0.9)
    assert got["ingest_bytes_per_s"] == pytest.approx(1000 / 4.0)  # 0 -> 4 s
    assert got["dispatch_s"] == pytest.approx(0.5)  # 10 -> 10.5
    assert got["decode_s"] == pytest.approx(1.5)
    assert got["d2h_bytes_a_row"] == pytest.approx(18.0)
    assert got["exchange_dev_share"] == pytest.approx(100 * 4.0 / 6.5)
    assert got["string_code_dev_share"] == 0.0
    assert got["scoped_dev_share"] == pytest.approx(100 * 5.0 / 6.5)
    assert got["idle_named_share"] == pytest.approx(100 * 11.0 / 13.5)


def test_the_median_is_over_jobs(monkeypatch):
    planes = hand_planes()
    host = planes[1]["lines"][0]["events"]
    host[0] = ("bench:window", 0.0, 40.0, {})
    host += [
        ("bench:requery", 20.0, 30.0, {}),
        span("dryad:other:collect", 20.0, 30.0, 30),
        span("dryad:dispatch:input+order_by", 20.5, 21.5, 31, 30),
        span("dryad:readback:fetch_copy", 22.0, 23.0, 32, 30, bytes=4000),
        span("dryad:decode:decode", 23.0, 26.5, 33, 30, rows=100, capacity=400),
        ("bench:requery", 30.0, 40.0, {}),
        span("dryad:other:collect", 30.0, 40.0, 40),
        span("dryad:dispatch:input+order_by", 30.0, 30.7, 41, 40),
        span("dryad:readback:fetch_copy", 32.0, 33.0, 42, 40, bytes=2000),
        span("dryad:decode:decode", 33.0, 35.0, 43, 40, rows=100, capacity=200),
    ]
    got = read_all(monkeypatch, PS.reduce(planes))
    assert got["decode_s"] == pytest.approx(2.0)  # of 1.5, 3.5, 2.0
    assert got["dispatch_s"] == pytest.approx(0.7)  # of 0.5, 1.5, 0.7
    assert got["d2h_bytes_a_row"] == pytest.approx(20.0)  # of 18, 40, 20


def test_readers_return_nothing_where_nothing_is_to_read(monkeypatch):
    for name, value in read_all(
            monkeypatch, PS.reduce(hand_planes(spans=False, scopes=False))).items():
        assert value is None, name
    for name, value in read_all(monkeypatch, None).items():  # no xplane
        assert value is None, name
    for name, value in read_all(
            monkeypatch, PS.reduce(hand_planes()), trace=False).items():
        assert value is None, name  # an untraced run
    got = read_all(monkeypatch, PS.reduce(hand_planes(scopes=False)))
    assert [n for n, v in got.items() if v is None] == [
        "exchange_dev_share", "string_code_dev_share", "scoped_dev_share"]


def test_the_summary_is_found_where_run_py_wrote_the_trace(tmp_path, monkeypatch, capsys):
    metric = tmp_path / "benchmarks" / "metrics" / "decode_s.py"
    cell = run.load_cell("sort-1c")
    PS._of_trace.cache_clear()
    assert PS.of(cell, str(metric)) is None  # no trace there
    PS._of_trace.cache_clear()
    trace = tmp_path / ".bench_out" / "trace-sort-1c" / "plugins" / "profile" / "t0"
    trace.mkdir(parents=True)
    (trace / "host.xplane.pb").write_bytes(b"")
    import xplane

    seen = []
    monkeypatch.setattr(xplane, "read",
                        lambda path: seen.append(path) or hand_planes())
    first = PS.of(cell, str(metric))
    assert PS.of(cell, str(metric)) is first and len(seen) == 1  # read once
    assert seen[0] == str(trace / "host.xplane.pb")
    PS._of_trace.cache_clear()
    lines = capsys.readouterr().out.splitlines()
    kinds = [" ".join(ln.split()[:2]) for ln in lines]
    assert kinds == ["[bench] spans", "[bench] spans", "[bench] scopes",
                     "[bench] idle_by_span"]
    assert "kind=bench:fresh jobs=1" in lines[0]
    assert "dryad:ingest:encode=0.900000" in lines[0]
    assert "capacity_over_rows=2.0000" in lines[1]
    assert "dryad.exchange_range/dryad.exchange.layout=46.154%" in lines[2]
    assert "idle_s=13.500000" in lines[3] and "(no span)=2.500000" in lines[3]


# -- one traced run on the CPU, the new readers listed for its cell ----------

def test_a_traced_cpu_run_reads_the_programs_spans(tmp_path, monkeypatch, capsys):
    """A temp copy with a tiny sort cell for which the new metrics are
    listed.  The CPU backend has no device plane, so XLA:CPU's thunks
    play chip 0 for ``trace_reduce`` (as in ``test_run_cpu``) and the
    scope shares find nothing to read; the span readers read the real
    program's real spans."""
    import importlib.util
    import shutil

    import jax

    from conftest import BENCH, ROOT
    from test_run_cpu import cpu_trace_loader

    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(f"{ROOT}/BENCHMARK.json") as fh:
        bench = json.load(fh)
    (tmp_path / "benchmarks" / "configs" / "tiny-1c.json").write_text(
        json.dumps({"name": "tiny-1c", "chips": 1, "reduced": []}))
    (tmp_path / "benchmarks" / "traffic" / "sort-tiny.json").write_text(
        json.dumps({"job": "sort", "rows": 4096, "pool": 2}))
    bench["configs"].append({
        "name": "tiny-1c", "source": "a throwaway of the CPU test",
        "file": "benchmarks/configs/tiny-1c.json", "reduced": [], "why": "test"})
    bench["workloads"].append({
        "name": "sort-tiny-1c", "config": "tiny-1c", "traffic": "sort-tiny",
        "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in NEW + ("egress_s",):
            m["workloads"].append("sort-tiny-1c")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = importlib.util.spec_from_file_location(
        "bench_copy_run_24", tmp_path / "benchmarks" / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setattr(module, "require_chips", lambda chips: jax.devices())
    monkeypatch.setattr(module, "load_peaks", lambda kind: {"hbm_bytes_per_s": 50e9})
    monkeypatch.setattr(TR, "load", cpu_trace_loader)
    PS._of_trace.cache_clear()
    capsys.readouterr()
    rc = module.main(["--workload", "sort-tiny-1c", "--seed", "3000000019",
                      "--seconds", "0.3", "--trace", "1"])
    PS._of_trace.cache_clear()
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is True
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # no device plane on the CPU: nothing carries a scope, nothing idles
    assert not {"exchange_dev_share", "scoped_dev_share",
                "string_code_dev_share", "idle_named_share"} & set(metrics)
    for name in ("ingest_encode_s", "ingest_bytes_per_s", "dispatch_s",
                 "decode_s", "d2h_bytes_a_row"):
        assert metrics[name] > 0, name
    # 4096 rows come back in 8192 slots of int32 + f32 + a validity byte
    assert metrics["d2h_bytes_a_row"] == pytest.approx(18.0)
    said = dict(item.split("=", 1) for item in next(
        ln for ln in lines if ln.startswith("[bench] window ")).split()[2:])
    assert int(said["d2h_bytes_a_job"]) == 18 * 4096
    pairs = [ln for ln in lines if ln.startswith("[bench] pair ")]
    spans = [ln for ln in lines if ln.startswith("[bench] spans ")]
    assert len(spans) == 2 and f"jobs={len(pairs) - 1}" in spans[0]
    assert "dryad:readback:fetch_copy=" in spans[1]
    assert "capacity_over_rows=2.0000" in spans[1]
    assert any(ln == "[bench] scopes none" for ln in lines)
    # the inside view fits inside the outside view
    assert metrics["ingest_encode_s"] <= metrics["ingest_s"] + 2e-3
    assert metrics["decode_s"] <= metrics["egress_s"] + 2e-3
