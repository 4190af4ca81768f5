"""``BENCHMARK.json`` as PR 34 leaves it (seven per-layer metrics that read
what the host passes say of themselves, in all six cells or in the four
that copy a table back), ``host_pass.py``'s arithmetic on hand-built
planes (the change's spans with their accounts, the parent's without,
no trace), and one traced CPU run of a tiny sort cell that reads the
bytes a row to the byte.  Everything is written as "at least these", as
``test_benchmark_json_32.py`` is."""

import inspect
import json
import os

import pytest

import host_pass as HP
import program_spans as PS
import run
import trace_reduce as TR
from conftest import BENCH, ROOT
from test_benchmark_json import NAME, SOURCES, UNIT, bench
from test_program_spans import span

ALL = ["sort-1c", "wordcount-1c", "groupby-4c", "join-topk-1c", "sort-4c",
       "sort-100b-1c"]
BACK = ["sort-1c", "groupby-4c", "sort-4c", "sort-100b-1c"]
PER_LAYER_34 = {
    # name: (unit, better, layer, moves, cells)
    "ingest_host_bytes_a_row": ("bytes", "lower", "Host ingest", "fresh_job_s", ALL),
    "encode_pad_s": ("s", "lower", "Host ingest", "fresh_job_s", ALL),
    "egress_host_bytes_a_row": ("bytes", "lower", "Egress", "requery_s", BACK),
    "fetch_copy_bytes_per_s": ("bytes/s", "higher", "Egress", "requery_s", BACK),
    "decode_bytes_per_s": ("bytes/s", "higher", "Egress", "requery_s", BACK),
    "collect_self_s": ("s", "lower", "API / planner", "fresh_job_s", ALL),
    "obs_sample_s": ("s", "lower", "Device", "fresh_job_s", ALL),
}


def test_the_seven_metrics():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert [e2e[n]["bound"] for n in ("fresh_job_s", "requery_s", "setup_s")] == [
        0.025, 0.02, 0.25]
    assert b["run_seconds"] == 48 and [w["name"] for w in b["workloads"]][:6] == ALL
    names = [m["name"] for m in b["per_layer"]]
    assert len(set(names)) == len(names) <= 128 and set(PER_LAYER_34) <= set(names)
    # new entries at the end of the list, after everything PR 32 left
    assert names.index("payload_move_dev_share") < min(
        names.index(n) for n in PER_LAYER_34)
    layers = {m["layer"] for m in b["per_layer"] if m["name"] not in PER_LAYER_34}
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name, (unit, better, layer, moves, listed) in PER_LAYER_34.items():
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert NAME.match(name) and UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            unit, better, "program_span", layer, moves)
        assert m["layer"] in layers and moves in e2e
        assert set(listed) <= set(m["workloads"]) <= cells
        assert os.path.exists(os.path.join(run.HERE, "metrics", name + ".py"))
        fn = run.load_module("metrics", name).read
        assert list(inspect.signature(fn).parameters) == [
            "trace", "spans", "counters", "cell"]
    # the three cells that read no host metric before now read these
    for cell in ("join-topk-1c", "sort-4c", "sort-100b-1c"):
        mine = {m["name"] for m in run.load_cell(cell).per_layer}
        assert {"ingest_host_bytes_a_row", "encode_pad_s", "collect_self_s",
                "obs_sample_s"} <= mine
    # a top 20 and 1,352 B copied back: no egress metric
    for cell in ("wordcount-1c", "join-topk-1c"):
        mine = {m["name"] for m in run.load_cell(cell).per_layer}
        assert not {"egress_host_bytes_a_row", "decode_bytes_per_s"} & mine


# -- the arithmetic on planes counted by hand -------------------------------------

def acct(user_s, sys_s, **stats):
    return dict(user_s=user_s, sys_s=sys_s, **stats)


def host_planes(accounts=True, text=False):
    """A 20 s window of one pair.  The fresh job 0-10: a schema
    ``encode`` 1-3 (800 B of physical columns, of which a ``pack`` inside
    it made 300), a pad ``encode`` 3-5 (900 B for 100 rows), a telemetry
    sample of 0.25 s inside ``drain``, ``fetch_copy`` 7-7.5, ``decode``
    8-9.5 with an ``unpack`` inside; ``collect`` covers 0.5-10 and its
    children leave 1.5 s of it.  The requery 10-18: ``fetch_copy`` 14-15
    (900 B), ``decode`` 15-17 (800 B for 100 rows, an ``unpack`` of 300
    B inside it).  ``text``: ``tokenize`` and ``vocab`` before the job's
    ``collect`` and no schema pass.  ``accounts=False``: the same spans
    as the parent of PR 34 writes them (no account, no ``bytes_out``, no
    sample's span)."""
    def a(*args, **stats):
        return acct(*args, **stats) if accounts else {
            k: v for k, v in stats.items() if k != "bytes_out"}

    host = [
        ("bench:window", 0.0, 20.0, {}),
        ("bench:fresh", 0.0, 10.0, {}),
        ("bench:requery", 10.0, 18.0, {}),
        span("dryad:other:collect", 0.5, 10.0, 1),
        span("dryad:ingest:bind", 1.0, 5.5, 2, 1, node=3),
        span("dryad:ingest:encode", 3.0, 5.0, 5, 2,
             **a(0.5, 1.5, rows=100, capacity=100, bytes_out=900)),
        span("dryad:ingest:h2d", 5.0, 5.5, 6, 2, bytes=900),
        span("dryad:readback:drain", 5.5, 7.0, 7, 1, inflight=1),
        span("dryad:readback:fetch_copy", 7.0, 7.5, 9, 1,
             **a(0.1, 0.3, bytes=900, capacity=100)),
        span("dryad:decode:decode", 8.0, 9.5, 10, 1,
             **a(0.2, 1.2, rows=100, capacity=100, bytes_out=800)),
        span("dryad:decode:unpack", 8.5, 9.0, 11, 10,
             **a(0.1, 0.4, rows=100, bytes=300, bytes_out=300)),
        span("dryad:other:collect", 10.0, 18.0, 12),
        span("dryad:readback:drain", 10.5, 14.0, 13, 12, inflight=1),
        span("dryad:readback:fetch_copy", 14.0, 15.0, 14, 12,
             **a(0.25, 0.5, bytes=900, capacity=100)),
        span("dryad:decode:decode", 15.0, 17.0, 15, 12,
             **a(0.25, 1.5, rows=100, capacity=100, bytes_out=800)),
        span("dryad:decode:unpack", 15.5, 16.5, 16, 15,
             **a(0.1, 0.4, rows=100, bytes=300, bytes_out=300)),
    ]
    if text:
        host += [
            span("dryad:ingest:tokenize", 0.0, 0.25, 17,
                 **a(0.2, 0.05, bytes=700, rows=100, bytes_out=1600)),
            span("dryad:ingest:vocab", 0.25, 0.5, 18,
                 **a(0.2, 0.0, rows=100, bytes_out=100)),
        ]
    else:
        host += [
            span("dryad:ingest:encode", 1.0, 3.0, 3, 2,
                 **a(1.0, 0.5, rows=100, bytes_out=800)),
            span("dryad:ingest:pack", 1.5, 2.5, 4, 3,
                 **a(0.5, 0.25, rows=100, bytes=250, bytes_out=300)),
        ]
    if accounts:
        host.append(span("dryad:other:resource_sample", 6.0, 6.25, 8, 7))
    return [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ("%fusion = u32[8]{0} fusion()", 5.5, 7.0, {"tf_op": "jit(x)/dryad.sort:"}),
            ("%fusion = u32[8]{0} fusion()", 10.5, 14.0, {"tf_op": "jit(x)/dryad.sort:"}),
        ]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]


def read_new(monkeypatch, summary, cell="sort-100b-1c", trace=True):
    monkeypatch.setattr(PS, "of", lambda cell, metric_file: summary)
    loaded = run.load_cell(cell)
    listed = {m["name"] for m in loaded.per_layer}
    return {name: run.load_module("metrics", name).read(
        {} if trace else None, {"pairs": []}, {}, loaded)
        for name in sorted(PER_LAYER_34) if name in listed}


def test_the_readers_arithmetic(monkeypatch, capsys):
    got = read_new(monkeypatch, PS.reduce(host_planes()))
    assert set(got) == set(PER_LAYER_34)
    # pack's 300 B lie inside the schema encode's 800: counted once
    assert got["ingest_host_bytes_a_row"] == pytest.approx((800 + 900) / 100)
    assert got["encode_pad_s"] == pytest.approx(2.0)
    # the requery's, and unpack's 300 B inside decode's 800 counted once
    assert got["egress_host_bytes_a_row"] == pytest.approx((900 + 800) / 100)
    assert got["fetch_copy_bytes_per_s"] == pytest.approx(900 / 1.0)
    assert got["decode_bytes_per_s"] == pytest.approx(800 / 2.0)
    # 9.5 s less bind 4.5, drain 1.5, fetch_copy 0.5, decode 1.5
    assert got["collect_self_s"] == pytest.approx(1.5)
    assert got["obs_sample_s"] == pytest.approx(0.25)
    # the sample's seconds left drain's self time
    summary = PS.reduce(host_planes())
    drain = next(s for s in summary.spans if s.name == "dryad:readback:drain")
    assert drain.self_s == pytest.approx(1.25)
    # a line a pass that states its bytes, medians over the kind's jobs
    capsys.readouterr()
    HP.report(summary)
    lines = {(f["kind"], f["span"]): f for f in (
        dict(item.split("=", 1) for item in ln.split()[2:])
        for ln in capsys.readouterr().out.splitlines())}
    assert set(lines) == {
        ("bench:fresh", "dryad:ingest:encode.schema"),
        ("bench:fresh", "dryad:ingest:encode.pad"),
        ("bench:fresh", "dryad:ingest:pack"),
        ("bench:fresh", "dryad:readback:fetch_copy"),
        ("bench:fresh", "dryad:decode:decode"),
        ("bench:fresh", "dryad:decode:unpack"),
        ("bench:requery", "dryad:readback:fetch_copy"),
        ("bench:requery", "dryad:decode:decode"),
        ("bench:requery", "dryad:decode:unpack"),
    }
    pad = lines["bench:fresh", "dryad:ingest:encode.pad"]
    assert (float(pad["s"]), float(pad["user_s"]), float(pad["sys_s"])) == (2.0, 0.5, 1.5)
    assert int(pad["bytes_out"]) == 900
    assert float(pad["GB_s"]) == pytest.approx(900 / 2.0 / 1e9, abs=1e-3)
    assert set(pad) == {"kind", "span", "jobs", "s", "user_s", "sys_s",
                        "bytes_out", "GB_s"}


def test_text_is_counted_by_its_tokens(monkeypatch):
    got = read_new(monkeypatch, PS.reduce(host_planes(text=True)), "wordcount-1c")
    assert set(got) == {"ingest_host_bytes_a_row", "encode_pad_s",
                        "collect_self_s", "obs_sample_s"}
    assert got["ingest_host_bytes_a_row"] == pytest.approx((1600 + 100 + 900) / 100)
    # no pad encode at all: the tokenizer's rows
    job = [s for s in PS.reduce(host_planes(text=True)).spans
           if not (s.name == HP.ENCODE)]
    assert HP.rows_ingested(job) == 100


def test_the_parent_and_no_trace_give_nothing_not_zero(monkeypatch):
    # the parent's spans: seconds, rows, bytes, capacity, and no account
    got = read_new(monkeypatch, PS.reduce(host_planes(accounts=False)))
    assert set(got) == set(PER_LAYER_34)
    # ``collect`` and its children are spans the parent has: its reading
    # there is the parent's own (no sample's span took 0.25 s off drain)
    assert got.pop("collect_self_s") == pytest.approx(1.5)
    assert all(v is None for v in got.values()), got
    for summary, trace in ((None, True), (PS.reduce(host_planes()), False)):
        got = read_new(monkeypatch, summary, trace=trace)
        assert all(v is None for v in got.values()), got


# -- one traced run on the CPU of a tiny sort cell --------------------------------

def test_a_traced_cpu_run_reads_the_bytes_a_row_to_the_byte(
        tmp_path, monkeypatch, capsys):
    """A temp copy with a tiny one-device sort cell for which the seven
    metrics are listed: 4,096 rows of an int32 key and an f32 payload at
    P = 1.  The schema pass makes 8 B a row, the pad 9 B a slot; the
    fetch copies 9 B a slot and ``decode`` makes 8 B a row: 17.0 and
    17.0, what ``PERF.md`` predicts of ``sort-1c``."""
    import importlib.util
    import shutil

    import jax

    from test_run_cpu import cpu_trace_loader

    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(f"{ROOT}/BENCHMARK.json") as fh:
        b = json.load(fh)
    (tmp_path / "benchmarks" / "configs" / "tiny-34.json").write_text(
        json.dumps({"name": "tiny-34", "chips": 1, "reduced": []}))
    (tmp_path / "benchmarks" / "traffic" / "sort-tiny-34.json").write_text(
        json.dumps({"job": "sort", "rows": 4096, "pool": 2}))
    b["configs"].append({
        "name": "tiny-34", "source": "a throwaway of the CPU test",
        "file": "benchmarks/configs/tiny-34.json", "reduced": [], "why": "test"})
    b["workloads"].append({
        "name": "sort-tiny-34", "config": "tiny-34", "traffic": "sort-tiny-34",
        "chips": 1, "why": "test"})
    for m in b["per_layer"]:
        if m["name"] in PER_LAYER_34:
            m["workloads"].append("sort-tiny-34")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    spec = importlib.util.spec_from_file_location(
        "bench_copy_run_34", tmp_path / "benchmarks" / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setattr(module, "require_chips", lambda chips: jax.devices()[:1])
    monkeypatch.setattr(module, "load_peaks", lambda kind: {"hbm_bytes_per_s": 50e9})
    monkeypatch.setattr(TR, "load", cpu_trace_loader)
    PS._of_trace.cache_clear()
    capsys.readouterr()
    rc = module.main(["--workload", "sort-tiny-34", "--seed", "3400000019",
                      "--seconds", "0.3", "--trace", "1"])
    PS._of_trace.cache_clear()
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["ingest_host_bytes_a_row"] == 17.0
    assert metrics["egress_host_bytes_a_row"] == 17.0
    assert 0 < metrics["encode_pad_s"] < 1
    assert metrics["fetch_copy_bytes_per_s"] > 0 and metrics["decode_bytes_per_s"] > 0
    assert 0 <= metrics["collect_self_s"] < 0.1
    # the first event of the window finds the last sample (the warm-up's)
    # over a second old or not; when it does the sample is in the trace
    assert metrics.get("obs_sample_s", 0.001) > 0
    said = [dict(item.split("=", 1) for item in ln.split()[2:])
            for ln in lines if ln.startswith("[bench] host_pass ")]
    fresh = {f["span"]: f for f in said if f["kind"] == "bench:fresh"}
    assert {"dryad:ingest:encode.schema", "dryad:ingest:encode.pad",
            "dryad:readback:fetch_copy", "dryad:decode:decode"} <= set(fresh)
    assert int(fresh["dryad:ingest:encode.schema"]["bytes_out"]) == 8 * 4096
    assert int(fresh["dryad:ingest:encode.pad"]["bytes_out"]) == 9 * 4096
    requery = {f["span"]: f for f in said if f["kind"] == "bench:requery"}
    assert set(requery) == {"dryad:readback:fetch_copy", "dryad:decode:decode"}
    assert int(requery["dryad:decode:decode"]["bytes_out"]) == 8 * 4096
