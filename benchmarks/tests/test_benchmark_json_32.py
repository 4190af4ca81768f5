"""``BENCHMARK.json`` as PR 32 leaves it (the configuration
``gensort-100b-1c``, its cell ``sort-100b-1c`` on one chip, four
per-layer metrics), the job's reference, compare and control with NumPy
alone, the four readers' arithmetic on hand-built planes (the change's
spans and scopes, the parent's without them, no trace), and one traced
CPU run of a tiny cell of the same shape.  Everything is written as "at
least these", as ``test_benchmark_json_30.py`` is."""

import inspect
import json
import os

import numpy as np
import pytest

import program_spans as PS
import run
import trace_reduce as TR
from conftest import BENCH, ROOT
from test_benchmark_json import NAME, SOURCES, UNIT, bench, line
from test_program_spans import SCOPE, span

CELLS_30 = ["sort-1c", "wordcount-1c", "groupby-4c", "join-topk-1c", "sort-4c"]
PER_LAYER_32 = {
    # name: (unit, better, source, layer, moves)
    "bytes_pack_s": ("s", "lower", "program_span", "Host ingest", "fresh_job_s"),
    "bytes_unpack_s": ("s", "lower", "program_span", "Egress", "requery_s"),
    "sort_carry_dev_share": ("%", "lower", "device_trace", "Kernels", "requery_s"),
    "payload_move_dev_share": ("%", "lower", "device_trace", "Kernels", "requery_s"),
}


def test_the_configuration_and_the_cell():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    # what was there stays first and as it was; new entries at the end
    assert [w["name"] for w in b["workloads"]][:5] == CELLS_30
    assert len(b["workloads"]) <= 24 and len(configs) <= 24
    assert len({c["source"] for c in b["configs"]}) == len(b["configs"])
    assert len({c["file"] for c in b["configs"]}) == len(b["configs"])
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    assert {w["config"] for w in cells.values()} == set(configs)

    entry = configs["gensort-100b-1c"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert line(entry["source"]) and line(entry["why"])
    assert entry["reduced"] == ["rows"]
    for words in ("sortbenchmark.org", "gensort", "valsort", "100-byte records",
                  "10-byte binary key", "memcmp", "90-byte payload", "10^10"):
        assert words in entry["source"], words
    # the published share, the rows held and that the widths are not cut
    for words in ("39,062,500", "2^2", "not cut"):
        assert words in entry["why"], words
    assert entry["file"] == "benchmarks/configs/gensort-100b-1c.json"
    with open(os.path.join(ROOT, entry["file"])) as fh:
        body = json.load(fh)
    assert body["name"] == entry["name"] and body["source"] == entry["source"]
    assert body["reduced"] == entry["reduced"] and set(body["reduced_why"]) == {"rows"}
    assert "39,062,500" in body["reduced_why"]["rows"]
    assert body["chips"] == 1 and body["partitions"] == 1 and body["mesh"] == {"p": 1}
    assert (body["record_bytes"], body["key_bytes"], body["payload_bytes"]) == (100, 10, 90)
    assert body["published_rows_a_chip"] == 39062500
    assert {"keys", "payload", "mix", "pool"} <= set(body["assumed"])
    said = " ".join(body["guarantees"])
    for words in ("memcmp order over all 10 bytes", "accounted for", "all 90 payload bytes",
                  "duplicates included", "deterministic"):
        assert words in said, words

    cell = cells["sort-100b-1c"]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and line(cell["why"])
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "gensort-100b-1c", "sort_100b", 1)
    loaded = run.load_cell("sort-100b-1c")
    assert loaded.chips == 1 and loaded.config["chips"] == 1
    params = loaded.params
    assert params["job"] == "sort_100b" and params["pool"] == 2
    assert params["rows"] in (2**23, 2**22) and "rows_chosen" in params
    assert params["rows"] == body["rows"]
    assert loaded.pair_rows == 2 * params["rows"]
    # the published record, read once and written once
    assert loaded.job.min_bytes(params) == 200 * params["rows"]


def test_the_new_metrics():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert [e2e[n]["bound"] for n in ("fresh_job_s", "requery_s", "setup_s")] == [
        0.025, 0.02, 0.25]
    assert b["run_seconds"] == 48
    names = [m["name"] for m in b["per_layer"]]
    assert len(set(names)) == len(names) <= 128 and set(PER_LAYER_32) <= set(names)
    layers = {m["layer"] for m in b["per_layer"] if m["name"] not in PER_LAYER_32}
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name, (unit, better, source, layer, moves) in PER_LAYER_32.items():
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert NAME.match(name) and UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            unit, better, source, layer, moves)
        assert m["layer"] in layers
        assert "sort-100b-1c" in m["workloads"] and set(m["workloads"]) <= cells
        assert os.path.exists(os.path.join(run.HERE, "metrics", name + ".py"))
        fn = run.load_module("metrics", name).read
        assert list(inspect.signature(fn).parameters) == [
            "trace", "spans", "counters", "cell"]
    # the cell reports the three end-to-end metrics and every accepted
    # metric that lists no cells (hbm_floor_share among them)
    mine = [m for m in b["per_layer"] if "sort-100b-1c" in m.get("workloads", cells)]
    assert {m["name"] for m in b["per_layer"] if "workloads" not in m} <= {
        m["name"] for m in mine}
    assert "hbm_floor_share" in {m["name"] for m in mine}
    assert {"Host ingest", "Executor", "Kernels", "Egress", "Device"} <= {
        m["layer"] for m in mine}
    cell = run.load_cell("sort-100b-1c")
    assert {m["name"] for m in cell.end_to_end} == set(e2e)
    assert {m["name"] for m in cell.per_layer} == {m["name"] for m in mine}


def test_a_full_check_still_fits():
    b = bench()
    n = len(b["workloads"])
    assert (2 + 14 * n) * (b["run_seconds"] + 60) + n * 180 + 1200 <= 43200


# -- the job with NumPy alone -----------------------------------------------------

def test_the_reference_passes_and_the_control_and_a_swapped_word_fail():
    job = run.load_module("jobs", "sort_100b")
    params = {"rows": 2**12}
    made = job.make_table(np.random.default_rng([32, 0]), params, None, 0)
    assert made["arrays"]["key"].shape == (2**12, 10)
    assert made["arrays"]["payload"].shape == (2**12, 90)
    # keys that tie on their first word, as some 8,000 pairs do at 2^23
    key = made["arrays"]["key"].copy()
    key[:, :4] = key[np.random.default_rng(1).integers(0, 32, 2**12), :4]
    table = job.table_of(key)
    want = table["want_key"]
    assert list(map(bytes, want)) == sorted(map(bytes, key))
    reference = {"key": want, "payload": job.key_payload(want)}
    checks = job.compare(table, reference, params)
    assert set(checks) == {"sort100b.rows_missing", "sort100b.keys_out_of_order",
                           "sort100b.payloads_off_key"}
    assert all(value == 0 and limit == 0 for value, limit in checks.values())
    control = job.compare(table, job.control(table, params), params)
    assert control["sort100b.keys_out_of_order"][0] > 0
    assert control["sort100b.payloads_off_key"][0] == 0
    swapped = {"key": want, "payload": job.key_payload(want)}
    a, b = swapped["payload"][5, 4:8].copy(), swapped["payload"][5, 40:44].copy()
    swapped["payload"][5, 4:8], swapped["payload"][5, 40:44] = b, a
    assert job.compare(table, swapped, params)["sort100b.payloads_off_key"] == (1, 0)
    short = {"key": want[:-3], "payload": swapped["payload"][:-3]}
    assert job.compare(table, short, params) == {"sort100b.rows_missing": (3, 0)}


# -- the four readers on planes counted by hand ----------------------------------

def wide_planes(spans=True, payload_apart=False, scopes=True):
    """One chip, a 20 s window: a fresh job 0-10 (``encode`` 0.5-2.5
    with ``pack`` spans of 0.25 and 1.0 s inside, busy 3-8) and a
    requery 10-18 (busy 10.5-15.5; ``decode`` 16-18 with ``unpack``
    spans of 0.2 and 1.3 s).  A job's device time: the three carried
    sorts 3.5 s, of which, with ``payload_apart``, 1.5 s are gathers
    under ``dryad.sort.payload``; the layout's scatters 1.5 s.
    ``spans=False``: a program without the new spans and the stat;
    ``scopes=False``: a program cached before any scope."""
    new = spans
    host = [
        ("bench:window", 0.0, 20.0, {}),
        ("bench:fresh", 0.0, 10.0, {}),
        ("bench:requery", 10.0, 18.0, {}),
        span("dryad:other:collect", 0.0, 10.0, 1),
        span("dryad:ingest:encode", 0.5, 2.5, 2, 1, rows=1000),
        span("dryad:dispatch:input+order_by", 2.6, 3.0, 5, 1, stage=0, boost=1,
             **({"row_words": 26} if new else {})),
        span("dryad:decode:decode", 8.0, 10.0, 6, 1, rows=1000, capacity=2000),
        span("dryad:other:collect", 10.0, 18.0, 9),
        span("dryad:dispatch:input+order_by", 10.1, 10.5, 10, 9, stage=1, boost=1,
             **({"row_words": 26} if new else {})),
        span("dryad:decode:decode", 16.0, 18.0, 11, 9, rows=1000, capacity=2000),
    ]
    if new:
        host += [
            span("dryad:ingest:pack", 0.5, 0.75, 3, 2, bytes=10000, rows=1000),
            span("dryad:ingest:pack", 0.75, 1.75, 4, 2, bytes=90000, rows=1000),
            span("dryad:decode:unpack", 8.0, 8.2, 7, 6, bytes=10000, rows=1000),
            span("dryad:decode:unpack", 8.2, 9.4, 8, 6, bytes=90000, rows=1000),
            span("dryad:decode:unpack", 16.0, 16.2, 12, 11, bytes=10000, rows=1000),
            span("dryad:decode:unpack", 16.2, 17.5, 13, 11, bytes=90000, rows=1000),
        ]

    def op(path, start, end):
        if not scopes:
            path = path.rsplit("/", 1)[-1]
        return ("%fusion = u32[8]{0} fusion()", start, end,
                {"hlo_category": "fusion", "tf_op": SCOPE + path})

    def job(t):
        xr, carry = "dryad.exchange_range/", "dryad.sort.carry/"
        moved = carry + "dryad.sort.payload/gather:" if payload_apart else carry + "sort:"
        return [
            op(xr + "dryad.exchange.layout/" + carry + "sort:", t, t + 0.5),
            op(xr + "dryad.exchange.layout/" + moved, t + 0.5, t + 1.0),
            op(xr + "dryad.exchange.layout/scatter:", t + 1.0, t + 2.5),
            op("dryad.resize/" + carry + "sort:", t + 2.5, t + 3.25),
            op("dryad.resize/" + moved, t + 3.25, t + 3.75),
            op("dryad.local_sort/" + carry + "sort:", t + 3.75, t + 4.5),
            op("dryad.local_sort/" + moved, t + 4.5, t + 5.0),
        ]

    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": job(3.0) + job(10.5)}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]


def read_new(monkeypatch, summary, trace=True):
    monkeypatch.setattr(PS, "of", lambda cell, metric_file: summary)
    cell = run.load_cell("sort-100b-1c")
    return {name: run.load_module("metrics", name).read(
        {} if trace else None, {"pairs": []}, {}, cell) for name in sorted(PER_LAYER_32)}


def test_the_readers_arithmetic(monkeypatch):
    # the payload rides: no operation under dryad.sort.payload
    got = read_new(monkeypatch, PS.reduce(wide_planes()))
    assert got["bytes_pack_s"] == pytest.approx(0.25 + 1.0)
    assert got["bytes_unpack_s"] == pytest.approx(0.2 + 1.3)  # the requery's
    assert got["sort_carry_dev_share"] == pytest.approx(100 * 3.5 / 5.0)
    assert got["payload_move_dev_share"] is None  # stated None, never 0
    # the payload moves apart: its gathers are inside the carry's scope
    got = read_new(monkeypatch, PS.reduce(wide_planes(payload_apart=True)))
    assert got["sort_carry_dev_share"] == pytest.approx(100 * 3.5 / 5.0)
    assert got["payload_move_dev_share"] == pytest.approx(100 * 1.5 / 5.0)


def test_the_parent_and_a_stale_cache_give_nothing_not_zero(monkeypatch):
    # a program without the spans: its scopes are there (PR 24)
    got = read_new(monkeypatch, PS.reduce(wide_planes(spans=False)))
    assert got["bytes_pack_s"] is None and got["bytes_unpack_s"] is None
    assert got["sort_carry_dev_share"] == pytest.approx(70.0)
    assert got["payload_move_dev_share"] is None
    # a program cached before any scope
    got = read_new(monkeypatch, PS.reduce(wide_planes(scopes=False)))
    assert got["sort_carry_dev_share"] is None and got["payload_move_dev_share"] is None
    assert got["bytes_pack_s"] == pytest.approx(1.25)
    # no xplane; an untraced run
    for summary, trace in ((None, True), (PS.reduce(wide_planes()), False)):
        got = read_new(monkeypatch, summary, trace)
        assert all(v is None for v in got.values()), got


# -- one traced run on the CPU of a cell of the same shape -------------------------

def test_a_traced_cpu_run_of_the_100_byte_sort(tmp_path, monkeypatch, capsys):
    """A temp copy with a tiny one-device cell of the new configuration's
    shape for which the four metrics are listed.  The CPU backend has no
    device plane, so the two device shares find nothing to read and are
    left out; the two span readers read the real program's real spans."""
    import importlib.util
    import shutil

    import jax

    from test_run_cpu import cpu_trace_loader

    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(f"{ROOT}/BENCHMARK.json") as fh:
        b = json.load(fh)
    (tmp_path / "benchmarks" / "configs" / "tiny-100b.json").write_text(
        json.dumps({"name": "tiny-100b", "chips": 1, "reduced": ["rows"]}))
    (tmp_path / "benchmarks" / "traffic" / "sort-100b-tiny.json").write_text(
        json.dumps({"job": "sort_100b", "rows": 4096, "pool": 2}))
    b["configs"].append({
        "name": "tiny-100b", "source": "a throwaway of the CPU test",
        "file": "benchmarks/configs/tiny-100b.json", "reduced": ["rows"], "why": "test"})
    b["workloads"].append({
        "name": "sort-100b-tiny", "config": "tiny-100b", "traffic": "sort-100b-tiny",
        "chips": 1, "why": "test"})
    for m in b["per_layer"]:
        if m["name"] in PER_LAYER_32:
            m["workloads"].append("sort-100b-tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    spec = importlib.util.spec_from_file_location(
        "bench_copy_run_32", tmp_path / "benchmarks" / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setattr(module, "require_chips", lambda chips: jax.devices())
    monkeypatch.setattr(module, "load_peaks", lambda kind: {"hbm_bytes_per_s": 50e9})
    monkeypatch.setattr(TR, "load", cpu_trace_loader)
    PS._of_trace.cache_clear()
    capsys.readouterr()
    rc = module.main(["--workload", "sort-100b-tiny", "--seed", "3200000019",
                      "--seconds", "0.3", "--trace", "1"])
    PS._of_trace.cache_clear()
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert not {"sort_carry_dev_share", "payload_move_dev_share"} & set(metrics)
    assert 0 < metrics["bytes_pack_s"] < 1 and 0 < metrics["bytes_unpack_s"] < 1
    assert metrics["window_compiles"] == 0
    for number in ("sort100b.rows_missing", "sort100b.keys_out_of_order",
                   "sort100b.payloads_off_key"):
        assert any(ln.startswith(f"[bench] check number={number} worst=0 limit=0")
                   for ln in lines), number
    fresh = [ln for ln in lines if ln.startswith("[bench] spans kind=bench:fresh")]
    assert "dryad:ingest:pack=" in fresh[0] and "dryad:ingest:encode=" in fresh[0]
    requery = [ln for ln in lines if ln.startswith("[bench] spans kind=bench:requery")]
    assert "dryad:decode:unpack=" in requery[0]
    # the table stays resident: a requery has no ingest span
    assert "dryad:ingest:" not in requery[0]
    assert "dryad:dispatch:input+order_by=" in requery[0]
