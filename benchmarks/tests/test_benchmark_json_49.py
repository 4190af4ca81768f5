"""``BENCHMARK.json`` as PR 49 leaves it (the configuration
``tpch-sf10-1c``, its cell ``tpch-q1-1c`` on one chip, four per-layer
metrics), the job file's functions, the four readers' arithmetic on
hand-built planes (a job whose dispatch states what its fold carries;
a program before PR 49; no trace) and one traced CPU run of a tiny cell
of the same shape.  Everything is written as "at least these", as
``test_benchmark_json_41.py`` is: a later PR that adds a cell or a
metric fails nothing here."""

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

PER_LAYER_49 = {
    # name: (unit, better, source, layer)
    "group_reduce_dev_share": ("%", "lower", "device_trace", "Kernels"),
    "decimal_dev_share": ("%", "lower", "device_trace", "Kernels"),
    "agg_fold_hbm_share": ("%", "higher", "device_trace", "Kernels"),
    "agg_state_words": ("count", "lower", "program_span", "Stage programs"),
}
UNLISTED = {"ingest_s", "execute_s", "window_compiles", "gather_dev_share",
            "hbm_floor_share", "device_idle_share", "mean_rows_per_s_chip"}
NUMBERS = {
    "tpch_q1.groups_wrong", "tpch_q1.count_order_differs", "tpch_q1.rows_lost",
    "tpch_q1.rows_after_cutoff", "tpch_q1.sum_qty_off_units",
    "tpch_q1.sum_base_price_off_units", "tpch_q1.sum_disc_price_off_units",
    "tpch_q1.sum_charge_off_units", "tpch_q1.avg_rel_err",
}


def test_the_configuration_and_the_cell():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    assert len(b["workloads"]) <= 24 and len(configs) <= 24
    assert len({c["source"] for c in b["configs"]}) == len(b["configs"])
    assert len({c["file"] for c in b["configs"]}) == len(b["configs"])
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    assert {w["config"] for w in cells.values()} == set(configs)

    entry = configs["tpch-sf10-1c"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert line(entry["source"]) and line(entry["why"])
    for words in ("TPC-H", "3.0.1", "4.2.3", "SF 10", "2.4.1", "Q1", "DELTA 90"):
        assert words in entry["source"], words
    assert entry["file"] == "benchmarks/configs/tpch-sf10-1c.json"
    with open(os.path.join(ROOT, entry["file"])) as fh:
        body = json.load(fh)
    assert body["name"] == entry["name"] and body["source"] == entry["source"]
    assert body["reduced"] == entry["reduced"] and set(entry["reduced"]) <= {"rows"}
    assert body["architecture"] is None  # a deployment, no catalog model
    assert body["chips"] == 1 and body["mesh"] == {"p": 1} and body["partitions"] == 1
    assert "DryadConfig() defaults" in body["engine_config"]
    if entry["reduced"]:  # the fallback: what is held of the source, stated
        assert {"lineitem", "held", "factor"} <= set(body["source_rows"])
        assert body["orders"] == body["source_rows"]["factor"] * body["source_rows"]["orders"]
    for key in ("deployment", "schema", "query", "guarantees", "assumed"):
        assert body[key], key

    cell = cells["tpch-q1-1c"]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and line(cell["why"])
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch-sf10-1c", "tpch_q1", 1)
    loaded = run.load_cell("tpch-q1-1c")
    assert loaded.chips == 1 and loaded.config["chips"] == 1
    params = loaded.params
    assert params["job"] == "tpch_q1" and params["pool"] == 2
    assert params["partitions"] == loaded.chips and params["delta_days"] == 90
    assert params["parts"] == 200_000 * loaded.config["scale_factor"]  # SF 10's
    assert params["orders"] == loaded.config["orders"]
    # the table is bound at one power of two over every seed's row count
    slots = params["slots"]
    assert slots & (slots - 1) == 0 and 4.1 * params["orders"] < slots
    assert loaded.pair_rows == 2 * 4 * params["orders"]
    assert loaded.job.min_bytes(params) == 28 * 4 * params["orders"] + 224
    assert loaded.job.fold_bytes(params) == 2 * (41 + 45) * slots


def test_the_job_files_functions():
    job = run.load_module("jobs", "tpch_q1")
    for name, args in {
        "make_table": ["rng", "params", "workdir", "index"],
        "bind": ["ctx", "table", "params"],
        "reference": ["arrays", "day"],
        "compare": ["table", "out", "params"],
        "control": ["table", "params"],
        "planted_faults": ["table", "params"],
        "input_rows": ["params"],
        "min_bytes": ["params"],
        "fold_bytes": ["params"],
    }.items():
        assert list(inspect.signature(getattr(job, name)).parameters) == args, name
    params = {"orders": 2000, "parts": 300, "slots": 1 << 14, "delta_days": 90,
              "partitions": 1}
    table = job.make_table(np.random.default_rng([49, 0]), params, None, 0)
    checks = job.compare(table, job.answer_of(table["want"]), params)
    assert set(checks) == NUMBERS
    assert all(value <= limit for value, limit in checks.values())
    assert checks["tpch_q1.avg_rel_err"][1] == job.AVG_LIMIT
    assert all(limit == 0 for name, (_, limit) in checks.items()
               if name != "tpch_q1.avg_rel_err")
    control = job.compare(table, job.control(table, params), params)
    failed = {n for n, (value, limit) in control.items() if value > limit}
    assert "tpch_q1.sum_charge_off_units" in failed
    assert "tpch_q1.count_order_differs" not in failed


def test_the_new_metrics():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert [e2e[n]["bound"] for n in ("fresh_job_s", "requery_s", "setup_s")] == [
        0.025, 0.02, 0.25]
    assert b["run_seconds"] == 48
    names = [m["name"] for m in b["per_layer"]]
    assert len(set(names)) == len(names) <= 128 and set(PER_LAYER_49) <= set(names)
    layers = {m["layer"] for m in b["per_layer"] if m["name"] not in PER_LAYER_49}
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name, (unit, better, source, layer) in PER_LAYER_49.items():
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert NAME.match(name) and UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            unit, better, source, layer, "requery_s")
        assert m["layer"] in layers
        assert "tpch-q1-1c" in m["workloads"] and set(m["workloads"]) <= cells
        fn = run.load_module("metrics", name).read
        assert list(inspect.signature(fn).parameters) == [
            "trace", "spans", "counters", "cell"]
    # no accepted entry's list of cells was edited for the new one
    for m in b["per_layer"]:
        if m["name"] not in PER_LAYER_49:
            assert "tpch-q1-1c" not in m.get("workloads", [])
    cell = run.load_cell("tpch-q1-1c")
    assert {m["name"] for m in cell.end_to_end} == set(e2e)
    mine = {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in b["per_layer"] if "workloads" not in m} <= mine
    assert UNLISTED | set(PER_LAYER_49) <= mine


def test_a_full_check_still_fits():
    b = bench()
    n = len(b["workloads"])
    assert (2 + 14 * n) * (b["run_seconds"] + 60) + n * 180 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= n // 2


# -- the four readers on planes counted by hand ----------------------------------------

def q1_planes(new=True, scopes=True):
    """One chip, a 20 s window: a fresh job 0-10 and a requery 10-18,
    each one dispatch whose span says what the widest fold carries (11
    words).  Device 5 s a job: the select's wide arithmetic 0.25 s, the
    group-by's layout 1.75 s and fold 2 s (scan 1.5, place 0.5), the
    order_by's sort 1 s.  ``new=False``: the dispatch span as the parent
    of PR 49 writes it; ``scopes=False``: a program from a cache older
    than any scope."""
    host = [
        ("bench:window", 0.0, 20.0, {}),
        ("bench:fresh", 0.0, 10.0, {}),
        ("bench:requery", 10.0, 18.0, {}),
    ]
    said = dict(group_keys=2, agg_channels=6, agg64_channels=5,
                agg_state_words=11) if new else {}

    def job(t, first_id):
        return [
            span("dryad:other:collect", t, t + 8.0, first_id),
            span("dryad:dispatch:input+where+select+group_by+order_by", t + 0.1,
                 t + 0.2, first_id + 1, first_id, boost=1, row_words=14, **said),
            span("dryad:readback:drain", t + 0.2, t + 6.0, first_id + 2, first_id),
        ]

    host += job(0.0, 1) + job(10.0, 30)

    def op(path, start, end):
        if not scopes:
            path = path.rsplit("/", 1)[-1]
        return ("%fusion = u32[8]{0} fusion()", start, end,
                {"hlo_category": "fusion", "tf_op": SCOPE + path})

    def device(t):
        fold = "dryad.group_reduce/dryad.group_reduce.fold/"
        return [
            op("dryad.select/dryad.decimal/multiply:", t, t + 0.25),
            op("dryad.group_reduce/dryad.group_reduce.layout/dryad.sort.carry/sort:",
               t + 0.25, t + 2.0),
            op(fold + "scan/while:", t + 2.0, t + 3.5),
            op(fold + "place/while:", t + 3.5, t + 4.0),
            op("dryad.local_sort/dryad.sort.carry/sort:", t + 4.0, t + 5.0),
        ]

    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": device(0.3) + device(10.3)}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]


class TinyJob:
    @staticmethod
    def fold_bytes(params):
        return 100e6  # a job


def read_new(monkeypatch, summary, trace=True, job=TinyJob):
    monkeypatch.setattr(PS, "of", lambda cell, metric_file: summary)
    cell = run.load_cell("tpch-q1-1c")
    cell.peaks, cell.job = {"hbm_bytes_per_s": 1e9}, job
    return {name: run.load_module("metrics", name).read(
        {} if trace else None, {"pairs": []}, {}, cell) for name in sorted(PER_LAYER_49)}


def test_the_readers_arithmetic(monkeypatch, capsys):
    got = read_new(monkeypatch, PS.reduce(q1_planes()))
    assert got["group_reduce_dev_share"] == pytest.approx(75.0)  # 3.75 s of 5 a job
    assert got["decimal_dev_share"] == pytest.approx(5.0)
    # two dispatches x 100 MB over 4 s of fold, of a peak of 1 GB/s
    assert got["agg_fold_hbm_share"] == pytest.approx(5.0)
    assert got["agg_state_words"] == 11.0
    assert "[bench] agg_fold dispatches=2 " in capsys.readouterr().out


def test_a_fold_cannot_read_over_its_roofline_at_the_cells_shape():
    """The folds of a job move ``fold_bytes`` at the very least; the
    scan alone is log2(slots) passes that each read and write the state,
    so at the chip's whole HBM peak every pass of one fold would take
    what the metric allows both folds together: the share is under 100 /
    log2(slots) percent by construction, whatever the device does."""
    cell = run.load_cell("tpch-q1-1c")
    slots = cell.params["slots"]
    least = cell.job.fold_bytes(cell.params)
    words_first, words_second = cell.job.STATE_WORDS
    passes = (slots - 1).bit_length()
    scans = sum(2 * (1 + 4 * w) * slots * passes for w in (words_first, words_second))
    assert scans == least * passes and passes >= 24
    assert 100.0 * least / scans < 5.0


def test_the_parent_and_a_stale_cache_give_nothing_not_zero(monkeypatch):
    # the parent's dispatch says nothing of the fold
    got = read_new(monkeypatch, PS.reduce(q1_planes(new=False)))
    assert got["agg_state_words"] is None
    assert got["group_reduce_dev_share"] == pytest.approx(75.0)
    # a program cached before any scope: the span reader reads on
    got = read_new(monkeypatch, PS.reduce(q1_planes(scopes=False)))
    assert got["group_reduce_dev_share"] is None and got["decimal_dev_share"] is None
    assert got["agg_fold_hbm_share"] is None and got["agg_state_words"] == 11.0
    # a job file that states no fold_bytes
    got = read_new(monkeypatch, PS.reduce(q1_planes()), job=object())
    assert got["agg_fold_hbm_share"] is None
    # no xplane; an untraced run
    for summary, trace in ((None, True), (PS.reduce(q1_planes()), False)):
        got = read_new(monkeypatch, summary, trace)
        assert all(v is None for v in got.values()), got


# -- one traced run on the CPU of a cell of the same shape -------------------------

def test_a_traced_cpu_run_of_q1(tmp_path, monkeypatch, capsys):
    """A temp copy with a tiny one-device cell of the new configuration's
    shape (2,000 orders, 2^14 slots) for which the four metrics are
    listed: the span reader reads the real program's real dispatch, and
    the seven metrics that list no cells read the cell as they read
    every cell.  (The CPU's trace has no device plane that carries
    scopes: the three scope readers are silent here.)"""
    import importlib.util
    import shutil

    import jax

    from test_run_cpu import cpu_trace_loader

    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(f"{ROOT}/BENCHMARK.json") as fh:
        b = json.load(fh)
    (tmp_path / "benchmarks" / "configs" / "tiny-q1.json").write_text(
        json.dumps({"name": "tiny-q1", "chips": 1, "reduced": []}))
    (tmp_path / "benchmarks" / "traffic" / "q1-tiny.json").write_text(
        json.dumps({"job": "tpch_q1", "orders": 2000, "parts": 300, "slots": 1 << 14,
                    "delta_days": 90, "partitions": 1, "pool": 2}))
    b["configs"].append({
        "name": "tiny-q1", "source": "a throwaway of the CPU test",
        "file": "benchmarks/configs/tiny-q1.json", "reduced": [], "why": "test"})
    b["workloads"].append({
        "name": "q1-tiny", "config": "tiny-q1", "traffic": "q1-tiny",
        "chips": 1, "why": "test"})
    for m in b["per_layer"]:
        if m["name"] in PER_LAYER_49:
            m["workloads"].append("q1-tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    spec = importlib.util.spec_from_file_location(
        "bench_copy_run_49", tmp_path / "benchmarks" / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setattr(module, "require_chips", lambda chips: jax.devices())
    monkeypatch.setattr(module, "load_peaks", lambda kind: {"hbm_bytes_per_s": 50e9})
    monkeypatch.setattr(TR, "load", cpu_trace_loader)
    PS._of_trace.cache_clear()
    capsys.readouterr()
    rc = module.main(["--workload", "q1-tiny", "--seed", "4900000019",
                      "--seconds", "0.3", "--trace", "1"])
    PS._of_trace.cache_clear()
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert UNLISTED <= set(metrics)
    assert metrics["agg_state_words"] == 11.0 and metrics["window_compiles"] == 0
    for number in sorted(NUMBERS):
        assert any(ln.startswith(f"[bench] check number={number} ")
                   and ln.endswith(" ok=1") for ln in lines), number
    requery = [ln for ln in lines if ln.startswith("[bench] spans kind=bench:requery")]
    assert "dryad:dispatch:input+where+select+group_by+order_by=" in requery[0]
    assert "dryad:ingest:" not in requery[0]  # the table stays resident


def test_a_program_without_decimal_leaves_at_import(monkeypatch):
    """The parent of PR 49 has no ``dryad_tpu.DECIMAL``: the job module
    ends the run with a sentence as it is imported, before a table is
    drawn."""
    import dryad_tpu

    monkeypatch.delattr(dryad_tpu, "DECIMAL")
    with pytest.raises(SystemExit, match="no DECIMAL / DATE column types"):
        run.load_module("jobs", "tpch_q1")
