"""``BENCHMARK.json`` as PR 41 leaves it (the configuration
``dryadlinq-decomp-skew-4c``, its cell ``groupby-skew-4c`` on four chips,
five per-layer metrics), the job file's functions, the five readers'
arithmetic on hand-built planes (a job whose drain states what its
exchange saw; the parent's spans without it; a retry; no trace) and one
traced CPU run of a tiny four-device cell of the same shape.  Everything
is written as "at least these", as ``test_benchmark_json_38.py`` is."""

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

CELLS_38 = ["sort-1c", "wordcount-1c", "groupby-4c", "join-topk-1c", "sort-4c",
            "sort-100b-1c", "applyfork-1c"]
PER_LAYER_41 = {
    # name: (unit, better, source, layer)
    "combine_dev_share": ("%", "lower", "device_trace", "Kernels"),
    "combine_scan_hbm_share": ("%", "higher", "device_trace", "Kernels"),
    "combine_keep_ratio": ("ratio", "lower", "program_span", "Stage programs"),
    "recv_balance": ("ratio", "lower", "program_span", "Stage programs"),
    "exchange_retries_a_job": ("count", "lower", "program_span", "Executor"),
}
SPAN_READERS = {"combine_keep_ratio", "recv_balance", "exchange_retries_a_job"}
UNLISTED = {"ingest_s", "execute_s", "window_compiles", "gather_dev_share",
            "hbm_floor_share", "device_idle_share", "mean_rows_per_s_chip"}
NUMBERS = {
    "groupby_skew.keys_wrong", "groupby_skew.counts_differ",
    "groupby_skew.rows_uncounted", "groupby_skew.last_ts_differ",
    "groupby_skew.last_v_differ", "groupby_skew.mean_err_over_rms",
    "groupby_skew.var_err_over_ms",
}
SIZES = {2**25, 2**26}  # ISSUE 41's two; no third


def test_the_configuration_and_the_cell():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    # what was there stays first and as it was; new entries at the end
    assert [w["name"] for w in b["workloads"]][:7] == CELLS_38
    assert len(b["workloads"]) <= 24 and len(configs) <= 24
    assert len({c["source"] for c in b["configs"]}) == len(b["configs"])
    assert len({c["file"] for c in b["configs"]}) == len(b["configs"])
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    assert {w["config"] for w in cells.values()} == set(configs)

    entry = configs["dryadlinq-decomp-skew-4c"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert line(entry["source"]) and line(entry["why"]) and entry["reduced"] == []
    for words in ("github.com/wycharry/Dryad", "GroupByReduceTests.cs", "[Decomposable]",
                  "IDecomposable.cs", "YCSB", "ZIPFIAN_CONSTANT 0.99"):
        assert words in entry["source"], words
    assert entry["file"] == "benchmarks/configs/dryadlinq-decomp-skew-4c.json"
    with open(os.path.join(ROOT, entry["file"])) as fh:
        body = json.load(fh)
    assert body["name"] == entry["name"] and body["source"] == entry["source"]
    assert body["architecture"] is None  # a deployment, no catalog model
    assert body["reduced"] == [] and body["chips"] == 4 and body["mesh"] == {"p": 4}
    assert body["partitions"] == 4 and "DryadConfig() defaults" in body["engine_config"]
    assert {"rows", "groups", "columns", "reducer", "keys", "mix", "pool"} <= set(
        body["assumed"])
    assert "perm[r]" in body["assumed"]["keys"]
    assert "group_by('k', decomposable=dec)" in body["query"]
    said = " ".join(body["guarantees"])
    for words in ("comes out once", "count and last_ts are exact", "bits of the row",
                  "float64 two-pass", "group's own scale", "exactly one group",
                  "deterministic"):
        assert words in said, words

    cell = cells["groupby-skew-4c"]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and line(cell["why"])
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dryadlinq-decomp-skew-4c", "groupby_skew", 4)
    assert "HBM" in cell["why"]  # the peak a chip is stated
    loaded = run.load_cell("groupby-skew-4c")
    assert loaded.chips == 4 and loaded.config["chips"] == 4
    params = loaded.params
    assert params["job"] == "groupby_skew" and params["pool"] == 2
    assert params["groups"] == 2**21 and params["zipf_theta"] == 0.99
    assert params["partitions"] == loaded.chips
    assert params["rows"] in SIZES and "pair" in params["rows_chosen"]
    assert loaded.pair_rows == 2 * params["rows"]
    assert loaded.job.min_bytes(params) == 12 * params["rows"] + 24 * params["groups"]
    # flag + five words a slot, read and written, 1 + slack shares of a chip's rows
    assert loaded.job.scan_bytes(params) == 2 * 21 * 3 * params["rows"] // 4


def test_the_job_files_functions():
    job = run.load_module("jobs", "groupby_skew")
    for name, args in {
        "make_table": ["rng", "params", "workdir", "index"],
        "bind": ["ctx", "table", "params"],
        "reference": ["arrays", "groups"],
        "grouped_answers": ["k", "ts", "v"],
        "compare": ["table", "out", "params"],
        "control": ["table", "params"],
        "planted_faults": ["table", "params"],
        "input_rows": ["params"],
        "min_bytes": ["params"],
        "scan_bytes": ["params"],
    }.items():
        assert list(inspect.signature(getattr(job, name)).parameters) == args, name
    params = {"rows": 2**12, "groups": 2**8, "zipf_theta": 0.99, "partitions": 4}
    table = job.make_table(np.random.default_rng([41, 0]), params, None, 0)
    checks = job.compare(table, job.answer_of(table["want"]), params)
    assert set(checks) == NUMBERS
    assert all(value <= limit for value, limit in checks.values())
    # the limits are relative to the group's scale and do not grow with its rows
    assert checks["groupby_skew.mean_err_over_rms"][1] == job.MEAN_LIMIT == 2.0**-16
    assert checks["groupby_skew.var_err_over_ms"][1] == job.VAR_LIMIT == 2.0**-14
    control = job.compare(table, job.control(table, params), params)
    failed = {n for n, (value, limit) in control.items() if value > limit}
    assert "groupby_skew.last_v_differ" in failed
    assert failed & {"groupby_skew.mean_err_over_rms", "groupby_skew.var_err_over_ms"}
    for name, (answer, meant, alone) in job.planted_faults(table, params).items():
        got = job.compare(table, answer, params)
        over = {n for n, (value, limit) in got.items() if value > limit}
        assert meant <= over and (over == meant or not alone), name


def test_the_new_metrics():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert [e2e[n]["bound"] for n in ("fresh_job_s", "requery_s", "setup_s")] == [
        0.025, 0.02, 0.25]
    assert b["run_seconds"] == 48
    names = [m["name"] for m in b["per_layer"]]
    assert len(set(names)) == len(names) <= 128 and set(PER_LAYER_41) <= set(names)
    layers = {m["layer"] for m in b["per_layer"] if m["name"] not in PER_LAYER_41}
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name, (unit, better, source, layer) in PER_LAYER_41.items():
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert NAME.match(name) and UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            unit, better, source, layer, "requery_s")
        assert m["layer"] in layers
        assert "groupby-skew-4c" in m["workloads"] and set(m["workloads"]) <= cells
        fn = run.load_module("metrics", name).read
        assert list(inspect.signature(fn).parameters) == [
            "trace", "spans", "counters", "cell"]
    # no accepted entry's list of cells was edited for the new one
    for m in b["per_layer"]:
        if m["name"] not in PER_LAYER_41:
            assert "groupby-skew-4c" not in m.get("workloads", [])
    cell = run.load_cell("groupby-skew-4c")
    assert {m["name"] for m in cell.end_to_end} == set(e2e)
    mine = {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in b["per_layer"] if "workloads" not in m} <= mine
    assert UNLISTED | set(PER_LAYER_41) == mine


def test_a_full_check_still_fits():
    b = bench()
    n = len(b["workloads"])
    assert (2 + 14 * n) * (b["run_seconds"] + 60) + n * 180 + 1200 <= 43200


# -- the five readers on planes counted by hand ---------------------------------------

def skewed_planes(new=True, scopes=True, retried=False):
    """Two chips, a 20 s window: a fresh job 0-10 and a requery 10-18,
    each one dispatch and one ``drain`` that states what the exchange
    saw: 8,000 rows into the combiners, 1,000 out, 520 received by the
    fuller chip.  Device 4 s a job a chip: the combiner's layout 1 s,
    scan 0.5 s, emit 1.5 s (both runs), the exchange 1 s.  ``new=False``:
    the spans as the parent of PR 41 writes them; ``retried``: the
    requery's first dispatch overflowed and the job ran again at boost 2
    (two dispatches, two drains, the second ``overflows`` 1)."""
    host = [
        ("bench:window", 0.0, 20.0, {}),
        ("bench:fresh", 0.0, 10.0, {}),
        ("bench:requery", 10.0, 18.0, {}),
    ]
    seen = dict(combine_rows_in=8000, combine_rows_out=1000, recv_rows_max=520,
                exchanges=1) if new else {}

    def job(t, first_id, retry):
        ids = iter(range(first_id, first_id + 20))
        root = next(ids)
        out = [span("dryad:other:collect", t, t + 8.0, root)]
        at = t + 0.1
        for boost in ((1, 2) if retry else (1,)):
            out.append(span("dryad:dispatch:input+group_by", at, at + 0.1, next(ids),
                            root, boost=boost))
            said = dict(seen, boost=boost, overflows=int(retry)) if new else {}
            if retry and boost == 1 and new:
                said.update(recv_rows_max=400, overflows=1)  # rows were dropped
            out.append(span("dryad:readback:drain", at + 0.1, at + 2.0, next(ids),
                            root, inflight=1, **said))
            at += 2.0
        return out

    host += job(0.0, 1, False) + job(10.0, 30, retried)

    def op(path, start, end):
        if not scopes:
            path = path.rsplit("/", 1)[-1]
        return ("%fusion = u32[8]{0} fusion()", start, end,
                {"hlo_category": "fusion", "tf_op": SCOPE + path})

    def device(t):
        combine = "dryad.group_combine/dryad.group_combine."
        return [
            op(combine + "layout/dryad.sort.carry/sort:", t, t + 1.0),
            op(combine + "scan/select_n:", t + 1.0, t + 1.5),
            op(combine + "emit/scatter:", t + 1.5, t + 3.0),
            op("dryad.exchange_hash/dryad.exchange.layout/scatter:", t + 3.0, t + 4.0),
        ]

    return [
        {"name": f"/device:TPU:{chip}", "lines": [
            {"name": "XLA Ops", "events": device(0.3) + device(10.3)}]}
        for chip in (0, 1)
    ] + [{"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]}]


class TinyJob:
    @staticmethod
    def scan_bytes(params):
        return 100e6  # a job a chip


def read_new(monkeypatch, summary, trace=True, job=TinyJob):
    monkeypatch.setattr(PS, "of", lambda cell, metric_file: summary)
    cell = run.load_cell("groupby-skew-4c")
    cell.chips, cell.peaks, cell.job = 2, {"hbm_bytes_per_s": 1e9}, job
    return {name: run.load_module("metrics", name).read(
        {} if trace else None, {"pairs": []}, {}, cell) for name in sorted(PER_LAYER_41)}


def test_the_readers_arithmetic(monkeypatch, capsys):
    got = read_new(monkeypatch, PS.reduce(skewed_planes()))
    assert got["combine_dev_share"] == pytest.approx(75.0)  # 3 s of 4 a job
    # two dispatches x 100 MB over 1 s of scan a chip, of a peak of 1 GB/s
    assert got["combine_scan_hbm_share"] == pytest.approx(20.0)
    assert got["combine_keep_ratio"] == pytest.approx(0.125)
    assert got["recv_balance"] == pytest.approx(520 * 2 / 1000)
    assert got["exchange_retries_a_job"] == 0.0  # a reading, not a silence
    assert "[bench] combine_scan dispatches=2 " in capsys.readouterr().out
    # a requery that overflowed and ran again: its last drain is read
    got = read_new(monkeypatch, PS.reduce(skewed_planes(retried=True)))
    assert got["exchange_retries_a_job"] == 1.0
    assert got["recv_balance"] == pytest.approx(1.04)
    assert got["combine_keep_ratio"] == pytest.approx(0.125)
    assert got["combine_scan_hbm_share"] == pytest.approx(30.0)  # three dispatches


def test_the_parent_and_a_stale_cache_give_nothing_not_zero(monkeypatch):
    # the parent's drain says nothing of the exchange, its program has
    # the operator's scope and none of the three inside it
    got = read_new(monkeypatch, PS.reduce(skewed_planes(new=False)))
    assert got["combine_keep_ratio"] is None and got["recv_balance"] is None
    assert got["exchange_retries_a_job"] is None
    # a program cached before any scope: the span readers read on
    got = read_new(monkeypatch, PS.reduce(skewed_planes(scopes=False)))
    assert got["combine_dev_share"] is None and got["combine_scan_hbm_share"] is None
    assert got["combine_keep_ratio"] == pytest.approx(0.125)
    # a job file that states no scan_bytes
    got = read_new(monkeypatch, PS.reduce(skewed_planes()), job=object())
    assert got["combine_scan_hbm_share"] is None
    assert got["combine_dev_share"] == pytest.approx(75.0)
    # no xplane; an untraced run
    for summary, trace in ((None, True), (PS.reduce(skewed_planes()), False)):
        got = read_new(monkeypatch, summary, trace)
        assert all(v is None for v in got.values()), got


# -- one traced run on the CPU of a cell of the same shape -------------------------

def test_a_traced_cpu_run_of_the_skewed_group_by(tmp_path, monkeypatch, capsys):
    """A temp copy with a tiny four-device cell of the new
    configuration's shape (2^14 rows over 2^10 keys) for which the five
    metrics are listed: the span readers read the real program's real
    drain, and the seven metrics that list no cells read the cell as
    they read every cell.  (The CPU's trace has no device plane that
    carries scopes: the two scope readers are silent here.)"""
    import importlib.util
    import shutil

    import jax

    from test_run_cpu import cpu_trace_loader

    rows, groups = 1 << 14, 1 << 10
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(f"{ROOT}/BENCHMARK.json") as fh:
        b = json.load(fh)
    (tmp_path / "benchmarks" / "configs" / "tiny-skew.json").write_text(
        json.dumps({"name": "tiny-skew", "chips": 4, "reduced": []}))
    (tmp_path / "benchmarks" / "traffic" / "skew-tiny.json").write_text(
        json.dumps({"job": "groupby_skew", "rows": rows, "groups": groups,
                    "zipf_theta": 0.99, "partitions": 4, "pool": 2}))
    b["configs"].append({
        "name": "tiny-skew", "source": "a throwaway of the CPU test",
        "file": "benchmarks/configs/tiny-skew.json", "reduced": [], "why": "test"})
    b["workloads"].append({
        "name": "skew-tiny", "config": "tiny-skew", "traffic": "skew-tiny",
        "chips": 4, "why": "test"})
    for m in b["per_layer"]:
        if m["name"] in PER_LAYER_41:
            m["workloads"].append("skew-tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    spec = importlib.util.spec_from_file_location(
        "bench_copy_run_41", tmp_path / "benchmarks" / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setattr(module, "require_chips", lambda chips: jax.devices())
    monkeypatch.setattr(module, "load_peaks", lambda kind: {"hbm_bytes_per_s": 50e9})
    monkeypatch.setattr(TR, "load", cpu_trace_loader)
    PS._of_trace.cache_clear()
    capsys.readouterr()
    rc = module.main(["--workload", "skew-tiny", "--seed", "4100000019",
                      "--seconds", "0.3", "--trace", "1"])
    PS._of_trace.cache_clear()
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert SPAN_READERS <= set(metrics) and UNLISTED <= set(metrics)
    assert metrics["window_compiles"] == 0
    assert metrics["exchange_retries_a_job"] == 0.0
    # a chip holds 2^12 rows of 2^10 keys, the hottest an eighth of
    # them: the combiner leaves well under half, over a key a chip
    assert groups / rows < metrics["combine_keep_ratio"] < 0.5
    assert 1.0 <= metrics["recv_balance"] < 1.3
    for number in sorted(NUMBERS):
        assert any(ln.startswith(f"[bench] check number={number} ")
                   and ln.endswith(" ok=1") for ln in lines), number
    requery = [ln for ln in lines if ln.startswith("[bench] spans kind=bench:requery")]
    assert "dryad:dispatch:input+group_by=" in requery[0]
    assert "dryad:readback:drain=" in requery[0]
    assert "dryad:ingest:" not in requery[0]  # the table stays resident
