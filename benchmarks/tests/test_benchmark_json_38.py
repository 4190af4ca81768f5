"""``BENCHMARK.json`` as PR 38 leaves it (the configuration
``dryadlinq-applyfork-1c``, its cell ``applyfork-1c`` on one chip, three
per-layer metrics), the job file's functions, the three readers'
arithmetic on hand-built planes (a job of three outputs; the parent's
spans without ``outputs`` / ``output``; no trace) and one traced CPU run
of a tiny cell of the same shape.  Everything is written as "at least
these", as ``test_benchmark_json_30.py`` is."""

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

CELLS_32 = ["sort-1c", "wordcount-1c", "groupby-4c", "join-topk-1c", "sort-4c",
            "sort-100b-1c"]
PER_LAYER_38 = {
    # name: (unit, better, source, layer)
    "outputs_a_job": ("count", "higher", "program_span", "API / planner"),
    "fetched_slots_a_row": ("ratio", "lower", "program_span", "Egress"),
    "sparse_decode_s": ("s", "lower", "program_span", "Egress"),
}
UNLISTED = {"ingest_s", "execute_s", "window_compiles", "gather_dev_share",
            "hbm_floor_share", "device_idle_share", "mean_rows_per_s_chip"}
NUMBERS = {
    "applyfork.rows_missing", "applyfork.hot_keys_out_of_order",
    "applyfork.hot_scores_off_key", "applyfork.hot_rows_misrouted",
    "applyfork.rest_rows_off", "applyfork.tee_count_off",
    "applyfork.tee_sum_err_over_tol",
}


def test_the_configuration_and_the_cell():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    # what was there stays first and as it was; new entries at the end
    assert [w["name"] for w in b["workloads"]][:6] == CELLS_32
    assert len(b["workloads"]) <= 24 and len(configs) <= 24
    assert len({c["source"] for c in b["configs"]}) == len(b["configs"])
    assert len({c["file"] for c in b["configs"]}) == len(b["configs"])
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    assert {w["config"] for w in cells.values()} == set(configs)

    entry = configs["dryadlinq-applyfork-1c"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert line(entry["source"]) and line(entry["why"]) and entry["reduced"] == []
    for words in ("github.com/wycharry/Dryad", "ApplyAndForkTests.cs",
                  "ApplyPerPartition", "Fork", "read twice", "one job", "shape 4"):
        assert words in entry["source"], words
    assert entry["file"] == "benchmarks/configs/dryadlinq-applyfork-1c.json"
    with open(os.path.join(ROOT, entry["file"])) as fh:
        body = json.load(fh)
    assert body["name"] == entry["name"] and body["source"] == entry["source"]
    assert body["reduced"] == [] and body["chips"] == 1 and body["mesh"] == {"p": 1}
    assert body["partitions"] == 1 and "DryadConfig() defaults" in body["engine_config"]
    assert {"rows", "split", "columns", "mix", "pool"} <= set(body["assumed"])
    assert "collect_many" in body["query"] and "fork" in body["query"]
    said = " ".join(body["guarantees"])
    for words in ("np.sort(key[hot])", "bit for bit", "in the table's order",
                  "exactly one of A and B", "64 x 2^-23 of the float64 sum", "one job",
                  "deterministic"):
        assert words in said, words

    cell = cells["applyfork-1c"]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and line(cell["why"])
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dryadlinq-applyfork-1c", "applyfork", 1)
    loaded = run.load_cell("applyfork-1c")
    assert loaded.chips == 1 and loaded.config["chips"] == 1
    params = loaded.params
    assert params["job"] == "applyfork" and params["pool"] == 2
    assert params["hot_eighths"] == 3 and params["outputs"] == 3
    assert params["rows"] == 2**26  # ISSUE 38's size: 512 MiB a table
    assert loaded.pair_rows == 2 * params["rows"]
    # the table read once, every row written once into A or B
    assert loaded.job.min_bytes(params) == 16 * params["rows"]
    # the split stays clear of the rungs of the trim ladder
    from dryad_tpu.columnar.batch import trim_tiers

    hot = params["rows"] * params["hot_eighths"] // 8
    tiers = trim_tiers(params["rows"])
    above = min(t for t in tiers if t >= hot)
    below = max(t for t in tiers if t < hot)
    assert min(above - hot, hot - below) > 100 * (params["rows"] * 15 / 64) ** 0.5


def test_the_job_files_functions():
    job = run.load_module("jobs", "applyfork")
    for name, args in {
        "make_table": ["rng", "params", "workdir", "index"],
        "bind": ["ctx", "table", "params"],
        "reference": ["arrays", "params", "score_dtype"],
        "compare": ["table", "answer", "params"],
        "control": ["table", "params"],
        "input_rows": ["params"],
        "min_bytes": ["params"],
        "wrong_sums": ["table", "params"],
    }.items():
        assert list(inspect.signature(getattr(job, name)).parameters) == args, name
    params = {"rows": 2**12, "hot_eighths": 3}
    table = job.make_table(np.random.default_rng([38, 0]), params, None, 0)
    a, b, c = job.reference(table["arrays"], params)
    answer = (a, b, {"n": c["n"], "t": c["t"].astype(np.float32)})
    checks = job.compare(table, answer, params)
    assert set(checks) == NUMBERS
    assert all(value <= limit for value, limit in checks.values())
    control = job.compare(table, job.control(table, params), params)
    failed = {n for n, (value, limit) in control.items() if value > limit}
    # by one limit, not by each: the scores bit for bit (at the cell's
    # rows the bfloat16 roundings average out of the sum; over a few
    # thousand rows they may not)
    assert {"applyfork.hot_scores_off_key"} <= failed <= {
        "applyfork.hot_scores_off_key", "applyfork.tee_sum_err_over_tol"}
    # the Tee's sum is held by its own limit: a fault planted on it
    # alone (another column, a block of rows short, zero) is not correct
    wrong = job.wrong_sums(table, params)
    assert set(wrong) == {"wrong_column", "dropped_block", "zero"}
    for name, answer in wrong.items():
        got = job.compare(table, answer, params)
        assert {n for n, (value, limit) in got.items() if value > limit} == {
            "applyfork.tee_sum_err_over_tol"}, name


def test_the_new_metrics():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert [e2e[n]["bound"] for n in ("fresh_job_s", "requery_s", "setup_s")] == [
        0.025, 0.02, 0.25]
    assert b["run_seconds"] == 48
    names = [m["name"] for m in b["per_layer"]]
    assert len(set(names)) == len(names) <= 128 and set(PER_LAYER_38) <= set(names)
    layers = {m["layer"] for m in b["per_layer"] if m["name"] not in PER_LAYER_38}
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name, (unit, better, source, layer) in PER_LAYER_38.items():
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert NAME.match(name) and UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            unit, better, source, layer, "requery_s")
        assert m["layer"] in layers
        assert "applyfork-1c" in m["workloads"] and set(m["workloads"]) <= cells
        fn = run.load_module("metrics", name).read
        assert list(inspect.signature(fn).parameters) == [
            "trace", "spans", "counters", "cell"]
    # the cell reports the three end-to-end metrics and every accepted
    # metric that lists no cells
    cell = run.load_cell("applyfork-1c")
    assert {m["name"] for m in cell.end_to_end} == set(e2e)
    mine = {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in b["per_layer"] if "workloads" not in m} <= mine
    assert UNLISTED | set(PER_LAYER_38) <= mine


def test_a_full_check_still_fits():
    b = bench()
    n = len(b["workloads"])
    assert (2 + 14 * n) * (b["run_seconds"] + 60) + n * 180 + 1200 <= 43200


# -- the three readers on planes counted by hand ---------------------------------------

def forked_planes(new=True, scopes=True, fused=True, compacted=False):
    """One chip, a 20 s window: a fresh job 0-10 and a requery 10-18,
    each ONE ``collect`` of three outputs: A, 1,000 rows fetched in
    1,100 slots (a trimmed copy; ``decode`` 0.5 s), B, 2,000 rows in
    3,000 slots (the whole capacity; ``decode`` 2.0 s, 2.5 s in the
    fresh job), C, one row.  Device 5 s a job: ``apply`` 0.25 s, ``fork``
    0.5 s, the sort 4 s, the fold 0.25 s.  ``new=False``: the spans as
    the parent of PR 38 writes them (no ``outputs``, no ``output``);
    ``fused=False``: a dispatch a stage; ``compacted``: B arrives packed
    too (what a device-side compaction would hand back)."""
    host = [
        ("bench:window", 0.0, 20.0, {}),
        ("bench:fresh", 0.0, 10.0, {}),
        ("bench:requery", 10.0, 18.0, {}),
    ]
    fetched_b = 2200 if compacted else 3000

    def job(t, first_id, b_decode_s):
        ids = iter(range(first_id, first_id + 20))
        root = next(ids)
        out = [span("dryad:other:collect", t, t + 8.0, root,
                    **({"outputs": 3} if new else {}))]
        names = ["input+apply+fork+order_by+aggregate"] if fused else [
            "input+apply+fork", "order_by", "aggregate"]
        for i, name in enumerate(names):
            out.append(span(f"dryad:dispatch:{name}", t + 0.1 + 0.1 * i,
                            t + 0.2 + 0.1 * i, next(ids), root, boost=1))
        at = t + 1.0
        for output, (rows, fetched, copy_s, decode_s) in enumerate((
                (1000, 1100, 0.25, 0.5), (2000, fetched_b, 0.5, b_decode_s),
                (1, 1, 0.01, 0.01))):
            said = {"output": output} if new else {}
            out.append(span("dryad:readback:fetch_copy", at, at + copy_s, next(ids),
                            root, bytes=9 * fetched, **said))
            out.append(span("dryad:decode:decode", at + copy_s, at + copy_s + decode_s,
                            next(ids), root, rows=rows, capacity=3000,
                            fetched=fetched, **said))
            at += copy_s + decode_s
        return out

    host += job(0.0, 1, 2.5) + job(10.0, 30, 2.0)

    def op(path, start, end):
        if not scopes:
            path = path.rsplit("/", 1)[-1]
        return ("%fusion = u32[8]{0} fusion()", start, end,
                {"hlo_category": "fusion", "tf_op": SCOPE + path})

    def device(t):
        return [
            op("dryad.apply/mul:", t, t + 0.25),
            op("dryad.fork/select:", t + 0.25, t + 0.75),
            op("dryad.local_sort/dryad.sort.carry/sort:", t + 0.75, t + 4.75),
            op("dryad.scalar_agg/reduce:", t + 4.75, t + 5.0),
        ]

    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": device(0.3) + device(10.3)}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]


def read_new(monkeypatch, summary, trace=True):
    monkeypatch.setattr(PS, "of", lambda cell, metric_file: summary)
    cell = run.load_cell("applyfork-1c")
    return {name: run.load_module("metrics", name).read(
        {} if trace else None, {"pairs": []}, {}, cell) for name in sorted(PER_LAYER_38)}


def test_the_readers_arithmetic(monkeypatch, capsys):
    got = read_new(monkeypatch, PS.reduce(forked_planes()))
    assert got["outputs_a_job"] == 3.0
    assert got["fetched_slots_a_row"] == pytest.approx((1100 + 3000 + 1) / 3001)
    assert got["sparse_decode_s"] == pytest.approx(2.0)  # the requery's B alone
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[bench] output ")]
    assert len(said) == 6  # two job kinds, three outputs
    assert ("[bench] output kind=bench:requery output=1 jobs=1 fetch_copy_s=0.500000 "
            "decode_s=2.000000 rows=2000 fetched=3000 bytes=27000") in said
    # the DAG unfused, a dispatch a stage: the older ``dispatches_a_job``
    # counts it as it stands (it lists its cells; R0 extends it to this one)
    monkeypatch.setattr(PS, "of", lambda cell, metric_file: PS.reduce(
        forked_planes(fused=False)))
    assert run.load_module("metrics", "dispatches_a_job").read(
        {}, {"pairs": []}, {}, run.load_cell("applyfork-1c")) == 3.0
    # what a compaction on the device would read: every answer a slice
    got = read_new(monkeypatch, PS.reduce(forked_planes(compacted=True)))
    assert got["fetched_slots_a_row"] == pytest.approx((1100 + 2200 + 1) / 3001)
    assert got["sparse_decode_s"] == 0.0  # a reading, not a silence


def test_the_parent_and_a_stale_cache_give_nothing_not_zero(monkeypatch, capsys):
    # the parent's spans: no ``outputs``, no ``output``; the rest reads
    got = read_new(monkeypatch, PS.reduce(forked_planes(new=False)))
    assert got["outputs_a_job"] is None
    assert got["fetched_slots_a_row"] == pytest.approx(4101 / 3001)
    assert got["sparse_decode_s"] == pytest.approx(2.0)
    assert "[bench] output " not in capsys.readouterr().out
    # a program cached before any scope: the span readers read on
    got = read_new(monkeypatch, PS.reduce(forked_planes(scopes=False)))
    assert got["outputs_a_job"] == 3.0 and got["sparse_decode_s"] == pytest.approx(2.0)
    # no xplane; an untraced run
    for summary, trace in ((None, True), (PS.reduce(forked_planes()), False)):
        got = read_new(monkeypatch, summary, trace)
        assert all(v is None for v in got.values()), got


# -- one traced run on the CPU of a cell of the same shape -------------------------

def test_a_traced_cpu_run_of_the_multi_output_job(tmp_path, monkeypatch, capsys):
    """A temp copy with a tiny one-device cell of the new configuration's
    shape (2^16 rows) for which the three metrics are listed: the span
    readers read the real program's real spans, and the seven metrics
    that list no cells read the cell as they read every cell."""
    import importlib.util
    import shutil

    import jax

    import dryad_tpu.columnar.batch as batch_mod
    from test_run_cpu import cpu_trace_loader

    rows = 1 << 16
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(f"{ROOT}/BENCHMARK.json") as fh:
        b = json.load(fh)
    (tmp_path / "benchmarks" / "configs" / "tiny-applyfork.json").write_text(
        json.dumps({"name": "tiny-applyfork", "chips": 1, "reduced": []}))
    (tmp_path / "benchmarks" / "traffic" / "applyfork-tiny.json").write_text(
        json.dumps({"job": "applyfork", "rows": rows, "pool": 2, "hot_eighths": 3}))
    b["configs"].append({
        "name": "tiny-applyfork", "source": "a throwaway of the CPU test",
        "file": "benchmarks/configs/tiny-applyfork.json", "reduced": [], "why": "test"})
    b["workloads"].append({
        "name": "applyfork-tiny", "config": "tiny-applyfork",
        "traffic": "applyfork-tiny", "chips": 1, "why": "test"})
    for m in b["per_layer"]:
        if m["name"] in PER_LAYER_38 or m["name"] == "dispatches_a_job":
            m["workloads"].append("applyfork-tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    spec = importlib.util.spec_from_file_location(
        "bench_copy_run_38", tmp_path / "benchmarks" / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setattr(module, "require_chips", lambda chips: jax.devices())
    monkeypatch.setattr(module, "load_peaks", lambda kind: {"hbm_bytes_per_s": 50e9})
    monkeypatch.setattr(TR, "load", cpu_trace_loader)
    # the cell's answers are over the fetch's gate; the tiny one's too
    monkeypatch.setattr(batch_mod, "TRIM_MIN_BYTES", 1 << 10)
    PS._of_trace.cache_clear()
    capsys.readouterr()
    rc = module.main(["--workload", "applyfork-tiny", "--seed", "3800000019",
                      "--seconds", "0.3", "--trace", "1"])
    PS._of_trace.cache_clear()
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(PER_LAYER_38) <= set(metrics)
    assert UNLISTED <= set(metrics)
    assert metrics["outputs_a_job"] == 3.0
    assert metrics["dispatches_a_job"] == 1.0  # PR 26's reader, listed in the copy
    assert metrics["window_compiles"] == 0
    # A's tier over its rows, B's whole capacity, the Tee's one slot
    from dryad_tpu.columnar.batch import trim_tiers

    hot = [int(f.split("=")[1]) for ln in lines
           if ln.startswith("[bench] output kind=bench:requery output=0 ")
           for f in ln.split() if f.startswith("rows=")][0]
    tier = min(t for t in trim_tiers(rows) if t >= hot)
    assert metrics["fetched_slots_a_row"] == pytest.approx((tier + rows + 1) / (rows + 1))
    assert 1.3 < metrics["fetched_slots_a_row"] < 1.5
    assert 0 < metrics["sparse_decode_s"] < 1
    for number in sorted(NUMBERS - {"applyfork.tee_sum_err_over_tol"}):
        assert any(ln.startswith(f"[bench] check number={number} worst=0 limit=0")
                   for ln in lines), number
    assert any(ln.startswith("[bench] check number=applyfork.tee_sum_err_over_tol ")
               and ln.endswith("limit=1.0 ok=1") for ln in lines)
    outputs = [ln for ln in lines if ln.startswith("[bench] output kind=bench:requery")]
    assert [ln.split()[3] for ln in outputs] == ["output=0", "output=1", "output=2"]
    assert f" fetched={rows} bytes={9 * rows}" in outputs[1]  # B: the whole capacity
    assert " rows=1 fetched=1 bytes=9" in outputs[2]  # the Tee
    requery = [ln for ln in lines if ln.startswith("[bench] spans kind=bench:requery")]
    assert "dryad:dispatch:input+apply+fork+order_by+aggregate=" in requery[0]
    assert "dryad:ingest:" not in requery[0]  # the table stays resident
