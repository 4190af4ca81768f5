"""``BENCHMARK.json`` as PR 26 leaves it (the configuration
``dryadlinq-join-1c``, its cell ``join-topk-1c``, five per-layer
metrics), and the five readers' arithmetic on hand-built planes and on
one traced CPU run.  Everything is written as "at least these", so
the next addition does not break it; the equalities by which
``test_benchmark_json.py`` and ``test_benchmark_json_24.py`` pin the
inventories of PR 23 and PR 24 fail by those pins alone and are a
``benchmark`` PR's to turn."""

import inspect
import json
import os

import pytest

import program_spans as PS
import run
import trace_reduce as TR
from conftest import BENCH, ROOT
from test_benchmark_json import NAME, SOURCES, UNIT, bench, line
from test_program_spans import SCOPE, span

CELLS_24 = ["sort-1c", "wordcount-1c", "groupby-4c"]
SCOPE_READERS = {
    "join_dev_share": "dryad.join",
    "join_probe_dev_share": "dryad.join.probe",
    "join_materialize_dev_share": "dryad.join.materialize",
    "topk_dev_share": "dryad.topk",
}
PER_LAYER_26 = set(SCOPE_READERS) | {"dispatches_a_job"}


def test_the_configuration_and_the_cell():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    # what was there stays first and as it was; new entries at the end
    assert [w["name"] for w in b["workloads"]][:3] == CELLS_24
    assert len(b["workloads"]) <= 24 and len(configs) <= 24
    assert len({c["source"] for c in b["configs"]}) == len(b["configs"])
    assert len({c["file"] for c in b["configs"]}) == len(b["configs"])
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    assert {w["config"] for w in cells.values()} == set(configs)

    entry = configs["dryadlinq-join-1c"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert line(entry["source"]) and line(entry["why"]) and entry["reduced"] == []
    assert "BasicAPITests.cs" in entry["source"]
    assert entry["file"] == "benchmarks/configs/dryadlinq-join-1c.json"
    with open(os.path.join(ROOT, entry["file"])) as fh:
        body = json.load(fh)
    assert body["name"] == entry["name"] and body["source"] == entry["source"]
    assert body["reduced"] == [] and body["chips"] == 1 and body["partitions"] == 1
    assert {"rows", "dim_rows", "top", "expansion", "mix", "pool"} <= set(body["assumed"])
    said = " ".join(body["guarantees"])
    for words in ("bit for bit", "exactly one dimension row", "deterministic"):
        assert words in said

    cell = cells["join-topk-1c"]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and line(cell["why"])
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dryadlinq-join-1c", "join_topk", 1)
    loaded = run.load_cell("join-topk-1c")
    assert loaded.config["chips"] == 1
    params = loaded.params
    # the job file that was there, as it is
    assert params["job"] == "join_topk" and params["rows"] in (2**23, 2**22)
    assert (params["dim_rows"], params["top"], params["expansion"], params["pool"]) == (
        2**16, 100, 1.25, 2)
    # the dimension table is as large as auto still broadcasts
    from dryad_tpu.utils.config import DryadConfig

    assert params["dim_rows"] == DryadConfig().broadcast_limit
    assert loaded.pair_rows == 2 * (params["rows"] + params["dim_rows"])
    assert loaded.job.min_bytes(params) == 8 * (params["rows"] + 2**16) + 1600


def test_the_new_metrics():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert [e2e[n]["bound"] for n in ("fresh_job_s", "requery_s", "setup_s")] == [
        0.025, 0.02, 0.25]
    names = [m["name"] for m in b["per_layer"]]
    assert len(set(names)) == len(names) <= 128 and PER_LAYER_26 <= set(names)
    layers = {m["layer"] for m in b["per_layer"] if m["name"] not in PER_LAYER_26}
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name in PER_LAYER_26:
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert NAME.match(name) and UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert m["moves"] == "requery_s" and m["layer"] in layers
        assert "join-topk-1c" in m["workloads"] and set(m["workloads"]) <= cells
        assert os.path.exists(os.path.join(run.HERE, "metrics", name + ".py"))
        fn = run.load_module("metrics", name).read
        assert list(inspect.signature(fn).parameters) == [
            "trace", "spans", "counters", "cell"]
    for name in SCOPE_READERS:
        assert (by_name[name]["unit"], by_name[name]["source"],
                by_name[name]["layer"]) == ("%", "device_trace", "Kernels")
    assert (by_name["dispatches_a_job"]["unit"], by_name["dispatches_a_job"]["source"],
            by_name["dispatches_a_job"]["layer"]) == ("count", "program_span", "Executor")
    # the cell reports the three end-to-end metrics, a metric of every
    # layer it runs, and every accepted metric that lists no cells
    mine = [m for m in b["per_layer"] if "join-topk-1c" in m.get("workloads", cells)]
    assert {"Host ingest", "Executor", "Kernels", "Device"} <= {m["layer"] for m in mine}
    assert {m["name"] for m in b["per_layer"] if "workloads" not in m} <= {
        m["name"] for m in mine}
    cell = run.load_cell("join-topk-1c")
    assert {m["name"] for m in cell.end_to_end} == set(e2e)
    assert {m["name"] for m in cell.per_layer} == {m["name"] for m in mine}


def test_a_full_check_still_fits():
    b = bench()
    n = len(b["workloads"])
    assert (2 + 14 * n) * (b["run_seconds"] + 60) + n * 180 + 1200 <= 43200


# -- the five readers on planes counted by hand ------------------------------

def join_planes(inner=True, scopes=True):
    """One chip, a 20 s window: a fresh job 0-10 (busy 2-8) and a
    requery 10-18 (busy 10.5-16.5), each one dispatch of the fused
    join + top-k stage, the requery's second one an overflow retry.
    ``inner=False``: the parent's program (``dryad.join`` and
    ``dryad.join.expand_pairs`` only); ``scopes=False``: a program
    cached before any scope."""
    host = [
        ("bench:window", 0.0, 20.0, {}),
        ("bench:fresh", 0.0, 10.0, {}),
        ("bench:requery", 10.0, 18.0, {}),
        span("dryad:other:collect", 0.0, 10.0, 1),
        span("dryad:dispatch:input+join+topk", 1.5, 2.0, 2, 1, stage=0, boost=1),
        span("dryad:other:collect", 10.0, 18.0, 3),
        span("dryad:dispatch:input+join+topk", 10.1, 10.5, 4, 3, stage=1, boost=1),
        span("dryad:dispatch:input+join+topk", 13.4, 13.5, 5, 3, stage=1, boost=2),
    ]

    def op(path, start, end):
        if not scopes:
            path = path.rsplit("/", 1)[-1]
        elif not inner:
            path = "/".join(p for p in path.split("/") if p not in (
                "dryad.join.probe", "dryad.join.materialize", "dryad.join.exact",
                "dryad.sort.carry") or "dryad.topk" in path)
        return ("%fusion = f32[8]{0} fusion()", start, end,
                {"hlo_category": "fusion", "tf_op": SCOPE + path})

    def job(t):
        return [
            op("dryad.join/dryad.join.probe/dryad.sort.carry/sort:", t, t + 0.5),
            op("dryad.join/dryad.join.probe/while:", t + 0.5, t + 2.5),
            op("dryad.join/dryad.join.probe/while/body/gather:", t + 1.0, t + 2.0),
            op("dryad.join/dryad.join.expand_pairs/while:", t + 2.5, t + 4.0),
            op("dryad.join/dryad.join.materialize/gather:", t + 4.0, t + 4.75),
            op("dryad.join/dryad.join.exact/gather:", t + 4.75, t + 5.25),
            op("dryad.topk/dryad.sort.carry/sort:", t + 5.25, t + 5.75),
            op("psum:", t + 5.75, t + 6.0),
        ]

    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": job(2.0) + job(10.5)}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]


def read_new(monkeypatch, summary, trace=True):
    monkeypatch.setattr(PS, "of", lambda cell, metric_file: summary)
    cell = run.load_cell("join-topk-1c")
    return {name: run.load_module("metrics", name).read(
        {} if trace else None, {"pairs": []}, {}, cell) for name in sorted(PER_LAYER_26)}


def test_the_readers_arithmetic(monkeypatch):
    got = read_new(monkeypatch, PS.reduce(join_planes()))
    # busy 12 s: probe 2.5 a job (the sort inside it counted), expand
    # 1.5, materialize 0.75, exact 0.5, top-k 0.5, the flag's psum 0.25
    assert got["join_probe_dev_share"] == pytest.approx(100 * 5.0 / 12)
    assert got["join_materialize_dev_share"] == pytest.approx(100 * 1.5 / 12)
    assert got["join_dev_share"] == pytest.approx(100 * 10.5 / 12)
    assert got["topk_dev_share"] == pytest.approx(100 * 1.0 / 12)
    assert got["dispatches_a_job"] == 2.0  # the requery's: one retry


def test_the_parent_and_a_stale_cache_give_nothing_not_zero(monkeypatch):
    # the parent's program: dryad.join and dryad.topk are there
    # (apply_op), the new inner scopes are not
    got = read_new(monkeypatch, PS.reduce(join_planes(inner=False)))
    assert got["join_probe_dev_share"] is None
    assert got["join_materialize_dev_share"] is None
    assert got["join_dev_share"] == pytest.approx(100 * 10.5 / 12)
    assert got["topk_dev_share"] == pytest.approx(100 * 1.0 / 12)
    assert got["dispatches_a_job"] == 2.0
    got = read_new(monkeypatch, PS.reduce(join_planes(scopes=False)))
    assert [n for n, v in got.items() if v is None] == sorted(SCOPE_READERS)
    for summary, trace in ((None, True), (PS.reduce(join_planes()), False)):
        got = read_new(monkeypatch, summary, trace)  # no xplane; an untraced run
        assert all(v is None for v in got.values()), got
    # a plan without a join: no dispatch span in a job, no join scope
    planes = join_planes()
    planes[1]["lines"][0]["events"] = planes[1]["lines"][0]["events"][:3]
    planes[0]["lines"][0]["events"] = [
        e for e in planes[0]["lines"][0]["events"] if "dryad.join" not in e[3]["tf_op"]]
    got = read_new(monkeypatch, PS.reduce(planes))
    assert got["dispatches_a_job"] is None and got["join_dev_share"] is None
    assert got["topk_dev_share"] == pytest.approx(100 * 1.0 / 1.5)


# -- one traced run on the CPU of the cell's job, the new readers listed -----

def test_a_traced_cpu_run_of_the_join(tmp_path, monkeypatch, capsys):
    """A temp copy with a tiny cell of the new configuration's shape for
    which the five metrics are listed.  The CPU backend has no device
    plane, so the scope shares find nothing to read and are left out;
    ``dispatches_a_job`` counts the real program's real spans."""
    import importlib.util
    import shutil

    import jax

    from test_run_cpu import cpu_trace_loader

    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(f"{ROOT}/BENCHMARK.json") as fh:
        b = json.load(fh)
    (tmp_path / "benchmarks" / "configs" / "tiny-1c.json").write_text(
        json.dumps({"name": "tiny-1c", "chips": 1, "reduced": []}))
    (tmp_path / "benchmarks" / "traffic" / "join-tiny.json").write_text(
        json.dumps({"job": "join_topk", "rows": 4096, "dim_rows": 128,
                    "top": 100, "expansion": 1.25, "pool": 2}))
    b["configs"].append({
        "name": "tiny-1c", "source": "a throwaway of the CPU test",
        "file": "benchmarks/configs/tiny-1c.json", "reduced": [], "why": "test"})
    b["workloads"].append({
        "name": "join-tiny-1c", "config": "tiny-1c", "traffic": "join-tiny",
        "chips": 1, "why": "test"})
    for m in b["per_layer"]:
        if m["name"] in PER_LAYER_26:
            m["workloads"].append("join-tiny-1c")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    spec = importlib.util.spec_from_file_location(
        "bench_copy_run_26", tmp_path / "benchmarks" / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setattr(module, "require_chips", lambda chips: jax.devices())
    monkeypatch.setattr(module, "load_peaks", lambda kind: {"hbm_bytes_per_s": 50e9})
    monkeypatch.setattr(TR, "load", cpu_trace_loader)
    PS._of_trace.cache_clear()
    capsys.readouterr()
    rc = module.main(["--workload", "join-tiny-1c", "--seed", "3000000019",
                      "--seconds", "0.3", "--trace", "1"])
    PS._of_trace.cache_clear()
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert not set(SCOPE_READERS) & set(metrics)
    assert metrics["dispatches_a_job"] == 1.0  # one fused stage, no retry
    assert metrics["window_compiles"] == 0
    assert any(ln == "[bench] scopes none" for ln in lines)
    spans = [ln for ln in lines if ln.startswith("[bench] spans kind=bench:fresh")]
    # two inputs in one collect(): both tables bound, encoded and put
    # before the one dispatch
    assert "dryad:dispatch:input+join+topk=" in spans[0]
    assert "dryad:ingest:h2d=" in spans[0] and "dryad:ingest:encode=" in spans[0]
