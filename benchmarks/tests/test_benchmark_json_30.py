"""``BENCHMARK.json`` as PR 30 leaves it (the configuration
``dryadlinq-sort-4c``, its cell ``sort-4c`` on four chips, four
per-layer metrics), the four readers' arithmetic on hand-built planes
(the change's spans, the parent's without the new stats, no trace),
the bytes a range exchange moves against the program's own accounting,
and one traced CPU run of a tiny cell of the same shape on four CPU
devices.  Everything is written as "at least these", as
``test_benchmark_json_26.py`` is."""

import inspect
import json
import os

import pytest

import program_spans as PS
import range_exchange as RX
import run
import trace_reduce as TR
from conftest import BENCH, ROOT
from test_benchmark_json import NAME, SOURCES, UNIT, bench, line
from test_program_spans import SCOPE, span

CELLS_26 = ["sort-1c", "wordcount-1c", "groupby-4c", "join-topk-1c"]
PER_LAYER_30 = {
    # name: (unit, better, source, layer)
    "range_balance": ("ratio", "lower", "program_span", "Stage programs"),
    "range_retries_a_job": ("count", "lower", "program_span", "Executor"),
    "splitters_dev_share": ("%", "lower", "device_trace", "Kernels"),
    "collective_ici_share": ("%", "higher", "device_trace", "Stage programs"),
}
ICI_BITS_PER_S = 1600e9


def test_the_configuration_and_the_cell():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    # what was there stays first and as it was; new entries at the end
    assert [w["name"] for w in b["workloads"]][:4] == CELLS_26
    assert len(b["workloads"]) <= 24 and len(configs) <= 24
    assert len({c["source"] for c in b["configs"]}) == len(b["configs"])
    assert len({c["file"] for c in b["configs"]}) == len(b["configs"])
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    assert {w["config"] for w in cells.values()} == set(configs)

    entry = configs["dryadlinq-sort-4c"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert line(entry["source"]) and line(entry["why"]) and entry["reduced"] == []
    assert "RangePartitionAPICoverageTests.cs" in entry["source"]
    assert "P = 4" in entry["source"]
    assert entry["file"] == "benchmarks/configs/dryadlinq-sort-4c.json"
    with open(os.path.join(ROOT, entry["file"])) as fh:
        body = json.load(fh)
    assert body["name"] == entry["name"] and body["source"] == entry["source"]
    assert body["reduced"] == [] and body["chips"] == 4 and body["partitions"] == 4
    assert body["mesh"] == {"p": 4}
    assert {"rows", "columns", "mix", "pool", "host"} <= set(body["assumed"])
    assert "100-byte" in body["assumed"]["columns"]  # not the TeraSort record
    said = " ".join(body["guarantees"])
    for words in ("np.sort", "global order", "accounted for", "duplicates included",
                  "deterministic"):
        assert words in said

    cell = cells["sort-4c"]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and line(cell["why"])
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dryadlinq-sort-4c", "sort_4c", 4)
    loaded = run.load_cell("sort-4c")
    assert loaded.chips == 4 and loaded.config["chips"] == 4
    params = loaded.params
    # the job file that was there, as it is; sort-1c's rows a chip, or half
    assert params["job"] == "sort" and params["pool"] == 2
    assert params["rows"] in (2**27, 2**26) and "rows_chosen" in params
    assert loaded.pair_rows == 2 * params["rows"]
    assert loaded.job.min_bytes(params) == 16 * params["rows"]
    if params["rows"] == 2**27:
        assert params["rows"] // 4 == run.load_cell("sort-1c").params["rows"]


def test_the_new_metrics():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert [e2e[n]["bound"] for n in ("fresh_job_s", "requery_s", "setup_s")] == [
        0.025, 0.02, 0.25]
    assert b["run_seconds"] == 48
    names = [m["name"] for m in b["per_layer"]]
    assert len(set(names)) == len(names) <= 128 and set(PER_LAYER_30) <= set(names)
    layers = {m["layer"] for m in b["per_layer"] if m["name"] not in PER_LAYER_30}
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name, (unit, better, source, layer) in PER_LAYER_30.items():
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert NAME.match(name) and UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            unit, better, source, layer)
        assert m["moves"] == "requery_s" and m["layer"] in layers
        assert "sort-4c" in m["workloads"] and set(m["workloads"]) <= cells
        assert os.path.exists(os.path.join(run.HERE, "metrics", name + ".py"))
        fn = run.load_module("metrics", name).read
        assert list(inspect.signature(fn).parameters) == [
            "trace", "spans", "counters", "cell"]
    # the cell reports the three end-to-end metrics and every accepted
    # metric that lists no cells
    mine = [m for m in b["per_layer"] if "sort-4c" in m.get("workloads", cells)]
    assert {m["name"] for m in b["per_layer"] if "workloads" not in m} <= {
        m["name"] for m in mine}
    assert {"Executor", "Stage programs", "Kernels", "Device"} <= {
        m["layer"] for m in mine}
    cell = run.load_cell("sort-4c")
    assert {m["name"] for m in cell.end_to_end} == set(e2e)
    assert {m["name"] for m in cell.per_layer} == {m["name"] for m in mine}


def test_a_full_check_still_fits():
    b = bench()
    n = len(b["workloads"])
    assert (2 + 14 * n) * (b["run_seconds"] + 60) + n * 180 + 1200 <= 43200


# -- the bytes a range exchange moves, against the program's accounting --------

@pytest.mark.parametrize("rows,chips", [(2**27, 4), (2**26, 4), (2**13, 4),
                                        (2**13, 8), (2**25, 1), (24, 4)])
def test_the_bytes_against_the_programs_accounting(rows, chips):
    from dryad_tpu.ops.shuffle import bucket_capacity
    from dryad_tpu.plan.xchgplan import flat_accounting
    from dryad_tpu.utils.config import DryadConfig

    assert RX.SLACK == DryadConfig().shuffle_slack
    bucket = bucket_capacity(rows // chips, chips, RX.SLACK)
    assert RX.bucket_rows(rows // chips, chips) == bucket
    assert RX.ici_bytes_a_dispatch(rows, chips) == flat_accounting(
        chips, 1, bucket, RX.SLOT_BYTES)["ici_bytes"]
    if chips == 1:
        assert RX.ici_bytes_a_dispatch(rows, chips) == 0


def test_the_bytes_of_the_cell():
    # 2^24 rows a chip in 4 buckets of 2^23 slots of 9 B: three leave
    assert RX.ici_bytes_a_dispatch(2**26, 4) == 3 * 2**23 * 9 == 226492416
    # at 2^27, the size first tried on the chip, twice that: at the ICI
    # peak 2.26 ms a dispatch
    assert RX.ici_bytes_a_dispatch(2**27, 4) == 452984832
    assert RX.roofline_share(452984832, 0.0022649, ICI_BITS_PER_S) == pytest.approx(
        100.0, rel=1e-4)


def test_the_programs_count_on_the_cpu_mesh():
    """What the dispatch span says a chip put on the ICI, at P = 4 on
    the CPU mesh, is what the shapes give."""
    import numpy as np

    from dryad_tpu import DryadContext

    job = run.load_module("jobs", "sort")
    params = {"rows": 2**13}
    table = job.make_table(np.random.default_rng(30), params, None, 0)
    ctx = DryadContext(num_partitions_=4)
    job.bind(ctx, table, params).collect()
    said = [e["xchg_ici_bytes"] for e in ctx.events.events()
            if e["kind"] == "span" and e.get("cat") == "execute"]
    assert said == [RX.ici_bytes_a_dispatch(2**13, 4)] == [3 * 2**10 * 9]


# -- the four readers on planes counted by hand --------------------------------

def sort_planes(stats=True, scopes=True, retry=False, chips=2):
    """``chips`` chips, a 20 s window: a fresh job 0-10 (busy 2-8) and a
    requery 10-18 (busy 10.5-16.5), each one dispatch of the fused
    ``order_by`` stage that ships 600 bytes a chip; ``retry``: the
    requery overflows and runs again at boost 2 (1,200 bytes).
    ``stats=False``: the parent's spans (``boost``, ``rows``,
    ``capacity``, not this PR's stats); ``scopes=False``: a program
    cached before any scope."""
    new = (lambda **kw: kw) if stats else (lambda **kw: {})
    host = [
        ("bench:window", 0.0, 20.0, {}),
        ("bench:fresh", 0.0, 10.0, {}),
        ("bench:requery", 10.0, 18.0, {}),
        span("dryad:other:collect", 0.0, 10.0, 1),
        span("dryad:dispatch:input+order_by", 1.5, 2.0, 2, 1, stage=0, boost=1,
             **new(xchg_ici_bytes=600)),
        span("dryad:decode:decode", 9.0, 10.0, 3, 1, rows=1000, capacity=2000,
             **new(shards=4, shard_rows_max=290, shard_rows_min=200)),
        span("dryad:other:collect", 10.0, 18.0, 4),
        span("dryad:dispatch:input+order_by", 10.1, 10.5, 5, 4, stage=1, boost=1,
             **new(xchg_ici_bytes=600)),
        span("dryad:decode:decode", 17.0, 18.0, 7, 4, rows=1000, capacity=2000,
             **new(shards=4, shard_rows_max=300, shard_rows_min=210)),
    ]
    if retry:
        host.append(span("dryad:dispatch:input+order_by", 13.4, 13.5, 6, 4,
                         stage=1, boost=2, **new(xchg_ici_bytes=1200)))

    def op(path, start, end):
        if not scopes:
            path = path.rsplit("/", 1)[-1]
        return ("%fusion = f32[8]{0} fusion()", start, end,
                {"hlo_category": "fusion", "tf_op": SCOPE + path})

    def job(t):
        xr = "dryad.exchange_range/"
        return [
            op(xr + "dryad.sort.splitters/dryad.sort.carry/sort:", t, t + 1.0),
            op(xr + "dryad.sort.splitters/all_gather:", t + 1.0, t + 1.25),
            op(xr + "dryad.sort.splitters/sort:", t + 1.25, t + 1.5),
            op(xr + "dryad.exchange.layout/dryad.sort.carry/sort:", t + 1.5, t + 3.0),
            op(xr + "dryad.exchange.collective/all_to_all:", t + 3.0, t + 3.5),
            op("dryad.resize/dryad.sort.carry/sort:", t + 3.5, t + 4.5),
            op("dryad.local_sort/dryad.sort.carry/sort:", t + 4.5, t + 6.0),
        ]

    return [
        *({"name": f"/device:TPU:{c}", "lines": [
            {"name": "XLA Ops", "events": job(2.0) + job(10.5)}]}
          for c in range(chips)),
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]


def read_new(monkeypatch, summary, trace=True, workload="sort-4c"):
    monkeypatch.setattr(PS, "of", lambda cell, metric_file: summary)
    cell = run.load_cell(workload)
    cell.peaks = {"ici_bits_per_s": ICI_BITS_PER_S}
    return {name: run.load_module("metrics", name).read(
        {} if trace else None, {"pairs": []}, {}, cell) for name in sorted(PER_LAYER_30)}


def test_the_readers_arithmetic(monkeypatch, capsys):
    got = read_new(monkeypatch, PS.reduce(sort_planes()))
    # the requery's answer: the fullest of 4 partitions holds 300 of 1,000
    assert got["range_balance"] == pytest.approx(300 * 4 / 1000)
    assert got["range_retries_a_job"] == 0.0
    # busy 12 s a chip: the splitters 1.5 s a job, the sort inside counted
    assert got["splitters_dev_share"] == pytest.approx(100 * 3.0 / 12)
    # two dispatches of 600 B over 2 x 0.5 s of collective a chip
    assert got["collective_ici_share"] == pytest.approx(
        100 * 1200 / 1.0 / (ICI_BITS_PER_S / 8))
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[bench] ici ")]
    assert said and "dispatches=2 bytes_a_dispatch=600 " in said[0]
    assert f"reckoned_from_shapes={RX.ici_bytes_a_dispatch(run.load_cell("sort-4c").params["rows"], 4)} " in said[0]
    # a retry is one more dispatch, and its bytes count
    got = read_new(monkeypatch, PS.reduce(sort_planes(retry=True)))
    assert got["range_retries_a_job"] == 1.0
    assert got["collective_ici_share"] == pytest.approx(
        100 * 2400 / 1.0 / (ICI_BITS_PER_S / 8))
    # one chip's seconds, not the sum over chips
    one = read_new(monkeypatch, PS.reduce(sort_planes(chips=1)))
    four = read_new(monkeypatch, PS.reduce(sort_planes(chips=4)))
    assert one == four == read_new(monkeypatch, PS.reduce(sort_planes()))


def test_the_parent_and_a_stale_cache_give_nothing_not_zero(monkeypatch):
    # the parent's spans: boost is there (PR 26), the new stats are not;
    # its program has the scopes (PR 24)
    got = read_new(monkeypatch, PS.reduce(sort_planes(stats=False)))
    assert got["range_balance"] is None and got["collective_ici_share"] is None
    assert got["range_retries_a_job"] == 0.0
    assert got["splitters_dev_share"] == pytest.approx(25.0)
    # a program cached before any scope
    got = read_new(monkeypatch, PS.reduce(sort_planes(scopes=False)))
    assert got["splitters_dev_share"] is None and got["collective_ici_share"] is None
    assert got["range_balance"] == pytest.approx(1.2)
    # no xplane; an untraced run
    for summary, trace in ((None, True), (PS.reduce(sort_planes()), False)):
        got = read_new(monkeypatch, summary, trace)
        assert all(v is None for v in got.values()), got
    # P = 1: the span says 0 bytes crossed the ICI, nothing to read
    planes = sort_planes()
    for name, _, _, stats in planes[-1]["lines"][0]["events"]:
        if "xchg_ici_bytes" in stats:
            stats["xchg_ici_bytes"] = 0
    assert read_new(monkeypatch, PS.reduce(planes))["collective_ici_share"] is None
    # a plan without a dispatch or a decode in its jobs
    planes = sort_planes()
    planes[-1]["lines"][0]["events"] = planes[-1]["lines"][0]["events"][:3]
    got = read_new(monkeypatch, PS.reduce(planes))
    assert got["range_balance"] is None and got["range_retries_a_job"] is None
    assert got["collective_ici_share"] is None


# -- one traced run on four CPU devices of a cell of the same shape ------------

def test_a_traced_cpu_run_of_the_sort_on_four_devices(tmp_path, monkeypatch, capsys):
    """A temp copy with a tiny four-device cell of the new
    configuration's shape for which the four metrics are listed.  The
    CPU backend has no device plane, so the two device shares find
    nothing to read and are left out; the two span readers read the
    real program's real spans."""
    import importlib.util
    import shutil

    import jax

    from test_run_cpu import cpu_trace_loader

    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(f"{ROOT}/BENCHMARK.json") as fh:
        b = json.load(fh)
    (tmp_path / "benchmarks" / "configs" / "tiny-4c.json").write_text(
        json.dumps({"name": "tiny-4c", "chips": 4, "reduced": []}))
    (tmp_path / "benchmarks" / "traffic" / "sort-tiny.json").write_text(
        json.dumps({"job": "sort", "rows": 8192, "pool": 2}))
    b["configs"].append({
        "name": "tiny-4c", "source": "a throwaway of the CPU test",
        "file": "benchmarks/configs/tiny-4c.json", "reduced": [], "why": "test"})
    b["workloads"].append({
        "name": "sort-tiny-4c", "config": "tiny-4c", "traffic": "sort-tiny",
        "chips": 4, "why": "test"})
    for m in b["per_layer"]:
        if m["name"] in PER_LAYER_30:
            m["workloads"].append("sort-tiny-4c")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    spec = importlib.util.spec_from_file_location(
        "bench_copy_run_30", tmp_path / "benchmarks" / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setattr(module, "require_chips", lambda chips: jax.devices())
    monkeypatch.setattr(module, "load_peaks", lambda kind: {
        "hbm_bytes_per_s": 50e9, "ici_bits_per_s": ICI_BITS_PER_S})
    monkeypatch.setattr(TR, "load", cpu_trace_loader)
    PS._of_trace.cache_clear()
    capsys.readouterr()
    rc = module.main(["--workload", "sort-tiny-4c", "--seed", "3000000019",
                      "--seconds", "0.3", "--trace", "1"])
    PS._of_trace.cache_clear()
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] >= 4
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert not {"splitters_dev_share", "collective_ici_share"} & set(metrics)
    assert 1.0 <= metrics["range_balance"] < 2.0
    assert metrics["range_retries_a_job"] == 0.0
    assert metrics["window_compiles"] == 0
    for number in ("sort.rows_missing", "sort.keys_out_of_order",
                   "sort.payloads_off_key"):
        assert any(ln.startswith(f"[bench] check number={number} worst=0 limit=0")
                   for ln in lines), number
    assert any(ln == "[bench] scopes none" for ln in lines)
    spans = [ln for ln in lines if ln.startswith("[bench] spans kind=bench:requery")]
    assert "dryad:dispatch:input+order_by=" in spans[0]
    assert "dryad:decode:decode=" in spans[0] and "capacity_over_rows=2.0000" in spans[0]
