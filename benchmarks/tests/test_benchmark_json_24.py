"""``BENCHMARK.json`` as PR 24 leaves it, against the same rules as
``test_benchmark_json.py``.  That file pins PR 23's inventory by three
equalities (the two cells, no four-chip cell, the eight per-layer
names) and may not be edited by the PR that adds to the benchmark, so
two of its tests now fail by their pins alone; this file holds the
rules against the new inventory, with the old names as a subset."""

import json
import os

import run
from conftest import ROOT
from test_benchmark_json import NAME, SOURCES, UNIT, bench, line

CELLS_23 = ["sort-1c", "wordcount-1c"]
PER_LAYER_23 = {
    "ingest_s", "window_compiles", "execute_s", "mean_rows_per_s_chip",
    "gather_dev_share", "hbm_floor_share", "egress_s", "device_idle_share"}
PER_LAYER_24 = {
    "collective_dev_share", "ingest_encode_s", "ingest_bytes_per_s",
    "dispatch_s", "exchange_dev_share", "string_code_dev_share",
    "scoped_dev_share", "decode_s", "d2h_bytes_a_row", "idle_named_share"}
SPAN_READERS = {"ingest_encode_s", "ingest_bytes_per_s", "dispatch_s",
                "decode_s", "d2h_bytes_a_row"}


def test_configs_and_cells():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    assert len(configs) == len(b["configs"]) <= 24
    files = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmarks/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as fh:
            body = json.load(fh)
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert body["guarantees"] and body["assumed"]
    assert len({c["source"] for c in b["configs"]}) == len(configs)
    cells = b["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    # of a benchmark's cells at most half, rounded down, may take four
    # chips, and one always may
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and line(w["why"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert w["chips"] == run.load_cell(w["name"]).config["chips"]
    assert {w["config"] for w in cells} == set(configs)
    # what was there stays first and as it was; new entries at the end
    names = [w["name"] for w in cells]
    assert names[:2] == CELLS_23 and names[2:] == ["groupby-4c"]
    assert [w["name"] for w in cells if w["chips"] == 4] == ["groupby-4c"]
    four = run.load_cell("groupby-4c")
    assert four.params["job"] == "groupby" and four.params["rows"] == 2**25
    assert four.params["groups"] == 2**21 and four.params["pool"] == 2
    assert "f32 accumulation bound" in " ".join(four.config["guarantees"])


def test_metrics():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert set(e2e) == {"fresh_job_s", "requery_s", "setup_s"}
    assert [e2e[n]["bound"] for n in ("fresh_job_s", "requery_s", "setup_s")] == [
        0.025, 0.02, 0.25]
    names = [m["name"] for m in b["per_layer"]]
    assert len(set(names)) == len(names) <= 128
    assert set(names[:8]) == PER_LAYER_23 and set(names[8:]) == PER_LAYER_24
    assert not set(names) & set(e2e)
    layers = {m["layer"] for m in b["per_layer"][:8]} | {"Stage programs"}
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and line(m["layer"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert m["moves"] in e2e and m["layer"] in layers
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_share"):
            assert m["unit"] == "%"
        assert os.path.exists(os.path.join(run.HERE, "metrics", m["name"] + ".py"))
    for m in b["per_layer"][8:]:
        # a metric of PR 24 lists the cells where its reader finds
        # something to read, so a later cell need not report it
        assert m["workloads"]
        want = "program_span" if m["name"] in SPAN_READERS else "device_trace"
        assert m["source"] == want, m["name"]
    by_name = {m["name"]: m for m in b["per_layer"]}
    assert by_name["collective_dev_share"]["workloads"] == ["groupby-4c"]
    assert by_name["string_code_dev_share"]["workloads"] == ["wordcount-1c"]
    for name in ("exchange_dev_share", "decode_s", "d2h_bytes_a_row", "egress_s"):
        assert by_name[name]["workloads"] == ["sort-1c", "groupby-4c"]
    # every cell reports at least one per-layer metric of every layer it runs
    for cell in cells:
        mine = [m for m in b["per_layer"] if cell in m.get("workloads", cells)]
        assert {"Host ingest", "Executor", "Kernels", "Device"} <= {
            m["layer"] for m in mine}


def test_a_full_check_fits():
    b = bench()
    n = len(b["workloads"])
    assert (2 + 14 * n) * (b["run_seconds"] + 60) + n * 180 + 1200 <= 43200


def test_every_metric_file_has_a_reader_with_the_signature():
    """``test_metrics.py`` pins the nine reader files of PR 23 by an
    equality too; the same rule over the files that are there now."""
    import inspect

    names = sorted(f[:-3] for f in os.listdir(os.path.join(run.HERE, "metrics"))
                   if f.endswith(".py"))
    assert set(names) == PER_LAYER_23 | PER_LAYER_24
    assert set(names) == {m["name"] for m in bench()["per_layer"]}
    for name in names:
        fn = run.load_module("metrics", name).read
        assert list(inspect.signature(fn).parameters) == [
            "trace", "spans", "counters", "cell"]
