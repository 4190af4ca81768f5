"""``BENCHMARK.json`` against the rules it is refused over before a
single run: keys, names, lengths, files, and that every metric's reader
and every cell's traffic and job file exist."""

import json
import os
import re

import run
from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_and_sizes():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert b["command"] == ["python3", "benchmarks/run.py"]
    assert b["paths"] == ["benchmarks"]
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    # a full check with all 24 cells must fit 43200 s
    runs = 2 + 14 * 24
    assert runs * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


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
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    cells = b["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and line(w["why"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert w["chips"] == run.load_cell(w["name"]).config["chips"]
    assert {w["config"] for w in cells} == set(configs)
    assert [w["name"] for w in cells] == ["sort-1c", "wordcount-1c"]
    assert not [w["name"] for w in cells if w["chips"] == 4]


def test_metrics():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert set(e2e) == {"fresh_job_s", "requery_s", "setup_s"}
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert e2e["setup_s"]["bound"] == 0.25
    layered = {m["name"]: m for m in b["per_layer"]}
    assert set(layered) == {
        "ingest_s", "window_compiles", "execute_s", "mean_rows_per_s_chip",
        "gather_dev_share", "hbm_floor_share", "egress_s", "device_idle_share"}
    assert not set(layered) & set(e2e)
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and line(m["layer"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_share"):
            assert m["unit"] == "%"
        assert os.path.exists(os.path.join(run.HERE, "metrics", m["name"] + ".py"))


def test_files_under_paths_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for folder, _, names in os.walk(run.HERE):
        if "__pycache__" in folder:
            continue
        for name in names:
            rel = os.path.relpath(os.path.join(folder, name), ROOT)
            assert ok.match(rel) and len(rel) <= 200, rel
