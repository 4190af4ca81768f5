"""``run.py`` end to end on the CPU at 2^12 rows, with the look for a
chip patched HERE only, on one device and on four virtual ones.

The runs are made in a temp copy of the benchmark to which a throwaway
configuration, traffic mixes, cells and a per-layer metric are ADDED
as new files and new ``BENCHMARK.json`` entries — no file that exists
is edited, which is how a later PR has to add its own.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT

TINY = {
    "sort-tiny": {"job": "sort", "rows": 4096, "pool": 2},
    "wordcount-tiny": {"job": "wordcount", "rows": 4096, "vocab": 256,
                       "top": 20, "pool": 2},
    "groupby-tiny": {"job": "groupby", "rows": 4096, "groups": 64, "pool": 2},
    "join-tiny": {"job": "join_topk", "rows": 4096, "dim_rows": 128,
                  "top": 100, "expansion": 1.25, "pool": 2},
}
CELLS = [("sort-tiny-1c", "sort-tiny", 1), ("wordcount-tiny-1c", "wordcount-tiny", 1),
         ("groupby-tiny-4c", "groupby-tiny", 4), ("join-tiny-1c", "join-tiny", 1),
         ("sort-tiny-4c", "sort-tiny", 4)]
NEW_METRIC = '''"""Pairs the window completed (a throwaway reader)."""


def read(trace, spans, counters, cell):
    return float(sum(1 for rec in spans["pairs"] if "pair_s" in rec))
'''


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The temp copy, its ``run`` module, and the names it added."""
    root = tmp_path_factory.mktemp("bench_copy")
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for chips in (1, 4):
        name = f"tiny-{chips}c"
        path = root / "benchmarks" / "configs" / f"{name}.json"
        path.write_text(json.dumps({"name": name, "chips": chips,
                                    "reduced": []}))
        bench["configs"].append({
            "name": name, "source": "a throwaway of the CPU test",
            "file": f"benchmarks/configs/{name}.json",
            "reduced": [], "why": "test"})
    for traffic, params in TINY.items():
        (root / "benchmarks" / "traffic" / f"{traffic}.json").write_text(
            json.dumps(params))
    for cell, traffic, chips in CELLS:
        bench["workloads"].append({
            "name": cell, "config": f"tiny-{chips}c", "traffic": traffic,
            "chips": chips, "why": "test"})
    (root / "benchmarks" / "metrics" / "pairs_done.py").write_text(NEW_METRIC)
    bench["per_layer"].append({
        "name": "pairs_done", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "API / planner",
        "moves": "fresh_job_s", "workloads": [c[0] for c in CELLS]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for path, content in before.items():
        assert path.read_bytes() == content, f"{path} was edited"
    spec = importlib.util.spec_from_file_location(
        "bench_copy_run", root / "benchmarks" / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return root, module, bench


def cpu_trace_loader(path):
    """A stand-in for the test only: the CPU backend has no device
    plane, so XLA:CPU's thunks (host events with an ``hlo_op`` stat)
    play chip 0."""
    import trace_reduce
    import xplane

    ops, annotations = {0: []}, []
    for plane in xplane.read(path):
        if plane["name"] != trace_reduce.HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, end, stats in line["events"]:
                if name.startswith(trace_reduce.ANNOTATION_PREFIX):
                    annotations.append((name, start, end))
                elif "hlo_op" in stats:
                    ops[0].append((name, start, end))
    return trace_reduce.Trace(ops, annotations)


@pytest.fixture
def on_cpu(copy, monkeypatch):
    """Patch the look for a chip (and the two facts only a TPU has: a
    device plane in the trace, a row in peaks.json) in the test only."""
    import jax
    import trace_reduce

    _, module, _ = copy
    monkeypatch.setattr(module, "require_chips", lambda chips: jax.devices())
    monkeypatch.setattr(module, "load_peaks",
                        lambda kind: {"hbm_bytes_per_s": 50e9})
    monkeypatch.setattr(trace_reduce, "load", cpu_trace_loader)
    return module


def run_cell(module, capsys, workload, trace, seconds="0.3", seed="3000000019"):
    capsys.readouterr()
    rc = module.main(["--workload", workload, "--seed", seed,
                      "--seconds", seconds, "--trace", str(trace)])
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, lines


def fields(line):
    return dict(item.split("=", 1) for item in line.split()[2:])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", [c[0] for c in CELLS])
def test_last_line_and_whole_pairs(on_cpu, copy, capsys, workload, trace):
    _, _, bench = copy
    rc, lines = run_cell(on_cpu, capsys, workload, trace)
    assert rc == 0
    result = json.loads(lines[-1])
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result) == want | ({"breakdown"} if trace else set())
    assert result["correct"] is True and result["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    if trace:
        names = {m["name"] for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])}
        assert result["device"]["busy_s"] > 0
        assert result["device"]["window_s"] >= result["device"]["busy_s"]
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert 0 < len(result["breakdown"]["device_ops"]) <= 10
        assert result["metrics"]["window_compiles"]["value"] == 0
        assert result["metrics"]["pairs_done"]["value"] >= 1
    else:
        names = {m["name"] for m in bench["end_to_end"]}
    assert set(result["metrics"]) == names
    for name, metric in result["metrics"].items():
        assert set(metric) == {"value", "unit"} and metric["value"] >= 0, name
    # whole pairs only: every pair has its three clocks, a pair starts
    # only inside the window and the one in flight runs to its end
    pairs = [fields(ln) for ln in lines if ln.startswith("[bench] pair ")]
    window = [p for p in pairs if int(p["i"]) >= 0]
    assert len(pairs) == len(window) + 1  # the warm-up pair, i = -1
    assert result["attempted"] == 2 * len(pairs)
    for p in window:
        assert {"fresh_s", "requery_s", "pair_s"} <= set(p)
        assert float(p["t"]) < 0.3
        assert float(p["pair_s"]) >= float(p["fresh_s"]) + float(p["requery_s"]) - 3e-6
    checks = [fields(ln) for ln in lines if ln.startswith("[bench] check ")]
    assert checks and all(c["ok"] == "1" and "limit" in c for c in checks)
    import statistics

    if trace:
        # the rate over all the work and all the time, where it decides nothing
        cell = on_cpu.load_cell(workload)
        said = fields(next(ln for ln in lines if ln.startswith("[bench] window ")))
        got = result["metrics"]["mean_rows_per_s_chip"]["value"]
        assert got == pytest.approx(
            cell.pair_rows * len(window) / float(said["open_to_last_end_s"])
            / cell.chips, rel=2e-2)
        # a job's parts, read off the trace, fit inside the job
        for part, kind in (("ingest_s", "fresh_s"), ("execute_s", "requery_s")):
            assert 0 <= result["metrics"][part]["value"] <= max(
                float(p[kind]) for p in window)
    else:
        # the two latencies are medians over the window's jobs
        for name, key in (("fresh_job_s", "fresh_s"), ("requery_s", "requery_s")):
            assert result["metrics"][name]["value"] == pytest.approx(
                statistics.median(float(p[key]) for p in window), abs=2e-6)
    # nothing of the harness runs between jobs: the next pair starts
    # within a few milliseconds of the last one's end
    for before, after in zip(window, window[1:]):
        gap = float(after["t"]) - float(before["t"]) - float(before["pair_s"])
        assert 0 <= gap < 0.02


@pytest.mark.parametrize("workload", [c[0] for c in CELLS[:4]])
def test_a_wrong_answer_lands_in_failed(on_cpu, capsys, monkeypatch, workload):
    """The rest of a run with the timed path broken underneath: every
    third ``collect()`` alters one value of its answer where it is
    produced; ``correct`` must come out false."""
    from dryad_tpu.api.query import Query

    sound, calls = Query.collect, [0]

    def broken(self):
        out = sound(self)
        calls[0] += 1
        if calls[0] % 3 == 0:
            column = "count" if "count" in out else sorted(
                c for c in out if out[c].dtype != object)[-1]
            out[column] = out[column].copy()
            out[column][0] += 1
        return out

    monkeypatch.setattr(Query, "collect", broken)
    rc, lines = run_cell(on_cpu, capsys, workload, 0)
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is False
    assert 0 < result["failed"] <= result["attempted"]
    assert result["failed"] == calls[0] // 3
    assert any(fields(ln)["ok"] == "0" for ln in lines
               if ln.startswith("[bench] check "))


def test_a_job_that_raises_is_a_failed_job(on_cpu, capsys, monkeypatch):
    from dryad_tpu.api.query import Query

    sound, calls = Query.collect, [0]

    def raising(self):
        calls[0] += 1
        if calls[0] == 4:
            raise RuntimeError("broken on purpose")
        return sound(self)

    monkeypatch.setattr(Query, "collect", raising)
    rc, lines = run_cell(on_cpu, capsys, "sort-tiny-1c", 0)
    result = json.loads(lines[-1])
    assert result["correct"] is False and result["failed"] == 1


def test_a_stalled_job_is_marked(on_cpu, capsys, monkeypatch):
    """A job that takes far longer than its kind has so far is marked
    on its pair's line and counted on the window's; the medians and
    ``correct`` do not care."""
    import time

    from dryad_tpu.api.query import Query

    sound, calls = Query.collect, [0]

    def hanging(self):
        calls[0] += 1
        if calls[0] == 5:  # the fresh job of the window's second pair
            time.sleep(1.0)
        return sound(self)

    monkeypatch.setattr(Query, "collect", hanging)
    rc, lines = run_cell(on_cpu, capsys, "sort-tiny-1c", 0, seconds="0.2")
    assert rc == 0 and json.loads(lines[-1])["correct"] is True
    pairs = [fields(ln) for ln in lines if ln.startswith("[bench] pair ")]
    assert [p.get("stalled") for p in pairs] == [None, None, "fresh"]
    assert fields(next(ln for ln in lines
                       if ln.startswith("[bench] window ")))["stalled"] == "1"


def test_no_chip_exits_nonzero_without_a_result_line():
    """No TPU here: the real look for a chip refuses, with no fallback."""
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "sort-1c",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_too_few_chips_is_refused(copy, monkeypatch):
    import jax

    _, module, _ = copy

    class Chip:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    assert module.require_chips(1)
    with pytest.raises(module.NoChips, match="asks for 4 chips"):
        module.require_chips(4)


def test_outside_the_repo_nothing_is_printed(copy):
    """In a directory that holds only BENCHMARK.json and benchmarks/,
    a run exits non-zero and prints no result (the engine is missing),
    even past the look for a chip."""
    root, _, _ = copy
    script = (
        "import sys; sys.path.insert(0, 'benchmarks'); import run, jax; "
        "run.require_chips = lambda chips: jax.devices(); "
        "sys.exit(run.main(['--workload', 'sort-tiny-1c', '--seed', '1', "
        "'--seconds', '0.2', '--trace', '0']))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "-c", script], cwd=root,
                          env={**env, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "dryad_tpu" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
