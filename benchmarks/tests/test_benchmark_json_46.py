"""The cell ``join-hash-4c`` and its configuration
``hashjoin-wlb-zipf-4c`` as PR 46 leaves them: in ``BENCHMARK.json``, at
the end of their lists, with five per-layer metrics of their own and
the cell appended to the lists of the accepted metrics that read it
(the contract lets a new cell be appended to an accepted list; nothing
else of an accepted entry changes: the file without what PR 46 appended
is the parent's, byte for byte).  The tests hold everything else to
those entries: the configuration and traffic files, the job file's
functions (the alphabet that is the configuration's, the controls that
fail each limit, the share of S a chip the configuration states), the
readers' arithmetic on hand-built planes (a job whose drain states what
each exchange and the join's pair buffer saw; a program before PR 46; a
retry; no trace; chips whose gathers differ) and one traced CPU run of
a tiny four-device cell of the same shape, large enough that ``auto``
exchanges both sides at the default ``broadcast_limit``.  Everything is
written as "at least these", as ``test_benchmark_json_41.py`` is."""

import hashlib
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

CELLS_41 = ["sort-1c", "wordcount-1c", "groupby-4c", "join-topk-1c", "sort-4c",
            "sort-100b-1c", "applyfork-1c", "groupby-skew-4c"]
PER_LAYER_46 = {
    # name: (unit, better, source, layer)
    "copartition_dev_share": ("%", "lower", "device_trace", "Stage programs"),
    "probe_side_balance": ("ratio", "lower", "program_span", "Stage programs"),
    "exchange_fill_max": ("ratio", "lower", "program_span", "Executor"),
    "join_slots_a_pair": ("ratio", "lower", "program_span", "Kernels"),
    "materialize_chip_spread": ("ratio", "lower", "device_trace", "Kernels"),
}
SPAN_READERS = set(PER_LAYER_46) - {"copartition_dev_share", "materialize_chip_spread"}
# ``git show <parent>:BENCHMARK.json | sha256sum``
PARENT_SHA256 = "d52cdafdba10d2b8f239f09fa5077e510a51e0ba63c77f93f400991f80de94fc"
UNLISTED = {"ingest_s", "execute_s", "window_compiles", "gather_dev_share",
            "hbm_floor_share", "device_idle_share", "mean_rows_per_s_chip"}
NUMBERS = {"join_hash.answer_rows_wrong", "join_hash.matches_differ",
           "join_hash.checksum_differs"}
SMALL = {"rows_r": 2**12, "rows_s": 2**12, "zipf_theta": 1.05, "alphabet_seed": 54321,
         "expansion": 1.0}
SIZES = {2**26, 2**27}  # ISSUE 45's two, ISSUE 46 keeps the first; no third
OLDER_SIX = ["sort-1c", "wordcount-1c", "groupby-4c", "join-topk-1c", "sort-4c",
             "sort-100b-1c"]
# the accepted metrics whose lists take the cell at their end, and what each listed
TAKEN = {
    "collective_dev_share": ["groupby-4c"],
    "exchange_dev_share": ["sort-1c", "groupby-4c"],
    "join_dev_share": ["join-topk-1c"],
    "join_probe_dev_share": ["join-topk-1c"],
    "join_materialize_dev_share": ["join-topk-1c"],
    "dispatches_a_job": ["join-topk-1c"],
    "sort_carry_dev_share": ["sort-100b-1c"],
    "recv_balance": ["groupby-skew-4c"],
    "exchange_retries_a_job": ["groupby-skew-4c"],
    # the host's side of a ``from_arrays`` job: spans every such job opens
    "ingest_encode_s": ["sort-1c", "wordcount-1c", "groupby-4c"],
    "ingest_bytes_per_s": ["sort-1c", "wordcount-1c", "groupby-4c"],
    "dispatch_s": ["sort-1c", "wordcount-1c", "groupby-4c"],
    "ingest_host_bytes_a_row": OLDER_SIX,
    "encode_pad_s": OLDER_SIX,
    "collect_self_s": OLDER_SIX,
    "ingest_warm_share": OLDER_SIX,
}
FRESH = {"ingest_encode_s", "ingest_bytes_per_s", "ingest_host_bytes_a_row", "encode_pad_s",
         "collect_self_s", "ingest_warm_share"}  # move ``fresh_job_s``; the rest ``requery_s``


def by_chip_tool():
    """``benchmarks/join_by_chip.py``: a job's device seconds by chip,
    table and scope."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "join_by_chip", os.path.join(BENCH, "join_by_chip.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture
def root():
    return ROOT


def bench_at(root):
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_what_was_accepted_is_the_parents_byte_for_byte():
    """``BENCHMARK.json`` less what PR 46 appended (the last
    configuration, the last cell, the last five per-layer entries, the
    cell's name at the end of sixteen lists), written as the file is
    written, is the parent's file."""
    b = bench()
    assert b["configs"].pop()["name"] == "hashjoin-wlb-zipf-4c"
    assert b["workloads"].pop()["name"] == "join-hash-4c"
    assert [m["name"] for m in b["per_layer"][-5:]] == list(PER_LAYER_46)
    del b["per_layer"][-5:]
    for m in b["per_layer"]:
        if "join-hash-4c" in m.get("workloads", []):
            assert m["workloads"].pop() == "join-hash-4c", m["name"]
    text = json.dumps(b, indent=2) + "\n"
    assert "join-hash-4c" not in text and "hashjoin" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_SHA256
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:  # and so is the new file
        assert fh.read() == json.dumps(bench(), indent=2) + "\n"


def test_the_cell_is_in_benchmark_json():
    b = bench()
    assert [w["name"] for w in b["workloads"]] == CELLS_41 + ["join-hash-4c"]
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 4  # of nine: the limit
    assert b["configs"][-1]["name"] == "hashjoin-wlb-zipf-4c"
    assert [m["name"] for m in b["per_layer"]][-len(PER_LAYER_46):] == list(PER_LAYER_46)
    accepted = {m["name"]: m for m in b["per_layer"]}
    for name, before in TAKEN.items():  # the cell at the end, nothing else
        assert accepted[name]["workloads"] == before + ["join-hash-4c"], name
        assert accepted[name]["moves"] == (
            "fresh_job_s" if name in FRESH else "requery_s"), name
    assert {m["name"] for m in b["per_layer"]
            if "join-hash-4c" in m.get("workloads", [])} == set(TAKEN) | set(PER_LAYER_46)
    assert run.load_cell("join-hash-4c").chips == 4
    # the retries are the accepted reader's: no second copy of it
    assert not os.path.exists(os.path.join(BENCH, "metrics", "join_retries_a_job.py"))
    assert not os.path.exists(os.path.join(BENCH, "held"))


def test_the_configuration_and_the_cell(root):
    b = bench_at(root)
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    # what was there stays first and as it was; new entries at the end
    assert [w["name"] for w in b["workloads"]] == CELLS_41 + ["join-hash-4c"]
    assert len(b["workloads"]) <= 24 and len(configs) <= 24
    assert len({c["source"] for c in b["configs"]}) == len(b["configs"])
    assert len({c["file"] for c in b["configs"]}) == len(b["configs"])
    # four of nine on four chips: the limit; the next four-chip cell waits
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    assert {w["config"] for w in cells.values()} == set(configs)

    entry = configs["hashjoin-wlb-zipf-4c"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert line(entry["source"]) and line(entry["why"])
    for words in ("Balkesen", "ICDE 2013", "Workload B", "Kim et al. VLDB 2009",
                  "128M x 128M 8 B tuples", "Zipf 1.05", "Blanas", "SIGMOD 2011",
                  "--skew"):
        assert words in entry["source"], words
    assert entry["file"] == "benchmarks/configs/hashjoin-wlb-zipf-4c.json"
    with open(os.path.join(ROOT, entry["file"])) as fh:
        body = json.load(fh)
    assert body["name"] == entry["name"] and body["source"] == entry["source"]
    assert body["architecture"] is None  # a deployment, no catalog model
    assert body["chips"] == 4 and body["mesh"] == {"p": 4} and body["partitions"] == 4
    assert "DryadConfig() defaults" in body["engine_config"]
    assert set(body["schema"]) == {"R", "S", "answer"}
    assert "alphabet[r]" in body["schema"]["S"] and "perm[r]" not in body["schema"]["S"]
    assert body["alphabet_seed"] == 54321
    assert "strategy='auto'" in body["query"] and "modulo 2^32" in body["query"]
    assert {"rows", "expansion", "answer", "payloads", "keys", "alphabet",
            "share_of_s_a_chip", "mix", "pool", "host"} <= set(body["assumed"])
    assert "54321" in body["assumed"]["alphabet"]
    said = " ".join(body["guarantees"])
    for words in ("exactly one R row", "to the bit", "no row is lost", "run again",
                  "deterministic"):
        assert words in said, words

    cell = cells["join-hash-4c"]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and line(cell["why"])
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "hashjoin-wlb-zipf-4c", "join_hash", 4)
    for words in ("8 B", "Zipf 1.05", "HBM", "only across chips"):
        assert words in cell["why"], words
    loaded = run.load_cell("join-hash-4c", root)
    assert loaded.chips == 4 and loaded.config["chips"] == 4
    params = loaded.params
    assert params["job"] == "join_hash" and params["pool"] == 2
    assert params["zipf_theta"] == 1.05 and params["expansion"] == 1.0
    assert params["alphabet_seed"] == body["alphabet_seed"]
    assert params["partitions"] == loaded.chips
    assert params["rows_r"] == params["rows_s"] and params["rows_s"] in SIZES
    assert "pair" in params["rows_chosen"]
    # the source's count stands beside the cell's; `rows` is cut unless 2^27 is held
    rows = body["source_rows"]
    assert (rows["R"], rows["S"], rows["held"]) == (128_000_000, 128_000_000,
                                                    params["rows_s"])
    assert rows["factor"] == pytest.approx(params["rows_s"] / 128e6)
    assert body["rows"] == params["rows_s"]  # the key `reduced` names
    assert body["reduced"] == entry["reduced"] == (
        ["rows"] if params["rows_s"] == 2**26 else [])
    assert loaded.pair_rows == 4 * params["rows_s"]
    assert loaded.job.min_bytes(params) == 16 * params["rows_s"] + 8


def test_the_job_files_functions():
    job = run.load_module("jobs", "join_hash")
    for name, args in {
        "make_table": ["rng", "params", "workdir", "index"],
        "bind": ["ctx", "table", "params"],
        "reference": ["table"],
        "zipf_ranks": ["rng", "rows", "ranks", "theta"],
        "compare": ["table", "out", "params"],
        "control": ["table", "params"],
        "controls": ["table", "params"],
        "alphabet": ["alphabet_seed", "rows_r"],
        "chip_shares": ["params", "partitions"],
        "input_rows": ["params"],
        "min_bytes": ["params"],
    }.items():
        assert list(inspect.signature(getattr(job, name)).parameters) == args, name
    table = job.make_table(np.random.default_rng([46, 0]), SMALL, None, 0)
    assert table["want"]["matches"] == 2**12
    want = {k: np.asarray([v], np.int32) for k, v in table["want"].items()}
    checks = job.compare(table, want, SMALL)
    assert set(checks) == NUMBERS and all(c == (0, 0) for c in checks.values())

    def failed(answer):
        return {n for n, (value, limit) in job.compare(table, answer, SMALL).items()
                if value > limit}

    # the precision below fails by ONE limit; the planted faults, between them, by each
    assert failed(job.control(table, SMALL)) == {"join_hash.checksum_differs"}
    wrong = {name: failed(answer) for name, answer in job.controls(table, SMALL).items()}
    assert wrong["payload_bit_flipped"] == {"join_hash.checksum_differs"}
    assert "join_hash.matches_differ" in wrong["row_dropped"]
    assert wrong["two_rows"] == {"join_hash.answer_rows_wrong"}
    assert set().union(*wrong.values()) == NUMBERS


def test_the_alphabet_is_the_configurations_and_the_rows_are_the_seeds():
    job = run.load_module("jobs", "join_hash")
    tables = {(seed, i): job.make_table(np.random.default_rng([seed, i]), SMALL, None, i)
              for seed in (4600000001, 4600000002) for i in (0, 1)}
    rows = SMALL["rows_r"]
    alphabet = np.random.default_rng(SMALL["alphabet_seed"]).permutation(rows)
    assert np.array_equal(job.alphabet(SMALL["alphabet_seed"], rows), alphabet)
    hottest = set()
    for table in tables.values():
        keys, counts = np.unique(table["S"]["key"], return_counts=True)
        hottest.add(int(keys[counts.argmax()]))
        assert np.array_equal(np.sort(table["R"]["key"]), np.arange(rows))
    assert hottest == {int(alphabet[0])}  # rank 0's key, whatever the seed and table
    first = tables[(4600000001, 0)]
    for other in (tables[(4600000001, 1)], tables[(4600000002, 0)]):
        for side in ("R", "S"):
            for col in ("key", "payload"):
                assert not np.array_equal(first[side][col], other[side][col]), (side, col)
    again = job.make_table(np.random.default_rng([4600000001, 0]), SMALL, None, 0)
    assert all(np.array_equal(first[s][c], again[s][c])
               for s in ("R", "S") for c in ("key", "payload"))
    # another alphabet, another hottest key
    moved = job.make_table(np.random.default_rng([4600000001, 0]),
                           dict(SMALL, alphabet_seed=12345), None, 0)
    assert not np.array_equal(moved["S"]["key"], first["S"]["key"])


def test_the_share_of_s_a_chip_is_what_the_configuration_states():
    """At the cell's own size (2^26 ranks: 10 s and 2.3 GB here, which
    is why tier-1 reckons it from the hot keys alone)."""
    cell = run.load_cell("join-hash-4c")
    stated = cell.config["assumed"]["share_of_s_a_chip"]
    shares = cell.job.chip_shares(cell.params, cell.chips)
    assert np.allclose(shares, stated["shares"], atol=1e-7)
    assert shares.max() * cell.chips == pytest.approx(stated["probe_side_balance"], abs=1e-6)
    import jax.numpy as jnp

    from dryad_tpu.ops.hash import partition_ids

    hot = cell.job.alphabet(cell.params["alphabet_seed"], cell.params["rows_r"])[:10]
    assert list(np.asarray(partition_ids([jnp.asarray(hot)], 4))) == stated[
        "the_ten_hottest_keys_chips"]


def test_the_new_metrics(root):
    b, taken = bench_at(root), set(TAKEN)
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert [e2e[n]["bound"] for n in ("fresh_job_s", "requery_s", "setup_s")] == [
        0.025, 0.02, 0.25]
    assert b["run_seconds"] == 48
    names = [m["name"] for m in b["per_layer"]]
    assert len(set(names)) == len(names) <= 128 and set(PER_LAYER_46) <= set(names)
    layers = {m["layer"] for m in b["per_layer"] if m["name"] not in PER_LAYER_46}
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name, (unit, better, source, layer) in PER_LAYER_46.items():
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert NAME.match(name) and UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            unit, better, source, layer, "requery_s")
        assert m["layer"] in layers
        assert m["workloads"] == ["join-hash-4c"] and set(m["workloads"]) <= cells
        fn = run.load_module("metrics", name).read
        assert list(inspect.signature(fn).parameters) == [
            "trace", "spans", "counters", "cell"]
    # an accepted entry's list of cells takes the new one at its end, nothing else
    for m in b["per_layer"]:
        if m["name"] not in PER_LAYER_46 and m["name"] not in taken:
            assert "join-hash-4c" not in m.get("workloads", [])
    cell = run.load_cell("join-hash-4c", root)
    assert {m["name"] for m in cell.end_to_end} == set(e2e)
    mine = {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in b["per_layer"] if "workloads" not in m} <= mine
    assert UNLISTED | set(PER_LAYER_46) | taken == mine


def test_a_full_check_still_fits(root):
    b = bench_at(root)
    n = len(b["workloads"])
    assert n >= 9
    assert (2 + 14 * n) * (b["run_seconds"] + 60) + n * 180 + 1200 <= 43200


# -- the readers on planes counted by hand ----- ---------------------------------------

def join_planes(new=True, scopes=True, retried=False, slow_chip=0.0):
    """Two chips, a 20 s window: a fresh job 0-10 and a requery 10-18,
    each one dispatch and one ``drain``.  The drain states what PR 46
    put there: two exchanges of 1,000 rows each, the probe side's
    fullest chip 700 rows of a capacity of 1,000 (balance 1.4, fill
    0.7), 1,000 pairs in 1,000 slots a chip.  Device 4 s a job a chip:
    the placement 1.5 s (exchange layout 0.5, collective 0.5, resize
    0.5), the probe 1 s, the gathers 1.5 s.  ``new=False``: the spans as
    a program before PR 46 writes them (PR 41's fields alone);
    ``retried``: the requery's first dispatch overflowed and the job
    ran again at boost 2 (two drains, the second ``overflows`` 1);
    ``slow_chip``: seconds chip 1's gathers of the requery take longer."""
    host = [
        ("bench:window", 0.0, 20.0, {}),
        ("bench:fresh", 0.0, 10.0, {}),
        ("bench:requery", 10.0, 18.0, {}),
    ]
    old = dict(combine_rows_in=2000, combine_rows_out=2000, recv_rows_max=1200,
               exchanges=2)
    mine = dict(recv_balance_max=1.4, recv_fill_max=0.7, join_pairs=1000,
                join_pairs_max=700, join_slots=1000) if new else {}

    def job(t, first_id, retry):
        ids = iter(range(first_id, first_id + 20))
        root = next(ids)
        out = [span("dryad:other:collect", t, t + 8.0, root)]
        at = t + 0.1
        for boost in ((1, 2) if retry else (1,)):
            out.append(span("dryad:dispatch:input+join+select+aggregate", at, at + 0.1,
                            next(ids), root, boost=boost))
            said = dict(old, **mine, boost=boost, overflows=int(retry))
            if retry and boost == 1 and new:  # the brim, and rows dropped
                said.update(recv_balance_max=2.0, recv_fill_max=1.0, join_pairs=800)
            if retry and boost == 2 and new:  # twice the room, four times the slots
                said.update(recv_balance_max=2.4, recv_fill_max=0.6, join_slots=4000)
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

    def device(t, slower=0.0):
        placed = "dryad.join/dryad.join.copartition/"
        return [
            op(placed + "dryad.exchange.layout/sort:", t, t + 0.5),
            op(placed + "dryad.exchange.collective/all_to_all:", t + 0.5, t + 1.0),
            op(placed + "dryad.resize/sort:", t + 1.0, t + 1.5),
            op("dryad.join/dryad.join.probe/dryad.sort.carry/sort:", t + 1.5, t + 2.5),
            op("dryad.join/dryad.join.materialize/gather:", t + 2.5, t + 4.0 + slower),
        ]

    return [
        {"name": f"/device:TPU:{chip}", "lines": [
            {"name": "XLA Ops", "events": device(0.3) + device(10.3, slow_chip * chip)}]}
        for chip in (0, 1)
    ] + [{"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]}]


ACCEPTED = ("exchange_retries_a_job", "recv_balance", "join_dev_share",
            "join_probe_dev_share", "join_materialize_dev_share", "exchange_dev_share",
            "sort_carry_dev_share", "dispatches_a_job")  # read off ``PS.of``, as the new


def read_new(monkeypatch, root, summary, trace=True, names=tuple(PER_LAYER_46),
             planes=None):
    import join_observed as JO

    monkeypatch.setattr(PS, "of", lambda cell, metric_file: summary)
    monkeypatch.setattr(JO, "planes_of", lambda cell, metric_file: planes)
    cell = run.load_cell("join-hash-4c", root)
    cell.chips, cell.peaks = 2, {"hbm_bytes_per_s": 1e9}
    return {name: run.load_module("metrics", name).read(
        {} if trace else None, {"pairs": []}, {}, cell) for name in sorted(names)}


def test_the_readers_arithmetic(monkeypatch, capsys, root):
    planes = join_planes()
    got = read_new(monkeypatch, root, PS.reduce(planes), planes=planes)
    assert got["materialize_chip_spread"] == pytest.approx(1.0)  # both chips 1.5 s
    slow = join_planes(slow_chip=0.3)  # chip 1's gathers of the requery 1.8 s
    got = read_new(monkeypatch, root, PS.reduce(slow), planes=slow)
    assert got["materialize_chip_spread"] == pytest.approx(1.2)
    assert got["copartition_dev_share"] == pytest.approx(100 * 3.0 / 8.15)  # busy is the mean over the chips
    got = read_new(monkeypatch, root, PS.reduce(planes), planes=planes)
    assert got["copartition_dev_share"] == pytest.approx(37.5)  # 1.5 s of 4 a job
    assert got["probe_side_balance"] == pytest.approx(1.4)
    assert got["exchange_fill_max"] == pytest.approx(0.7)
    assert got["join_slots_a_pair"] == pytest.approx(2.0)  # 1,000 slots x 2 chips / 1,000
    assert "[bench]" not in capsys.readouterr().out  # a reader reads; it prints nothing
    # the accepted readers whose lists take the cell read the same planes
    assert set(ACCEPTED) < set(TAKEN)
    got = read_new(monkeypatch, root, PS.reduce(join_planes()), names=ACCEPTED)
    assert got["exchange_retries_a_job"] == 0.0  # a reading, not a silence
    assert got["recv_balance"] == pytest.approx(1.2)  # the SUM's: 1,200 x 2 / 2,000
    assert got["join_dev_share"] == pytest.approx(100.0)
    assert got["join_probe_dev_share"] == pytest.approx(25.0)
    assert got["join_materialize_dev_share"] == pytest.approx(37.5)
    assert got["exchange_dev_share"] == pytest.approx(25.0)  # layout + collective
    assert got["sort_carry_dev_share"] == pytest.approx(25.0)  # the probe's here
    assert got["dispatches_a_job"] == 1.0
    # a requery that overflowed and ran again: its last drain is read
    got = read_new(monkeypatch, root, PS.reduce(join_planes(retried=True)), names=ACCEPTED)
    assert got["exchange_retries_a_job"] == 1.0 and got["dispatches_a_job"] == 2.0
    got = read_new(monkeypatch, root, PS.reduce(join_planes(retried=True)))
    assert got["probe_side_balance"] == pytest.approx(2.4)
    assert got["exchange_fill_max"] == pytest.approx(0.6)
    assert got["join_slots_a_pair"] == pytest.approx(8.0)


def test_an_older_program_and_a_stale_cache_give_nothing_not_zero(monkeypatch, root):
    # a program before PR 46: PR 41's fields on the drain, no scope around the placement
    planes = join_planes(new=False)
    for plane in planes[:2]:
        for i, (name, start, end, stats) in enumerate(plane["lines"][0]["events"]):
            stats["tf_op"] = stats["tf_op"].replace("dryad.join.copartition/", "")
    got = read_new(monkeypatch, root, PS.reduce(planes), planes=planes)
    # ``dryad.join.materialize`` is PR 26's scope: the parent's program has it
    assert got.pop("materialize_chip_spread") == pytest.approx(1.0)
    assert all(value is None for value in got.values()), got
    # a program cached before any scope: the span readers read on
    bare = join_planes(scopes=False)
    got = read_new(monkeypatch, root, PS.reduce(bare), planes=bare)
    assert got["copartition_dev_share"] is None and got["materialize_chip_spread"] is None
    assert got["probe_side_balance"] == pytest.approx(1.4)
    # one chip: nothing to compare
    got = read_new(monkeypatch, root, PS.reduce(planes), planes=planes[1:])
    assert got["materialize_chip_spread"] is None
    # no xplane; an untraced run
    for summary, trace in ((None, True), (PS.reduce(join_planes()), False)):
        got = read_new(monkeypatch, root, summary, trace)
        assert all(v is None for v in got.values()), got


# -- the cell by chip and by table: benchmarks/join_by_chip.py ------------------------

def test_the_yardstick_splits_a_job_by_chip_and_table():
    """``join_planes``' two jobs as requeries of two tables: every chip
    4 s busy a job, 1.5 s of it the gathers, one operation."""
    tool = by_chip_tool()
    planes = join_planes()
    planes[-1]["lines"][0]["events"] += [("bench:table0", 0.0, 10.0, {}),
                                         ("bench:table1", 10.0, 18.0, {})]
    lines = list(tool.by_chip(planes))
    # two chips x two tables x (scopes, operations), and a table's spread over the chips
    assert len(lines) == 10
    assert lines[-2:] == ["[by_chip] table=0 materialize_chip_spread=1.0000",
                          "[by_chip] table=1 materialize_chip_spread=1.0000"]
    for chip in (0, 1):
        for table in (0, 1):
            scopes, each = [ln for ln in lines
                            if ln.startswith(f"[by_chip] chip={chip} table={table} ")]
            assert " busy=4.0000 " in scopes
            assert " dryad.join/dryad.join.materialize=1.5000" in scopes
            assert each.endswith(" materialize_ops_ms=1500.00")
    assert tool.CELL == "join-hash-4c"


def test_the_yardstick_runs_on_the_cpu_mesh(monkeypatch, capsys):
    """At 2^17 rows a table on four CPU devices: the requeries and what
    each table's exchange and pair buffer held (no device plane here, so
    no line a chip)."""
    tool = by_chip_tool()
    load = run.load_cell

    def small(name):
        cell = load(name)
        cell.params.update(rows_r=1 << 17, rows_s=1 << 17)
        return cell

    monkeypatch.setattr(tool.R, "load_cell", small)
    monkeypatch.setattr(tool.R, "require_chips", lambda chips: None)
    assert tool.main(["--seed", "4600000045", "--reps", "1"]) == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("[by_chip]")]
    assert [ln.split(" requery_s=")[0] for ln in lines[:2]] == [
        "[by_chip] table=0 rep=0", "[by_chip] table=1 rep=0"]
    for i in (0, 1):
        said, = [ln for ln in lines if ln.startswith(f"[by_chip] table={i} pairs=[")]
        pairs = json.loads(said.split(" pairs=")[1].split(" recv_rows=")[0])
        recv = json.loads(said.split(" recv_rows=")[1])
        assert len(pairs) == len(recv) == 4 and sum(recv) == 2 * 131072  # both exchanges'
        assert 131072 <= sum(pairs) < 131072 + 64  # a pair a probe row, a few collisions


# -- one traced run on the CPU of a cell of the same shape -------------------------

def test_a_traced_cpu_run_of_the_copartitioned_join(tmp_path, monkeypatch, capsys, root):
    """A temp copy with a tiny four-device cell of the new
    configuration's shape (2^17 rows a table: over the default
    ``broadcast_limit`` of 2^16, so ``auto`` exchanges both sides as the
    cell does) for which the four new metrics and the accepted ones that take the cell are listed: the span readers
    read the real program's real drain, and the seven metrics that list
    no cells read the cell as they read every cell.  (The CPU's trace
    has no device plane that carries scopes: the scope reader is silent
    here.)"""
    import importlib.util
    import shutil

    import jax

    from test_run_cpu import cpu_trace_loader

    rows = 1 << 17
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = bench_at(root)
    (tmp_path / "benchmarks" / "configs" / "tiny-join.json").write_text(
        json.dumps({"name": "tiny-join", "chips": 4, "reduced": []}))
    (tmp_path / "benchmarks" / "traffic" / "join-tiny.json").write_text(
        json.dumps({"job": "join_hash", "rows_r": rows, "rows_s": rows,
                    "zipf_theta": 1.05, "alphabet_seed": 54321, "expansion": 1.0,
                    "partitions": 4, "pool": 2}))
    b["configs"].append({
        "name": "tiny-join", "source": "a throwaway of the CPU test",
        "file": "benchmarks/configs/tiny-join.json", "reduced": [], "why": "test"})
    b["workloads"].append({
        "name": "join-tiny", "config": "tiny-join", "traffic": "join-tiny",
        "chips": 4, "why": "test"})
    for m in b["per_layer"]:
        if "join-hash-4c" in m.get("workloads", []):
            m["workloads"] = m["workloads"] + ["join-tiny"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    spec = importlib.util.spec_from_file_location(
        "bench_copy_run_46", tmp_path / "benchmarks" / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setattr(module, "require_chips", lambda chips: jax.devices())
    monkeypatch.setattr(module, "load_peaks", lambda kind: {"hbm_bytes_per_s": 50e9})
    monkeypatch.setattr(TR, "load", cpu_trace_loader)
    PS._of_trace.cache_clear()
    capsys.readouterr()
    rc = module.main(["--workload", "join-tiny", "--seed", "4600000019",
                      "--seconds", "0.3", "--trace", "1"])
    PS._of_trace.cache_clear()
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert SPAN_READERS <= set(metrics) and UNLISTED <= set(metrics)
    assert {"exchange_retries_a_job", "recv_balance", "dispatches_a_job"} <= set(metrics)
    assert FRESH | {"dispatch_s"} <= set(metrics)  # the host's side reads the job too
    assert metrics["ingest_host_bytes_a_row"] == 9.0  # 8 B a row of two tables + validity
    # no scoped device plane on the CPU
    assert not {"copartition_dev_share", "materialize_chip_spread"} & set(metrics)
    assert metrics["window_compiles"] == 0
    assert metrics["exchange_retries_a_job"] == 0.0 and metrics["dispatches_a_job"] == 1.0
    # the sum over both exchanges flattens what the probe side's alone says
    assert 1.0 < metrics["recv_balance"] < metrics["probe_side_balance"]
    # the hottest key is a tenth of the probe side and lands on one chip: what the
    # job file reckons from the alphabet under the engine's hash, to the draw's noise
    job = run.load_module("jobs", "join_hash")
    tiny = {"rows_r": rows, "zipf_theta": 1.05, "alphabet_seed": 54321}
    assert metrics["probe_side_balance"] == pytest.approx(
        job.chip_shares(tiny, 4).max() * 4, rel=0.02)
    assert 1.05 < metrics["probe_side_balance"] < 1.7
    assert metrics["exchange_fill_max"] == pytest.approx(
        metrics["probe_side_balance"] / 2.0)  # 2 shards of room at the default slack
    # 2 shards of slots a chip for a shard's worth of pairs; a few hash collisions
    assert 1.99 < metrics["join_slots_a_pair"] <= 2.0
    for number in sorted(NUMBERS):
        assert any(ln.startswith(f"[bench] check number={number} ")
                   and ln.endswith(" ok=1") for ln in lines), number
    requery = [ln for ln in lines if ln.startswith("[bench] spans kind=bench:requery")]
    assert "dryad:dispatch:input+join+select+aggregate=" in requery[0]
    assert "dryad:readback:drain=" in requery[0]
    assert "dryad:ingest:" not in requery[0]  # both tables stay resident
