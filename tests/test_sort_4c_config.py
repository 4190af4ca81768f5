"""The deployment ``dryadlinq-sort-4c`` as its cell runs it, on the CPU
mesh: ``benchmarks/jobs/sort.py`` loaded by path, its ``bind(...)``
collected fresh and again through ``DryadContext`` at P = 1, 4 and 8 on
the inputs that stress a range partition, against ``np.sort`` +
``key_payload`` (the plain reference), the job's own ``compare`` and
the P = 1 answer bit for bit; then what the PR that added the cell put
into the program for it: how evenly the splitters cut the answer on
the ``decode`` span, the exchange's ICI bytes on the ``dispatch`` span,
and a stage program whose name and scopes are the parent's."""

import importlib.util
import os
import re

import jax
import numpy as np
import pytest

from dryad_tpu import DryadContext
from dryad_tpu.plan.xchgplan import flat_accounting
from dryad_tpu.utils.config import DryadConfig
from test_join_topk_config import lowered_programs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 1 << 13
INT32_MIN, INT32_MAX = -(2**31), 2**31 - 1


@pytest.fixture(scope="module")
def job():
    path = os.path.join(ROOT, "benchmarks", "jobs", "sort.py")
    spec = importlib.util.spec_from_file_location("bench_job_sort", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def uniform(rng):
    return rng.integers(INT32_MIN, 2**31, ROWS, dtype=np.int64).astype(np.int32)


def heavy_key(rng):
    """Nine rows in ten on one key: only the spread word can cut it."""
    key = uniform(rng)
    key[rng.random(ROWS) < 0.9] = 7
    return key


def with_both_ends(rng):
    key = uniform(rng)
    key[:64] = INT32_MIN
    key[64:128] = INT32_MAX
    return rng.permutation(key)


# name -> (keys from a generator, shuffle_slack)
SHAPES = {
    "uniform": (uniform, 2.0),
    "all_keys_equal": (lambda rng: np.full(ROWS, -5, np.int32), 2.0),
    "heavy_key": (heavy_key, 2.0),
    "sorted": (lambda rng: np.sort(uniform(rng)), 2.0),
    "reverse_sorted": (lambda rng: np.sort(uniform(rng))[::-1].copy(), 2.0),
    "both_ends_present": (with_both_ends, 2.0),
    "slack_1_overflows": (uniform, 1.0),
}
CASES = [(shape, P) for shape in SHAPES for P in (1, 4, 8)]
# The boosts of the fresh job's dispatches and then the requery's, where
# not one dispatch each.  A send bucket holds slack / P of a shard: at
# slack 1.0 the fullest bucket of a random cut is over it; of a sorted
# table every shard lies inside one range and goes to one chip whole,
# whatever the splitters, so the job doubles its room until a bucket
# holds a shard (the second job at P = 8 starts where the first ended:
# the rewriter's floor).
BOOSTS = {
    ("slack_1_overflows", 4): [1, 2, 1, 2], ("slack_1_overflows", 8): [1, 2, 1, 2],
    ("sorted", 4): [1, 2, 1, 2], ("sorted", 8): [1, 2, 4, 4],
    ("reverse_sorted", 4): [1, 2, 1, 2], ("reverse_sorted", 8): [1, 2, 4, 4],
}
ONE_PARTITION = {}  # shape -> the P = 1 answer


def the_table(job, shape):
    key = SHAPES[shape][0](np.random.default_rng([30, len(shape)]))
    return {"arrays": {"key": key, "payload": job.key_payload(key)},
            "want_key": np.sort(key)}


def collect_twice(job, shape, P):
    """The cell's query, a fresh job and a requery; the answers and the
    context's events."""
    table = the_table(job, shape)
    ctx = DryadContext(
        num_partitions_=P, config=DryadConfig(shuffle_slack=SHAPES[shape][1]))
    query = job.bind(ctx, table, {"rows": ROWS})
    return table, [query.collect(), query.collect()], ctx.events.events()


def spans(events, **match):
    return [e for e in events if e["kind"] == "span"
            and all(e.get(k) == v for k, v in match.items())]


@pytest.mark.parametrize("shape,P", CASES)
def test_the_cells_query_is_exact(job, shape, P):
    table, answers, events = collect_twice(job, shape, P)
    if shape not in ONE_PARTITION:
        ONE_PARTITION[shape] = (answers[0] if P == 1
                                else collect_twice(job, shape, 1)[1][0])
    want_key = np.sort(table["arrays"]["key"])
    for answer in answers:
        assert answer["key"].dtype == np.int32 and answer["payload"].dtype == np.float32
        assert np.array_equal(answer["key"], want_key)
        assert np.array_equal(answer["payload"], job.key_payload(want_key))
        checks = job.compare(table, answer, {"rows": ROWS})
        assert set(checks) == {"sort.rows_missing", "sort.keys_out_of_order",
                               "sort.payloads_off_key"}
        assert all(value == 0 and limit == 0 for value, limit in checks.values())
        for column, one in ONE_PARTITION[shape].items():
            assert answer[column].tobytes() == one.tobytes()
    control = job.control(table, {"rows": ROWS})
    assert job.compare(table, control, {"rows": ROWS})["sort.payloads_off_key"][0] > 0

    # the decode span says how the splitters cut the answer
    decoded = spans(events, name="decode")
    assert len(decoded) == 2
    for e in decoded:
        assert e["rows"] == ROWS and e["shards"] == P
        assert e["shard_rows_min"] <= ROWS // P <= e["shard_rows_max"]
        balance = e["shard_rows_max"] * e["shards"] / e["rows"]
        assert 1.0 <= balance < 2.0  # what the cell's metric reads
        if P == 1:
            assert balance == 1.0

    # the dispatch span says what a chip put on the ICI, as the
    # exchange_round events do; a retry is one more dispatch, at boost > 1.
    # On one partition the exchange is not traced: no round, no byte, no
    # slack on the answer's capacity, and the span says one was skipped.
    dispatched = spans(events, cat="execute")
    rounds = [e for e in events if e["kind"] == "exchange_round"]
    if P == 1:
        assert not rounds
        assert [e["capacity"] for e in decoded] == [ROWS, ROWS]
    else:
        assert [e["xchg_ici_bytes"] for e in dispatched] == [r["ici_bytes"] for r in rounds]
    assert [e["xchg_elided"] for e in dispatched] == [int(P == 1)] * len(dispatched)
    boosts = [e["boost"] for e in dispatched]
    assert boosts == BOOSTS.get((shape, P), [1, 1])
    overflows = [e for e in events if e["kind"] == "stage_overflow"]
    assert len(overflows) == sum(b > a for a, b in zip(boosts, boosts[1:]))
    for e in dispatched:
        slack = SHAPES[shape][1] * e["boost"]
        bucket = min(ROWS // P, max(8, -(-int(ROWS // P * slack) // P)))
        assert e["xchg_ici_bytes"] == flat_accounting(P, 1, bucket, 9)["ici_bytes"]
        assert (e["xchg_ici_bytes"] == 0) == (P == 1)


def test_the_stage_program_is_the_parents(job, monkeypatch):
    """The P = 4 program of the cell's query holds the range exchange's
    ``all_to_all`` under ``dryad.exchange.collective`` and the sample's
    gather over the mesh under ``dryad.sort.splitters``.  The program's
    name, which is in jax's compilation cache key, counts the
    generations of scopes (``parallel/stage.py``): PR 30 added none and
    kept ``dryad_stage_2``; PR 41's ``dryad.group_combine.*`` made it
    ``dryad_stage_3``.  Since PR 48 nothing lies under ``dryad.resize``:
    the ``local_sort`` that reads the slot next puts valid rows first
    itself, so the program holds one sort fewer (the sample's on a chip,
    the elected sample's, the bucket layout's and the ``local_sort``'s:
    four where the parent had five, three of them over a chip's rows)."""
    from dryad_tpu.parallel import stage

    program, = lowered_programs(
        job, monkeypatch, the_table(job, "uniform"), {"rows": ROWS}, 4)
    assert stage.PROGRAM_NAME == "dryad_stage_3"
    assert "module @jit_dryad_stage_3 " in program.as_text()
    paths = re.findall(r'op_name="([^"]*)"', program.compile().as_text())
    under = "/dryad.exchange_range/"
    assert any(under + "dryad.exchange.collective/all_to_all" in p for p in paths)
    assert any(under + "dryad.sort.splitters/all_gather" in p for p in paths)
    assert any(under + "dryad.sort.splitters/dryad.sort.carry/" in p for p in paths)
    assert any(under + "dryad.exchange.layout/dryad.sort.carry/" in p for p in paths)
    assert not [p for p in paths if "dryad.resize" in p]
    assert any("/dryad.local_sort/dryad.sort.carry/" in p for p in paths)
    assert len(re.findall(r"stablehlo\.sort", program.as_text())) == 4
    lowered = re.findall(r'loc\("([^"]*dryad\.[^"]*)"', program.as_text(debug_info=True))
    assert lowered and not [p for p in lowered if "dryad.resize" in p]


def test_the_one_chip_program_is_the_local_sort_alone(job, monkeypatch):
    """``sort-1c``'s program, the same query at P = 1: the exchange and
    its ``resize`` trace nothing there, so every operation is the
    ``local_sort``'s and the answer has the slots the table had."""
    program, = lowered_programs(
        job, monkeypatch, the_table(job, "uniform"), {"rows": ROWS}, 1)
    assert "module @jit_dryad_stage_3 " in program.as_text()
    compiled = program.compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', compiled)
    assert any("/dryad.local_sort/dryad.sort.carry/" in p for p in paths)
    assert not [p for p in paths if "dryad.exchange" in p or "dryad.resize" in p
                or "dryad.sort.splitters" in p]
    assert "scatter" not in compiled and "all-to-all" not in compiled
    # no slack: the answer has the slots the table had
    slots = {leaf.shape[0] for info in (program.args_info, program.out_info)
             for leaf in jax.tree_util.tree_leaves(info) if leaf.shape}
    assert slots == {ROWS}
