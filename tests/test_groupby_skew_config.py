"""The deployment ``dryadlinq-decomp-skew-4c`` as its cell runs it, on
the CPU mesh: ``benchmarks/jobs/groupby_skew.py`` loaded by path, its
``bind(...)`` (``group_by`` through a user-written ``Decomposable``)
collected fresh and again at P = 1 and P = 4 over Zipf keys whose
hottest group spans every shard, against the job's NumPy references
(the one ``make_table`` computes group by group, the sort-free one, a
``lexsort``), the bfloat16 control and the planted faults; the merge's
algebra on the host; and what PR 41 put into the program for the cell:
the counts an exchange reads back with the overflow flag (``drain``
span, ``exchange_observed`` event) and the three
``dryad.group_combine.*`` scopes."""

import importlib.util
import itertools
import os
import re

import numpy as np
import pytest

from dryad_tpu import DryadContext
from test_join_topk_config import lowered_programs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARAMS = {"rows": 1 << 14, "groups": 1 << 10, "zipf_theta": 0.99, "partitions": 4}
EXACT = ["groupby_skew.keys_wrong", "groupby_skew.counts_differ",
         "groupby_skew.rows_uncounted", "groupby_skew.last_ts_differ",
         "groupby_skew.last_v_differ"]
MEAN, VAR = "groupby_skew.mean_err_over_rms", "groupby_skew.var_err_over_ms"
STATE = ["n", "ts", "last", "mean", "m2"]


@pytest.fixture(scope="module")
def job():
    path = os.path.join(ROOT, "benchmarks", "jobs", "groupby_skew.py")
    spec = importlib.util.spec_from_file_location("bench_job_groupby_skew", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def table(job):
    return job.make_table(np.random.default_rng([41, 0]), PARAMS, None, 0)


@pytest.fixture(scope="module")
def runs(job, table):
    """P -> (the fresh job's answer and the requery's, the events)."""
    out = {}
    for P in (1, 4):
        ctx = DryadContext(num_partitions_=P)
        query = job.bind(ctx, table, PARAMS)
        out[P] = ([query.collect(), query.collect()], ctx.events.events())
    return out


def failed_by(checks):
    return {name for name, (value, limit) in checks.items() if value > limit}


def test_the_table_is_the_deployments(job, table):
    k, ts, v = (table["arrays"][c] for c in ("k", "ts", "v"))
    assert (k.dtype, ts.dtype, v.dtype) == (np.int32, np.int32, np.float32)
    assert np.array_equal(np.sort(ts), np.arange(PARAMS["rows"]))
    count = np.bincount(k, minlength=PARAMS["groups"])
    # Zipf 0.99 over 2^10 keys: the hottest key about an eighth of the
    # rows, and its rows on every one of the four shards
    assert 0.10 < count.max() / PARAMS["rows"] < 0.16
    hot = np.flatnonzero(k == np.argmax(count))
    assert set(hot // (PARAMS["rows"] // 4)) == {0, 1, 2, 3}
    assert 0 < np.count_nonzero(count == 0) and 0 < np.count_nonzero(count == 1)
    # the same seed, the same table; another seed, another
    again = job.make_table(np.random.default_rng([41, 0]), PARAMS, None, 0)
    other = job.make_table(np.random.default_rng([41, 1]), PARAMS, None, 0)
    assert all(np.array_equal(table["arrays"][c], again["arrays"][c]) for c in "kv")
    assert not np.array_equal(table["arrays"]["k"], other["arrays"]["k"])


def test_the_three_references_agree(job, table):
    """What ``make_table`` keeps, the sort-free ``reference`` and a
    ``lexsort`` written here from the definitions."""
    arrays, groups = table["arrays"], PARAMS["groups"]
    want, free = table["want"], job.reference(arrays, groups)
    order = np.lexsort((arrays["ts"], arrays["k"]))
    k, v = arrays["k"][order], arrays["v"][order].astype(np.float64)
    last = np.append(np.flatnonzero(k[1:] != k[:-1]), len(k) - 1)
    for key, row in zip(k[last], last):
        mine = v[k == key]
        assert want["count"][key] == len(mine) == free["count"][key]
        assert want["last_ts"][key] == arrays["ts"][order][row] == free["last_ts"][key]
        assert want["last_v"][key] == arrays["v"][order][row] == free["last_v"][key]
        for got in (want, free):
            assert got["mean"][key] == pytest.approx(mine.mean(), rel=1e-12, abs=1e-15)
            assert got["var"][key] == pytest.approx(mine.var(), rel=1e-9, abs=1e-15)
    absent = np.setdiff1d(np.arange(groups), k)
    assert len(absent) and not want["count"][absent].any()
    assert not free["count"][absent].any()


@pytest.mark.parametrize("P", [1, 4])
def test_the_cells_query_against_the_reference(job, table, runs, P):
    answers, events = runs[P]
    for answer in answers:
        assert list(answer) == ["k", "count", "last_ts", "last_v", "mean", "var"]
        assert [answer[c].dtype for c in answer] == [
            np.int32, np.int32, np.int32, np.float32, np.float32, np.float32]
        checks = job.compare(table, answer, PARAMS)
        assert set(checks) == set(EXACT) | {MEAN, VAR}
        assert all(checks[name] == (0, 0) for name in EXACT)
        assert checks[MEAN][1] == job.MEAN_LIMIT and checks[VAR][1] == job.VAR_LIMIT
        # a few f32 roundings, a hundred times under the limits
        assert checks[MEAN][0] < job.MEAN_LIMIT / 50
        assert checks[VAR][0] < job.VAR_LIMIT / 50
    # no bucket overflowed at the default configuration
    assert not [e for e in events if e["kind"] == "stage_overflow"]
    # the keys, counts and latest readings do not depend on the partitions
    order = [np.argsort(a["k"]) for a in (answers[0], runs[1][0][0])]
    for column in ("k", "count", "last_ts", "last_v"):
        assert np.array_equal(answers[0][column][order[0]],
                              runs[1][0][0][column][order[1]])


def test_the_control_and_the_planted_faults(job, table, capsys):
    checks = job.compare(table, job.control(table, PARAMS), PARAMS)
    failed = failed_by(checks)
    # the bits of last_v, and the mean or the variance, by a limit each
    assert "groupby_skew.last_v_differ" in failed and failed & {MEAN, VAR}
    assert not failed & {"groupby_skew.keys_wrong", "groupby_skew.counts_differ",
                         "groupby_skew.rows_uncounted", "groupby_skew.last_ts_differ"}
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[bench] fault job=groupby_skew ")]
    assert len(said) == 4  # the control's run reads the faults too
    faults = job.planted_faults(table, PARAMS)
    assert list(faults) == ["no_cross_term", "unmerged", "last_by_value",
                            "dropped_state"]
    for name, (answer, meant, alone) in faults.items():
        failed = failed_by(job.compare(table, answer, PARAMS))
        assert meant <= failed and (failed == meant or not alone), name
    assert faults["no_cross_term"][1] == {VAR}
    assert faults["unmerged"][1] == {"groupby_skew.keys_wrong"}
    assert faults["last_by_value"][1] == {"groupby_skew.last_v_differ"}
    assert faults["dropped_state"][1] == {"groupby_skew.counts_differ",
                                          "groupby_skew.rows_uncounted"}
    # the reference's own answer passes; a NaN does not
    right = job.answer_of(table["want"])
    assert not failed_by(job.compare(table, right, PARAMS))
    right["mean"][0] = np.nan
    assert failed_by(job.compare(table, right, PARAMS)) == {MEAN}
    # a fault that passes ends the run
    passing = dict(faults, quiet=(job.answer_of(table["want"]), {VAR}, True))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(job, "planted_faults", lambda table, params: passing)
        with pytest.raises(SystemExit, match="quiet"):
            job.say_faults(table, PARAMS)


# -- the merge's algebra, on the host ---------------------------------------------

def states_of(job, arrays, rows):
    """The partial state of each key among ``rows``: every row seeded,
    then merged one at a time by the job's ``merge`` (a left fold, in
    float32 as the program's), key -> state."""
    import jax.numpy as jnp

    cols = {c: jnp.asarray(arrays[c][rows]) for c in ("k", "ts", "v")}
    seeded = {c: np.asarray(a) for c, a in job.seed(cols).items()}
    out = {}
    for i, key in enumerate(arrays["k"][rows]):
        one = {c: seeded[c][i] for c in STATE}
        out[key] = one if key not in out else {
            c: np.asarray(a) for c, a in job.merge(out[key], one).items()}
    return out


@pytest.fixture(scope="module")
def shard_states(job):
    """A table small enough to fold row by row on the host, cut in four
    shards as the chips cut it: the per-shard partial states."""
    params = dict(PARAMS, rows=1 << 10, groups=1 << 6)
    table = job.make_table(np.random.default_rng([41, 2]), params, None, 0)
    shard = params["rows"] // 4
    return params, table, [
        states_of(job, table["arrays"], np.arange(p * shard, (p + 1) * shard))
        for p in range(4)]


def test_the_merge_is_order_free_where_it_is_exact(job, shard_states):
    """The hottest key's four per-chip states merged in all 24 orders
    give one ``n``, ``ts``, ``last``; the moments agree to rounding."""
    params, table, states = shard_states
    hot = int(np.argmax(table["want"]["count"]))
    assert all(hot in s for s in states)
    merged = []
    for order in itertools.permutations(range(4)):
        acc = states[order[0]][hot]
        for p in order[1:]:
            acc = {c: np.asarray(a) for c, a in job.merge(acc, states[p][hot]).items()}
        merged.append(acc)
    assert len(merged) == 24
    for c in ("n", "ts", "last"):
        assert len({m[c].tobytes() for m in merged}) == 1, c
    assert int(merged[0]["n"]) == table["want"]["count"][hot]
    assert int(merged[0]["ts"]) == table["want"]["last_ts"][hot]
    assert merged[0]["last"] == table["want"]["last_v"][hot]
    for c in ("mean", "m2"):
        values = np.array([float(m[c]) for m in merged])
        assert np.ptp(values) <= 1e-5 * np.abs(values).max()


def test_the_per_chip_states_merged_on_the_host_give_the_uncut_answer(job, shard_states):
    """The combiner's share: every key's per-chip partial states,
    merged by ``merge`` and finalized, are the whole table's answer."""
    import jax.numpy as jnp

    params, table, states = shard_states
    whole = {}
    for shard in states:
        for key, state in shard.items():
            whole[key] = state if key not in whole else {
                c: np.asarray(a) for c, a in job.merge(whole[key], state).items()}
    keys = sorted(whole)
    cols = {c: jnp.asarray(np.stack([whole[k][c] for k in keys])) for c in STATE}
    cols["k"] = jnp.asarray(np.asarray(keys, np.int32))
    answer = {c: np.asarray(a) for c, a in job.finalize(cols).items()}
    checks = job.compare(table, answer, params)
    assert not failed_by(checks) and all(checks[name] == (0, 0) for name in EXACT)
    # more states than keys went in: the hottest keys lay on every chip
    assert sum(len(s) for s in states) > 2 * len(keys)


# -- what the program says of the exchange, and its scopes --------------------------

def test_the_exchange_says_what_it_saw_at_four_partitions_and_nothing_at_one(table, runs):
    rows = PARAMS["rows"]
    answers, events = runs[4]
    seen = [e for e in events if e["kind"] == "exchange_observed"]
    drains = [e for e in events if e["kind"] == "span" and e["name"] == "drain"]
    assert len(seen) == len(drains) == 2  # one a dispatch, a fresh job and a requery
    present = len(answers[0]["k"])
    for event, drain in zip(seen, drains):
        assert event["name"] == "input+group_by" and event["exchanges"] == 1
        assert event["boost"] == 1 and event["overflows"] == 0
        assert event["combine_rows_in"] == rows
        # the combiner left a row a key a chip: fewer than the rows, no
        # fewer than the keys, and the chips received just those
        assert present <= event["combine_rows_out"] < rows
        assert len(event["recv_rows"]) == 4
        assert sum(event["recv_rows"]) == event["combine_rows_out"]
        assert max(event["recv_rows"]) * 4 < 2.0 * event["combine_rows_out"]
        for field in ("combine_rows_in", "combine_rows_out", "recv_rows", "boost",
                      "overflows", "exchanges"):
            assert drain[field] == event[field], field
        assert drain["recv_rows_max"] == max(event["recv_rows"])
        assert drain["inflight"] == 1 and drain["cat"] == "readback"
    # the counts ride the one readback: the stage's own chips' sums
    shard = rows // 4
    per_chip = sum(len(np.unique(table["arrays"]["k"][p * shard:(p + 1) * shard]))
                   for p in range(4))
    assert seen[0]["combine_rows_out"] == per_chip
    # one partition: no exchange, nothing observed, the drain as it was
    _, events = runs[1]
    assert not [e for e in events if e["kind"] == "exchange_observed"]
    drains = [e for e in events if e["kind"] == "span" and e["name"] == "drain"]
    assert len(drains) == 2
    assert not any("combine_rows_in" in d or "recv_rows" in d for d in drains)


@pytest.mark.parametrize("P", [1, 4])
def test_the_stage_program_splits_the_combiner_in_three(job, table, monkeypatch, P):
    from dryad_tpu.parallel import stage

    program, = lowered_programs(job, monkeypatch, table, PARAMS, P)
    assert stage.PROGRAM_NAME == "dryad_stage_3"
    assert f"module @jit_{stage.PROGRAM_NAME} " in program.as_text()
    paths = re.findall(r'op_name="([^"]*)"', program.compile().as_text())
    under = "/dryad.group_combine/"
    for scope in ("layout", "scan", "emit"):
        assert any(f"{under}dryad.group_combine.{scope}/" in p + "/" for p in paths), scope
    assert any(f"{under}dryad.group_combine.layout/dryad.sort.carry/" in p for p in paths)
    # the builtin combiner's names are not the user's: none is here
    assert not [p for p in paths if "dryad.group_reduce" in p]
    # everything of the combiner lies in one of the three
    inside = [p for p in paths if under in p + "/"]
    assert inside and all("/dryad.group_combine/dryad.group_combine." in p
                          for p in inside)
    exchanged = [p for p in paths if "dryad.exchange_hash" in p]
    assert bool(exchanged) == (P == 4)


@pytest.mark.parametrize("cell", ["groupby-4c", "groupby-skew-4c"])
def test_the_program_at_four_partitions_holds_three_sorts(job, table, monkeypatch, cell):
    """The two group-by cells' P = 4 programs since PR 48: the combiner's
    sort, the bucket layout's and the final fold's, THREE where the
    parent had four, and nothing under ``dryad.resize``: the fold that
    reads the received slots sorts valid rows first itself
    (``exec/kernels.py::_reader_sorts``), so the ``resize`` between
    them counts and does not sort; the job says so (``resize_sorts``)."""
    if cell == "groupby-4c":
        path = os.path.join(ROOT, "benchmarks", "jobs", "groupby.py")
        spec = importlib.util.spec_from_file_location("bench_job_groupby", path)
        job = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(job)
        params = {"rows": 1 << 14, "groups": 1 << 10}
        table = job.make_table(np.random.default_rng([48, 0]), params, None, 0)
    else:
        params = PARAMS
    ctx = DryadContext(num_partitions_=4)
    program, = lowered_programs(job, monkeypatch, table, params, 4, ctx)
    assert len(re.findall(r"stablehlo\.sort", program.as_text())) == 3
    paths = re.findall(r'loc\("([^"]*dryad\.[^"]*)"', program.as_text(debug_info=True))
    assert any("dryad.exchange.layout/dryad.sort.carry" in p for p in paths)
    assert not [p for p in paths if "dryad.resize" in p]
    drain, = [e for e in ctx.events.events() if e["kind"] == "span" and e["name"] == "drain"]
    assert drain["exchanges"] == 1 and drain["resize_sorts"] == 0


def test_the_builtin_combiners_scopes_stay_what_they_were(monkeypatch):
    """``group_reduce`` keeps ``dryad.group_reduce.layout`` and
    ``.fold``: ``_segment_layout``'s scope is its caller's."""
    import jax

    from dryad_tpu.columnar.batch import ColumnBatch
    from dryad_tpu.ops.segmented import AggSpec, group_reduce

    batch = ColumnBatch({"k": np.arange(64, dtype=np.int32) % 5,
                         "v": np.ones(64, np.float32)}, np.ones(64, bool))
    text = jax.jit(lambda b: group_reduce(b, ["k"], [AggSpec("sum", "v", "s")])).lower(
        batch).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    assert any("dryad.group_reduce.layout/" in p for p in paths)
    assert any("dryad.group_reduce.fold/" in p for p in paths)
    assert not [p for p in paths if "group_combine" in p]
