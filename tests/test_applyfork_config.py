"""The deployment ``dryadlinq-applyfork-1c`` as its cell runs it, on the
CPU mesh: ``benchmarks/jobs/applyfork.py`` loaded by path, its
``bind(...)`` (an ``apply``, a ``fork``, three pipelines, ONE job:
``DryadContext.collect_many``) collected fresh and again at P = 1 and 4
against the job's own NumPy reference and compare; the one job against
three separate ``collect()``s; the graph, the dispatches and the spans
of a job with several outputs; and ``collect_many`` of one query against
``Query.collect()``."""

import importlib.util
import os

import numpy as np
import pytest

import dryad_tpu.columnar.batch as batch_mod
from dryad_tpu import DryadConfig, DryadContext
from dryad_tpu.exec.failure import StageFailedError
from dryad_tpu.plan.lower import lower

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 1 << 14
PARAMS = {"rows": ROWS, "hot_eighths": 3}
NUMBERS = {
    "applyfork.rows_missing", "applyfork.hot_keys_out_of_order",
    "applyfork.hot_scores_off_key", "applyfork.hot_rows_misrouted",
    "applyfork.rest_rows_off", "applyfork.tee_count_off",
    "applyfork.tee_sum_err_over_tol",
}


@pytest.fixture(scope="module")
def job():
    path = os.path.join(ROOT, "benchmarks", "jobs", "applyfork.py")
    spec = importlib.util.spec_from_file_location("bench_job_applyfork", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def the_table(job, seed=0):
    return job.make_table(np.random.default_rng([38, seed]), PARAMS, None, 0)


def passes(checks):
    assert set(checks) == NUMBERS
    return all(value <= limit for value, limit in checks.values())


def spans(ctx, since=0):
    return [e for e in ctx.events.events()[since:] if e.get("kind") == "span"]


def named(events, name):
    return [e for e in events if e["name"] == name]


def same_bytes(got, want):
    assert list(got) == list(want)
    for name in want:
        assert got[name].dtype == want[name].dtype, name
        assert got[name].tobytes() == want[name].tobytes(), name


# -- the job file with NumPy alone ------------------------------------------------

def test_the_reference_takes_nothing_from_the_program_and_passes(job):
    with open(job.__file__) as fh:
        source = fh.read()
    # the program's types are named in ``bind`` alone (an ``apply`` and
    # a ``fork`` state their schemas); the reference is NumPy
    assert source.count("dryad_tpu") == 1 and "import jax" not in source
    table = the_table(job)
    again = the_table(job)
    assert table["arrays"]["key"].tobytes() == again["arrays"]["key"].tobytes()
    key = table["arrays"]["key"]
    hot = (key & 7) < 3
    a, b, c = job.reference(table["arrays"], PARAMS)
    assert a["key"].tolist() == sorted(key[hot].tolist())
    assert b["key"].tolist() == key[~hot].tolist()  # the table's order
    assert len(a["key"]) + len(b["key"]) == ROWS and int(c["n"][0]) == hot.sum()
    # the score is exact in f32 whichever way it is rounded
    payload = job.key_payload(a["key"])
    assert a["score"].tolist() == (payload.astype(np.float64) * 0.5 + 1).astype(
        np.float32).tolist()
    checks = job.compare(table, (a, b, {"n": c["n"], "t": c["t"].astype(np.float32)}),
                         PARAMS)
    assert passes(checks)
    assert all(v == 0 for n, (v, _) in checks.items() if not n.endswith("over_tol"))
    assert job.input_rows(PARAMS) == ROWS and job.min_bytes(PARAMS) == 16 * ROWS


def test_the_control_fails_and_so_does_every_kind_of_wrong_answer(job):
    table = the_table(job)
    checks = job.compare(table, job.control(table, PARAMS), PARAMS)
    assert not passes(checks)
    assert checks["applyfork.hot_scores_off_key"][0] > 0.9 * len(table["want_hot_key"])
    assert checks["applyfork.hot_keys_out_of_order"][0] == 0
    assert checks["applyfork.rest_rows_off"][0] == 0

    def answer():
        a, b, c = job.reference(table["arrays"], PARAMS)
        return a, b, {"n": c["n"], "t": c["t"].astype(np.float32)}

    a, b, c = answer()
    b["key"][[3, 4]] = b["key"][[4, 3]]  # B out of the table's order
    b["payload"][[3, 4]] = b["payload"][[4, 3]]
    assert job.compare(table, (a, b, c), PARAMS)["applyfork.rest_rows_off"] == (2, 0)
    a, b, c = answer()
    a["key"][7] = b["key"][0]  # a row of A that fails the predicate
    got = job.compare(table, (a, b, c), PARAMS)
    assert got["applyfork.hot_rows_misrouted"] == (1, 0)
    assert got["applyfork.hot_keys_out_of_order"] == (1, 0)
    a, b, c = answer()
    b["payload"][[3, 4]] = b["payload"][[4, 3]]  # two payloads swapped, keys in place
    assert job.compare(table, (a, b, c), PARAMS)["applyfork.rest_rows_off"] == (2, 0)
    a, b, c = answer()
    a["score"][[5, 6]] = a["score"][[6, 5]]  # two scores off their keys
    got = job.compare(table, (a, b, c), PARAMS)
    assert [n for n, (v, lim) in got.items() if v > lim] == [
        "applyfork.hot_scores_off_key"]
    assert got["applyfork.hot_scores_off_key"] == (2, 0)
    a, b, c = answer()
    c["n"][0] += 1
    assert job.compare(table, (a, b, c), PARAMS)["applyfork.tee_count_off"] == (1, 0)
    a, b, c = answer()
    c["t"][0] = np.nan
    assert job.compare(table, (a, b, c), PARAMS)[
        "applyfork.tee_sum_err_over_tol"][0] == np.inf
    # a fault planted on the Tee's sum alone is caught by its limit
    # alone: the limit is relative, a few roundings wide
    for name, wrong in job.wrong_sums(table, PARAMS).items():
        got = job.compare(table, wrong, PARAMS)
        assert [n for n, (v, lim) in got.items() if v > lim] == [
            "applyfork.tee_sum_err_over_tol"], name
        assert got["applyfork.tee_sum_err_over_tol"][0] > 100, name
    assert table["tol"] < 1e-5 * table["want_t"]
    a, b, c = answer()
    short = {"key": a["key"][:-2], "score": a["score"][:-2]}
    assert job.compare(table, (short, b, c), PARAMS) == {
        "applyfork.rows_missing": (2, 0)}
    assert not passes({**dict.fromkeys(NUMBERS, (0, 0)),
                       **job.compare(table, a, PARAMS)})  # one table, not three


def test_the_control_reads_the_planted_faults_too(job, monkeypatch, capsys):
    """``benchmarks/limits.py`` reads a cell's limits through ``control``
    alone, so ``control`` puts the faults planted on the sum through
    ``compare`` as well: a line each, and no answer at all once a fault
    is let through or is caught by another number."""
    table = the_table(job)
    job.control(table, PARAMS)
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[bench] fault job=applyfork ")]
    assert [ln.split()[3] for ln in said] == [
        "planted=wrong_column", "planted=dropped_block", "planted=zero"]
    for ln in said:
        fields = dict(f.split("=", 1) for f in ln.split()[2:])
        assert fields["not_correct_by"] == fields["number"] == job.SUM
        assert float(fields["value"]) > 100 and fields["limit"] == "1.0"
    # the order-free bound (n + 8) * 2^-23 * t came to 1.5 t at 12.58 M
    # rows (PR 37) and would have let all three through
    loose = {**table, "tol": 1.5 * table["want_t"]}
    with pytest.raises(SystemExit, match="'wrong_column' must come out not correct"):
        job.control(loose, PARAMS)
    sound = job.compare

    def by_the_count_as_well(table, answer, params):
        checks = sound(table, answer, params)
        return {**checks, "applyfork.tee_count_off": (1, 0)}

    monkeypatch.setattr(job, "compare", by_the_count_as_well)
    with pytest.raises(SystemExit, match="alone; it did by"):
        job.control(table, PARAMS)


# -- the program against the reference ------------------------------------------------

def by_row(table):
    order = np.lexsort((table["payload"], table["key"]))
    return {name: col[order] for name, col in table.items()}


@pytest.mark.parametrize("P", [1, 4])
def test_bind_against_the_jobs_own_reference(job, P):
    ctx = DryadContext(num_partitions_=P)
    table = the_table(job, seed=P)
    bound = job.bind(ctx, table, PARAMS)
    for answer in (bound.collect(), bound.collect()):  # fresh, then requery
        assert isinstance(answer, tuple) and len(answer) == 3
        if P > 1:
            # a branch no operator reorders keeps its PARTITION's
            # order: across partitions B is compared as a multiset
            table = {**table, "want_rest": by_row(table["want_rest"])}
            answer = (answer[0], by_row(answer[1]), answer[2])
        checks = job.compare(table, answer, PARAMS)
        assert passes(checks), checks
        assert all(v == 0 for n, (v, _) in checks.items()
                   if not n.endswith("over_tol"))
        # a few roundings of the f32 sum: far inside the limit
        assert checks["applyfork.tee_sum_err_over_tol"][0] < 0.1


def test_a_program_without_the_entry_point_leaves_at_once(job):
    class Parent:  # DryadContext before PR 38
        pass

    with pytest.raises(SystemExit, match="no job of several outputs"):
        job.bind(Parent(), the_table(job), PARAMS)


@pytest.mark.parametrize("P", [1, 4])
def test_the_one_job_equals_three_collects_byte_for_byte(job, P):
    table = the_table(job, seed=10 + P)
    one = job.bind(DryadContext(num_partitions_=P), table, PARAMS)
    three = job.bind(DryadContext(num_partitions_=P), table, PARAMS)
    answers = one.collect()
    for got, query in zip(answers, three.queries):
        same_bytes(got, query.collect())
    # and again, from the device cache
    for got, want in zip(one.collect(), answers):
        same_bytes(got, want)


# -- the graph and its dispatches -------------------------------------------------------

def test_the_three_roots_share_one_fork_stage(job):
    ctx = DryadContext(num_partitions_=1)
    bound = job.bind(ctx, the_table(job), PARAMS)
    graph = lower([q.node for q in bound.queries], ctx.config, ctx.dictionary, P=1)
    forks = [s for s in graph.stages if any(op.kind == "fork" for op in s.ops)]
    assert len(forks) == 1
    (stage,) = forks
    assert [op.kind for op in stage.ops] == ["apply", "fork"]
    # its outputs are slots the stage allocates: the next after its input
    assert stage.out_slots == [1, 2] == stage.ops[-1].params["out_slots"]
    consumers = [ref for s in graph.stages for ref in s.input_refs
                 if ref[0] == stage.id]
    consumers += [ref for ref in graph.outputs.values() if ref[0] == stage.id]
    # hot feeds the sort and the Tee's fold, rest is an answer as it stands
    assert sorted(consumers) == [(stage.id, 0), (stage.id, 0), (stage.id, 1)]
    assert len(graph.outputs) == 3 and len(graph.inputs) == 1


@pytest.mark.parametrize("fuse", [True, False])
def test_the_shared_stage_is_traced_and_run_once(job, fuse):
    traced = []

    def score_fn(batch):
        traced.append(batch.capacity)
        return job.score_fn(batch)

    ctx = DryadContext(num_partitions_=1, config=DryadConfig(plan_fuse=fuse))
    table = the_table(job, seed=int(fuse))
    base = ctx.from_arrays(table["arrays"]).apply(score_fn)
    hot, rest = base.fork(job.split_fn(3), [
        q.schema for q in job.bind(ctx, table, PARAMS).queries[:2]])
    group = [
        hot.order_by(["key"]), rest,
        hot.aggregate_as_query({"n": ("count", None), "t": ("sum", "score")})]
    assert passes(job.compare(table, ctx.collect_many(group), PARAMS))
    assert passes(job.compare(table, ctx.collect_many(group), PARAMS))
    assert len(traced) == 1  # one trace for two jobs of three answers
    dispatched = [e["name"] for e in spans(ctx) if e.get("cat") == "execute"]
    if fuse:  # the whole DAG is one program
        assert dispatched == ["input+apply+fork+order_by+aggregate"] * 2
    else:  # a dispatch a stage, the shared one once a job
        assert sorted(dispatched) == sorted(
            ["input+apply+fork", "order_by", "aggregate"] * 2)


# -- the spans of a job with several outputs -----------------------------------------------

def test_the_spans_of_a_multi_output_job(job, monkeypatch):
    monkeypatch.setattr(batch_mod, "TRIM_MIN_BYTES", 1 << 10)
    ctx = DryadContext(num_partitions_=1)
    bound = job.bind(ctx, the_table(job), PARAMS)
    bound.collect()
    fresh = spans(ctx)
    mark = len(ctx.events.events())
    hot_rows = len(bound.collect()[0]["key"])
    requery = spans(ctx, mark)
    (collect,) = named(fresh, "collect")
    assert collect["outputs"] == 3
    (lowered,) = named(fresh, "lower")
    assert lowered["roots"] == 3 and lowered["stages"] == 3
    assert len(named(fresh, "fuse")) == len(named(fresh, "drain")) == 1
    assert len(named(fresh, "encode")) == len(named(fresh, "bind")) == 1
    assert len([e for e in fresh if e.get("cat") == "execute"]) == 1
    for name in ("fetch_wait", "fetch_copy", "decode"):
        assert [e["output"] for e in named(fresh, name)] == [0, 1, 2], name
    # the Tee's one row is under any gate: copied without asking
    assert [e["output"] for e in named(fresh, "fetch_trim")] == [0, 1]
    assert [e["output"] for e in named(fresh, "drop")] == [0, 0, 1, 1, 2, 2]
    # everything of the job hangs off the one root
    ids = {e["span_id"] for e in fresh}
    assert all(e["parent_id"] in ids for e in fresh if e is not collect)
    # the sorted branch is a trimmed copy and a slice, the branch that
    # is passed through lies scattered over its capacity: copied whole
    trims = named(fresh, "fetch_trim")
    decodes = named(fresh, "decode")
    assert (trims[0]["trimmed"], trims[1]["trimmed"]) == (1, 0)
    assert trims[0]["extent_max"] == trims[0]["count"] == hot_rows
    assert trims[1]["count"] == ROWS - hot_rows < trims[1]["extent_max"]
    assert hot_rows <= decodes[0]["fetched"] < 1.2 * hot_rows
    assert decodes[1]["fetched"] == decodes[1]["capacity"] == ROWS
    assert [d["rows"] for d in decodes] == [hot_rows, ROWS - hot_rows, 1]
    # one release before the first fetch, one last of all; the order
    order = [e["name"] for e in sorted(fresh, key=lambda e: e["span_id"])
             if e["name"] in ("lower", "drain", "release", "fetch_wait", "drop")]
    assert order == (["lower", "drain", "release"]
                     + ["fetch_wait", "drop", "drop"] * 3 + ["release"])
    # the requery binds from the device cache: no ingest, no release
    assert not [e for e in requery if e.get("cat") == "ingest"]
    assert named(requery, "collect")[0]["outputs"] == 3


def shape_of(events):
    """What a reader of spans sees: name, category, the names of the
    fields, and how the spans nest."""
    by_id = {e["span_id"]: e for e in events}
    return [
        (e["name"], e.get("cat"),
         tuple(sorted(k for k in e if k not in ("ts", "seq", "qid"))),
         by_id.get(e["parent_id"], {}).get("name"))
        for e in sorted(events, key=lambda e: e["span_id"])
    ]


def test_collect_many_of_one_query_is_collect(job):
    table = the_table(job)["arrays"]
    runs = []
    for how in ("collect", "collect_many"):
        ctx = DryadContext(num_partitions_=4)
        query = ctx.from_arrays(table).order_by(["key"])
        for _ in range(2):  # fresh, then requery
            mark = len(ctx.events.events())
            out = query.collect() if how == "collect" else ctx.collect_many([query])
            runs.append((out, spans(ctx, mark)))
    (a1, s1), (a2, s2), (b1, t1), (b2, t2) = runs
    assert isinstance(b1, tuple) and len(b1) == 1
    same_bytes(b1[0], a1)
    same_bytes(b2[0], a2)
    assert shape_of(s1) == shape_of(t1) and shape_of(s2) == shape_of(t2)
    (root,) = named(s1, "collect")
    assert root["outputs"] == 1 and named(s1, "lower")[0]["roots"] == 1
    assert {e["output"] for e in s1 if "output" in e} == {0}
    assert len(named(s1, "release")) == 2 and not named(s2, "release")


def test_the_asynchronous_forms_are_the_same_job(job):
    """``run_many_to_host_async`` (and ``run_to_host_async``, its form of
    one query) lower, bind and fetch by the code ``collect_many`` runs:
    one ``lower`` span with the roots, the same tables, ``output`` on
    the spans of each fetch."""
    table = the_table(job, seed=5)
    ctx = DryadContext(num_partitions_=1)
    bound = job.bind(ctx, table, PARAMS)
    want = bound.collect()
    mark = len(ctx.events.events())
    fetches = ctx.run_many_to_host_async(bound.queries)
    for got, wanted in zip([fetch() for fetch in fetches], want):
        same_bytes(got, wanted)
    after = spans(ctx, mark)
    assert [e["roots"] for e in named(after, "lower")] == [3]
    assert len([e for e in after if e.get("cat") == "execute"]) == 1
    assert [e["output"] for e in named(after, "decode")] == [0, 1, 2]
    mark = len(ctx.events.events())
    same_bytes(ctx.run_to_host_async(bound.queries[1])(), want[1])
    after = spans(ctx, mark)
    assert [e["roots"] for e in named(after, "lower")] == [1]
    assert [e["output"] for e in named(after, "decode")] == [0]


def test_collect_many_takes_queries_of_one_context(job):
    table = the_table(job)["arrays"]
    ctx, other = DryadContext(num_partitions_=1), DryadContext(num_partitions_=1)
    with pytest.raises(ValueError, match="ONE context"):
        ctx.collect_many([ctx.from_arrays(table), other.from_arrays(table)])
    with pytest.raises(ValueError, match="at least one"):
        ctx.collect_many([])


def test_local_debug_runs_the_outputs_one_by_one(job):
    table = the_table(job)
    dbg = DryadContext(local_debug=True)
    answers = job.bind(dbg, table, PARAMS).collect()
    assert passes(job.compare(table, answers, PARAMS))
    assert named(spans(dbg), "collect")[0]["outputs"] == 3


def test_a_dictionary_miss_in_any_output_raises_before_any_table(rng):
    ctx = DryadContext(num_partitions_=4)
    fine = ctx.from_arrays({"key": rng.integers(0, 99, 500).astype(np.int32)})
    arrays = {"k": rng.integers(0, 20, 400).astype(np.int32)}
    dense = ctx.from_arrays(arrays).group_by("k", {"c": ("count", None)})
    arrays["k"][:] = arrays["k"] + 100  # outside the range the ingest saw
    mark = len(ctx.events.events())
    with pytest.raises(StageFailedError, match="ingest-time range"):
        ctx.collect_many([fine.order_by(["key"]), dense])
    after = spans(ctx, mark)
    # the miss is the SECOND output's; it rode the first fetch, and no
    # answer was decoded, let alone handed out
    assert [e["output"] for e in named(after, "fetch_copy")] == [0]
    assert not named(after, "decode")
    assert named(after, "collect")[0]["outputs"] == 2


# -- a fork is held to its schemas where it is traced ----------------------------------

def test_a_wrong_fork_fails_where_it_is_traced_and_names_the_output(job):
    table = the_table(job)["arrays"]
    schemas = [q.schema for q in job.bind(
        DryadContext(num_partitions_=1), the_table(job), PARAMS).queries[:2]]

    def forked(split):
        ctx = DryadContext(num_partitions_=1)
        base = ctx.from_arrays(table).apply(job.score_fn)
        hot, rest = base.fork(split, schemas)
        # the first consumer is a sort over "key": a wrong column would
        # otherwise fail there, or not at all
        return job.OneJob(ctx, [hot.order_by(["key"]), rest])

    def swapped(batch):
        hot, rest = job.split_fn(3)(batch)
        return rest, hot

    def wider(batch):
        hot, rest = job.split_fn(3)(batch)
        return hot, rest.with_column("key", rest["key"].astype("int8"))

    def cut(batch):
        from dryad_tpu import ColumnBatch

        hot, rest = job.split_fn(3)(batch)
        half = batch.capacity // 2
        return hot, ColumnBatch({n: a[:half] for n, a in rest.data.items()},
                                rest.valid[:half])

    with pytest.raises(ValueError, match=r"fork output 0 has columns .*payload.*"
                                         r"out_schemas\[0\]\) says .*score"):
        forked(swapped).collect()
    with pytest.raises(ValueError, match=r"fork output 1 has columns .*int8"):
        forked(wider).collect()
    with pytest.raises(ValueError, match=r"fork output 1 has capacity 8192; a fork "
                                         r"keeps its input's \(16384\)"):
        forked(cut).collect()
    with pytest.raises(ValueError, match="returned 1 outputs, expected 2"):
        forked(lambda batch: (batch,)).collect()
