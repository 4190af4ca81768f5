"""The deployment ``dryadlinq-join-1c`` as its cell runs it, on the CPU:
``benchmarks/jobs/join_topk.py`` loaded by path, its ``bind(...)``
collected through ``DryadContext`` at 4,096 x 128 rows, against the
job's own NumPy answer AND the LocalDebug interpreter; then what the
PR that added the cell put into the program for it: the join's inner
scopes in the lowered stage program, one ``join_plan`` event a join a
compile, and a program name that says which scopes a cached program
carries."""

import importlib.util
import os
import re

import numpy as np
import pytest

from dryad_tpu import DryadContext
from dryad_tpu.api.query import Query

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, DIM_ROWS, TOP = 4096, 128, 100


@pytest.fixture(scope="module")
def job():
    path = os.path.join(ROOT, "benchmarks", "jobs", "join_topk.py")
    spec = importlib.util.spec_from_file_location("bench_job_join_topk", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reference(table, top):
    """Every (fact row, dimension row) pair with equal keys, the ``top``
    of largest payload: plain NumPy, nothing of the engine."""
    fact, dim = table["fact"], table["dim"]
    order = np.argsort(dim["dkey"], kind="stable")
    dkey = dim["dkey"][order]
    lo = np.searchsorted(dkey, fact["key"], side="left")
    hi = np.searchsorted(dkey, fact["key"], side="right")
    left = np.repeat(np.arange(len(fact["key"])), hi - lo)
    right = order[np.concatenate([np.arange(a, b) for a, b in zip(lo, hi)])]
    rows = np.rec.fromarrays(
        [fact["payload"][left], fact["key"][left], dim["weight"][right]],
        names="payload,key,weight")
    return rows[np.argsort(-rows["payload"], kind="stable")[:top]]


def as_rows(answer):
    """An answer as a sorted record array: the order among rows of one
    payload (a fan-out of 2 gives such pairs) is the engine's."""
    rows = np.rec.fromarrays(
        [np.asarray(answer[c]) for c in ("payload", "key", "weight")],
        names="payload,key,weight")
    return np.sort(rows, order=["payload", "key", "weight"])


def the_jobs_table(job, rng, params):
    return job.make_table(rng, params, None, 0)


def fan_out_2(job, rng, params):
    """Every dimension key twice, with two weights: two pairs a fact row."""
    table = job.make_table(rng, params, None, 0)
    dim = table["dim"]
    table["dim"] = {
        "dkey": np.concatenate([dim["dkey"], dim["dkey"]]),
        "weight": np.concatenate([dim["weight"], -dim["weight"] - 1]),
    }
    return table


def keys_without_a_match(job, rng, params):
    """A third of the fact rows point past the dimension table, the
    rows of largest payload among them."""
    table = job.make_table(rng, params, None, 0)
    fact = table["fact"]
    lost = np.argsort(-fact["payload"])[::3]
    fact["key"] = fact["key"].copy()
    fact["key"][lost] += DIM_ROWS
    return table


# name -> (table maker, expansion, strategies, retries at boost > 1)
SHAPES = {
    "as_the_cell": (the_jobs_table, 1.25, ("auto", "broadcast", "shuffle"), False),
    "fan_out_2": (fan_out_2, 2.5, ("auto",), False),
    "keys_without_a_match": (keys_without_a_match, 1.25, ("auto",), False),
    "expansion_too_small": (the_jobs_table, 0.3, ("auto",), True),
}
CASES = [(shape, strategy, P) for shape, spec in SHAPES.items()
         for strategy in spec[2] for P in (1, 4)]


@pytest.mark.parametrize("shape,strategy,P", CASES)
def test_the_cells_query_is_exact(job, monkeypatch, shape, strategy, P):
    make, expansion, _, retries = SHAPES[shape]
    params = {"rows": ROWS, "dim_rows": DIM_ROWS, "top": TOP,
              "expansion": expansion}
    table = make(job, np.random.default_rng([26, P]), params)
    want = as_rows(reference(table, TOP))

    # the job file's bind says "auto": the other two strategies go
    # through the same call with that one argument replaced
    join = Query.join
    monkeypatch.setattr(
        Query, "join",
        lambda self, *a, **k: join(self, *a, **{**k, "strategy": strategy}))

    ctx = DryadContext(num_partitions_=P)
    query = job.bind(ctx, table, params)
    for answer in (query.collect(), query.collect()):  # fresh job, requery
        assert np.array_equal(as_rows(answer), want)
        if shape == "as_the_cell":  # the job's own answer and limits
            checks = job.compare(table, answer, params)
            assert all(value <= limit for value, limit in checks.values())
    debug = job.bind(DryadContext(local_debug=True), table, params).collect()
    assert np.array_equal(as_rows(debug), want)

    events = ctx.events.events()
    boosts = [e["boost"] for e in events
              if e["kind"] == "span" and e.get("cat") == "execute"]
    plans = [e for e in events if e["kind"] == "join_plan"]
    assert (max(boosts) > 1) == retries
    # one event a join a compile (a retry compiles anew), none on the requery
    assert len(plans) == len(set(boosts)) and len(boosts) == len(plans) + 1
    want_strategy = "shuffle" if strategy == "shuffle" else "broadcast"
    assert {p["strategy"] for p in plans} == {want_strategy}
    if shape == "as_the_cell":
        control = job.control(table, params)
        assert any(value > limit for value, limit in
                   job.compare(table, control, params).values())


def test_the_join_plan_record(job):
    """What the event says of the cell's join."""
    params = {"rows": ROWS, "dim_rows": DIM_ROWS, "top": TOP, "expansion": 1.25}
    ctx = DryadContext(num_partitions_=4)
    table = job.make_table(np.random.default_rng(26), params, None, 0)
    query = job.bind(ctx, table, params)
    query.collect()
    first = len(ctx.events.events())
    query.collect()
    events = ctx.events.events()
    plan, = [e for e in events if e["kind"] == "join_plan"]
    assert not [e for e in events[first:] if e["kind"] in ("join_plan", "xla_compile")]
    compiled, = [e for e in events if e["kind"] == "xla_compile"]
    assert plan["stage"] == compiled["stage"] and plan["key"] == compiled["key"]
    assert plan["qid"] == compiled["qid"]
    # 1,024 fact rows a partition, 1.25 slots a row; the dimension
    # table (32 rows a partition) gathered to all 128 on every one
    assert plan["strategy"] == "broadcast" and plan["est_right"] is None
    assert plan["broadcast_limit"] == ctx.config.broadcast_limit == 1 << 16
    assert (plan["left_capacity"], plan["right_capacity"],
            plan["out_capacity"]) == (1024, 128, 1280)
    # what the traced join gathers over its pair slots (PR 42): five
    # columns (the right row's base, key, payload; dkey, weight), an
    # index's through ONE gather where its words were stacked
    stacked = plan["stacked_words"]
    assert set(stacked) == {"li", "ri"} and stacked["li"] in (0, 3) and stacked["ri"] in (0, 2)
    assert plan["slot_gathers"] == 5 - sum(w - 1 for w in stacked.values() if w)
    # a stage without a join says nothing of joins
    other = DryadContext(num_partitions_=4)
    other.from_arrays(table["fact"]).order_by([("payload", True)]).take(5).collect()
    kinds = [e["kind"] for e in other.events.events()]
    assert "xla_compile" in kinds and "join_plan" not in kinds


def lowered_programs(job, monkeypatch, table, params, P, ctx=None):
    """The cell's query collected once; every stage program it lowered."""
    from dryad_tpu.exec.executor import GraphExecutor

    lowered = []
    real = GraphExecutor._get_compiled

    def spy(self, *args, **kwargs):
        hit = real(self, *args, **kwargs)
        fn = hit.fn

        def lowering(*operands):
            lowered.append(fn.lower(*operands))
            return fn(*operands)

        hit.fn = lowering
        return hit

    monkeypatch.setattr(GraphExecutor, "_get_compiled", spy)
    job.bind(ctx or DryadContext(num_partitions_=P), table, params).collect()
    return lowered


def test_the_stage_program_names_the_joins_parts(job, monkeypatch):
    """The lowered program of the cell's query carries the four join
    scopes and the top-k's in its operations' metadata, under the
    operator's scope, and its name is not the one the parent's cached
    programs have."""
    from dryad_tpu.parallel import stage

    params = {"rows": ROWS, "dim_rows": DIM_ROWS, "top": TOP, "expansion": 1.25}
    table = job.make_table(np.random.default_rng(26), params, None, 0)
    program, = lowered_programs(job, monkeypatch, table, params, 4)
    paths = re.findall(r'op_name="([^"]*)"', program.compile().as_text())
    for scope, operator in (
            ("dryad.join.probe", "dryad.join"),
            ("dryad.join.expand_pairs", "dryad.join"),
            ("dryad.join.materialize", "dryad.join"),
            ("dryad.join.exact", "dryad.join"),
            ("dryad.sort.carry", "dryad.join/dryad.join.probe"),
            ("dryad.sort.carry", "dryad.topk")):
        assert any(f"/{operator}/{scope}/" in p + "/" for p in paths), scope
    # every operation of the join lies in one of its four parts, but
    # for the placement (the dimension table's all_gather)
    inside = [p for p in paths if "/dryad.join/" in p + "/"]
    outside = {p.rsplit("/", 1)[-1] for p in inside
               if "/dryad.join/dryad.join." not in p}
    assert inside and outside <= {"all_gather"}, outside
    # the name is in jax's compilation-cache key, the scopes are not: a
    # new set of scopes takes a new name (benchmarks/TRACING.md)
    assert stage.PROGRAM_NAME != "dryad_stage"  # PR 24's and the parent's
    assert f"module @jit_{stage.PROGRAM_NAME} " in program.as_text()


def test_the_join_plan_counts_the_programs_gathers(job, monkeypatch):
    """``slot_gathers`` of the ``join_plan`` event is the number of
    ``gather``s under ``dryad.join`` over the pair slots in the stage
    program the executor lowered, none of them a validity's; the
    parent's program had nine."""
    params = {"rows": ROWS, "dim_rows": DIM_ROWS, "top": TOP, "expansion": 1.25}
    table = job.make_table(np.random.default_rng(26), params, None, 0)
    ctx = DryadContext(num_partitions_=1)
    program, = lowered_programs(job, monkeypatch, table, params, 1, ctx)
    plan, = [e for e in ctx.events.events() if e["kind"] == "join_plan"]
    slots = plan["out_capacity"]
    assert slots == 5120
    text = program.as_text(debug_info=True)
    locs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))
    found = []
    for line in text.splitlines():
        if '"stablehlo.gather"' not in line:
            continue
        result, where = re.search(r"-> tensor<([^>]*)> loc\((#loc\d+)\)", line).groups()
        *dims, dtype = result.split("x")
        if str(slots) in dims and "/dryad.join/" in locs[where]:
            found.append(dtype)
    assert len(found) == plan["slot_gathers"] <= 5 and "i1" not in found


def whiles_by_scope(program):
    """The ``op_name`` path of every ``while`` of a program.  Read from
    the compiled HLO: a lowered ``stablehlo.while`` inside a nested
    ``jit`` (``jnp.searchsorted`` is one) carries a path relative to
    its own function, the compiled instruction the whole one."""
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in program.compile().as_text().splitlines()
            if re.search(r"\bwhile\(", line)]


def test_a_binary_search_would_be_seen():
    """What the next test looks for is there to find when it is there."""
    import jax
    import jax.numpy as jnp

    def search(a, q):
        with jax.named_scope("dryad.join.probe"):
            return jnp.searchsorted(a, q)

    program = jax.jit(search).lower(jnp.arange(128, dtype=jnp.uint32),
                                    jnp.arange(4, dtype=jnp.uint32))
    path, = whiles_by_scope(program)
    assert "dryad.join.probe" in path


@pytest.mark.parametrize("rows", [ROWS, 4])
def test_no_loop_under_the_joins_searches(job, monkeypatch, rows):
    """A rank in a sorted array comes from a sort and a scan: the cell's
    program holds no ``while`` under any ``dryad.join`` scope, nor does
    the same query with four fact rows against the same dimension table
    (a few left rows into a long right side)."""
    params = {"rows": ROWS, "dim_rows": DIM_ROWS, "top": TOP, "expansion": 1.25}
    table = job.make_table(np.random.default_rng(26), params, None, 0)
    table["fact"] = {name: col[:rows] for name, col in table["fact"].items()}
    program, = lowered_programs(job, monkeypatch, table, params, 1)
    loops = [path for path in whiles_by_scope(program) if "dryad.join" in path]
    assert loops == []
