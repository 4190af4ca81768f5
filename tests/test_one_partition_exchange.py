"""An exchange over one partition moves nothing.  On a mesh of one
partition ``exchange_range``, ``exchange_hash`` and the ``resize``
paired with each trace no device operation (``exec/kernels.py::_elided``):
an ``order_by`` is its ``local_sort`` alone, at the capacity its input
had, and the answers are the ones NumPy gives and, bit for bit, the ones
a mesh of four or eight partitions gives, where the exchange runs as it
did.  The ``dispatch`` span says how many exchanges a trace skipped
(``xchg_elided``), and no ``exchange_round`` is written for one.
"""

import functools
import re

import jax
import numpy as np
import pytest

from dryad_tpu import DryadContext

ROWS = 3000
MESHES = (1, 4, 8)


def _distinct_keys(rng, n=ROWS):
    """int32 keys with no two equal, so an order has one answer."""
    return rng.permutation(np.arange(-n, n, dtype=np.int32))[:n] * 7919


def _narrow(rng):
    key = _distinct_keys(rng)
    return {"k": key, "v": rng.standard_normal(ROWS).astype(np.float32)}


def _two_keys(rng):
    return {"a": rng.integers(-9, 9, ROWS).astype(np.int32),
            "b": _distinct_keys(rng),
            "v": rng.standard_normal(ROWS).astype(np.float32)}


def _records(rng):
    """The sort benchmark's record: a BYTES(10) key, a BYTES(90) payload."""
    return {"key": rng.integers(0, 256, (ROWS, 10), dtype=np.uint8),
            "payload": rng.integers(0, 256, (ROWS, 90), dtype=np.uint8)}


def _take(table, order):
    return {name: col[order] for name, col in table.items()}


def _kept(table):
    return _take(table, np.flatnonzero(table["v"] > -0.4))


# name -> (table from a generator, query over it, NumPy's answer)
SORTS = {
    "int32_key_f32_payload": (
        _narrow, lambda t: t.order_by(["k"]),
        lambda tb: _take(tb, np.argsort(tb["k"], kind="stable"))),
    "descending": (
        _narrow, lambda t: t.order_by([("k", True)]),
        lambda tb: _take(tb, np.argsort(tb["k"], kind="stable")[::-1])),
    "two_keys": (
        _two_keys, lambda t: t.order_by(["a", ("b", True)]),
        lambda tb: _take(tb, np.lexsort((-tb["b"].astype(np.int64), tb["a"])))),
    "where_before_it": (  # invalid rows lie between the valid ones
        _narrow, lambda t: t.where(lambda c: c["v"] > -0.4).order_by(["k"]),
        lambda tb: _take(_kept(tb), np.argsort(_kept(tb)["k"], kind="stable"))),
    "wide_row": (
        _records, lambda t: t.order_by(["key"]),
        lambda tb: _take(tb, np.lexsort(tb["key"].T[::-1]))),
}


def _grouped(tb):
    keys, inverse = np.unique(tb["k"], return_inverse=True)
    return {"k": keys, "c": np.bincount(inverse).astype(np.int32),
            "s": np.bincount(inverse, tb["v"]).astype(np.float32)}


def _small_keys(rng):
    """Keys of both signs (a negative key keeps the dense rewrite off)
    and a payload of small whole numbers, whose f32 sums are exact in
    any order."""
    return {"k": rng.integers(-40, 40, ROWS).astype(np.int32),
            "v": rng.integers(0, 8, ROWS).astype(np.float32)}


def _pairs(rng):
    return {"k": rng.integers(-40, 40, ROWS).astype(np.int32),
            "w": rng.integers(0, 5, ROWS).astype(np.int32)}


DIM = {"dk": np.arange(-40, 40, dtype=np.int32),
       "dv": (np.arange(80) * 0.5).astype(np.float32)}


def _joined(tb):
    return dict(tb, dv=DIM["dv"][tb["k"] + 40])


def _unique_rows(tb):
    rows = np.unique(np.stack([tb["k"], tb["w"]], axis=1), axis=0)
    return {"k": rows[:, 0].copy(), "w": rows[:, 1].copy()}


# name -> (table, query, NumPy's answer, exchanges in the stage program,
#          capacity of the answer over the input's at one partition)
REPARTITIONS = {
    "range_partition": (
        _narrow, lambda t: t.range_partition(["k"]), lambda tb: tb, 1, 1),
    "hash_partition": (
        _narrow, lambda t: t.hash_partition(["k"]), lambda tb: tb, 1, 1),
    "group_by": (
        _small_keys,
        lambda t: t.group_by("k", {"c": ("count", None), "s": ("sum", "v")}),
        _grouped, 1, 1),
    "distinct": (_pairs, lambda t: t.distinct(), _unique_rows, 1, 1),
    "shuffle_join": (  # both sides exchange; the pair buffer is 2 x the left
        _small_keys,
        lambda t: t.join(t.ctx.from_arrays(DIM), "k", "dk",
                         strategy="shuffle", expansion=2.0),
        _joined, 2, 2),
}
CASES = {**{name: case + (1, 1) for name, case in SORTS.items()},
         **REPARTITIONS}


@functools.lru_cache(maxsize=None)
def ran(name, P):
    """The case's query collected once on a mesh of ``P`` partitions: its
    answer, the stage programs it lowered and the events of the job."""
    from dryad_tpu.exec.executor import GraphExecutor

    make, query = CASES[name][:2]
    table = make(np.random.default_rng([33, len(name)]))
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

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(GraphExecutor, "_get_compiled", spy)
        ctx = DryadContext(num_partitions_=P)
        answer = query(ctx.from_arrays(table)).collect()
    return table, answer, lowered, ctx.events.events()


def _canonical(table):
    """The rows in one order whatever order they came in."""
    cols = [c.reshape(len(c), -1) for _, c in sorted(table.items())]
    order = np.lexsort(np.concatenate(cols, axis=1).T[::-1])
    return _take(table, order)


def _assert_bit_for_bit(got, want):
    assert sorted(got) == sorted(want)
    for name, col in want.items():
        assert got[name].dtype == col.dtype and got[name].shape == col.shape, name
        assert got[name].tobytes() == col.tobytes(), name


def _slots(info):
    """Slots a partition of the batches in a program's arguments or results."""
    return {leaf.shape[0] for leaf in jax.tree_util.tree_leaves(info)
            if len(leaf.shape) >= 1}


def _scopes(program):
    return set(re.findall(r"dryad\.[a-z_.]+", program.as_text(debug_info=True)))


def _dispatches(events):
    return [e for e in events if e["kind"] == "span" and e.get("cat") == "execute"]


@pytest.mark.parametrize("name", SORTS)
def test_at_one_partition_an_order_by_is_its_local_sort_alone(name):
    _, _, (program,), _ = ran(name, 1)
    scopes = _scopes(program)
    assert "dryad.local_sort" in scopes and "dryad.sort.carry" in scopes
    assert not {s for s in scopes
                if s.startswith(("dryad.exchange", "dryad.resize", "dryad.sort.splitters"))}
    text = program.as_text()
    assert "scatter" not in text and "all_to_all" not in text
    # no slack: the answer has the slots its input had
    assert _slots(program.out_info) == _slots(program.args_info)


@pytest.mark.parametrize("P", MESHES)
@pytest.mark.parametrize("name", SORTS)
def test_an_order_by_answers_as_numpy_and_as_every_mesh(name, P):
    table, answer, _, _ = ran(name, P)
    _assert_bit_for_bit(answer, SORTS[name][2](table))
    _assert_bit_for_bit(answer, ran(name, 1)[1])


@pytest.mark.parametrize("name", REPARTITIONS)
def test_at_one_partition_a_repartition_traces_nothing(name):
    _, _, (program,), events = ran(name, 1)
    exchanges, growth = REPARTITIONS[name][3:]
    assert not {s for s in _scopes(program)
                if s.startswith(("dryad.exchange", "dryad.resize"))}
    assert "all_to_all" not in program.as_text()
    if name.endswith("_partition"):  # nothing at all is left to run
        assert not _scopes(program) and "scatter" not in program.as_text()
    (slots_in,) = _slots(program.args_info) - {len(DIM["dk"])}
    # the batches: beside them a join says its pairs, a column a chip (PR 46)
    assert _slots(program.out_info[0]) == {growth * slots_in}
    assert [e["xchg_elided"] for e in _dispatches(events)] == [exchanges]


@pytest.mark.parametrize("P", MESHES)
@pytest.mark.parametrize("name", REPARTITIONS)
def test_a_repartition_answers_as_numpy_and_as_every_mesh(name, P):
    table, answer, _, _ = ran(name, P)
    got = _canonical(answer)
    _assert_bit_for_bit(got, _canonical(REPARTITIONS[name][2](table)))
    _assert_bit_for_bit(got, _canonical(ran(name, 1)[1]))


@pytest.mark.parametrize("name", CASES)
def test_the_dispatch_span_counts_the_exchanges_not_run(name):
    exchanges = CASES[name][3]
    for P in (1, 4):
        events = ran(name, P)[3]
        (dispatched,) = _dispatches(events)
        rounds = [e for e in events if e["kind"] == "exchange_round"]
        if P == 1:
            assert dispatched["xchg_elided"] == exchanges
            assert dispatched["xchg_ici_bytes"] == 0 and not rounds
        else:
            assert dispatched["xchg_elided"] == 0
            assert dispatched["xchg_ici_bytes"] > 0 and len(rounds) == exchanges
        assert not [e for e in events if e["kind"] == "stage_overflow"]


@pytest.mark.parametrize("name", ["int32_key_f32_payload", "wide_row", "group_by"])
def test_at_four_partitions_the_exchange_is_where_it_was(name):
    """The scopes ``tests/test_sort_4c_config.py`` pins in the cell's
    program are in every program that exchanges on a mesh."""
    _, _, (program,), _ = ran(name, 4)
    scopes = _scopes(program)
    kind = "dryad.exchange_hash" if name == "group_by" else "dryad.exchange_range"
    assert {kind, "dryad.exchange.layout", "dryad.exchange.collective",
            "dryad.resize"} <= scopes
    assert ("dryad.sort.splitters" in scopes) == (name != "group_by")
    assert "all_to_all" in program.as_text()
    assert max(_slots(program.out_info)) > max(_slots(program.args_info))


def test_a_fused_region_adds_its_members_elisions():
    """``group_by`` then ``order_by``: two exchanges in what one dispatch
    covers, both skipped on one partition, both run on four."""
    table = _small_keys(np.random.default_rng(33))
    answers = {}
    for P in (1, 4):
        ctx = DryadContext(num_partitions_=P)
        answers[P] = (ctx.from_arrays(table)
                      .group_by("k", {"c": ("count", None), "s": ("sum", "v")})
                      .order_by([("s", True), "k"]).collect())
        events = ctx.events.events()
        assert sum(e["xchg_elided"] for e in _dispatches(events)) == (2 if P == 1 else 0)
        rounds = [e for e in events if e["kind"] == "exchange_round"]
        assert len(rounds) == (0 if P == 1 else 2)
    want = _grouped(table)
    _assert_bit_for_bit(answers[1], _take(want, np.lexsort((want["k"], -want["s"]))))
    _assert_bit_for_bit(answers[4], answers[1])


def test_an_overflow_retry_on_one_partition_still_elides():
    """A join whose pairs outgrow the buffer retries at boost 2: the
    retry's trace skips its exchanges too, and the answer is whole."""
    table = {"k": np.zeros(ROWS, np.int32), "v": np.ones(ROWS, np.float32)}
    dim = {"dk": np.zeros(4, np.int32), "dv": np.arange(4, dtype=np.float32)}
    ctx = DryadContext(num_partitions_=1)
    out = ctx.from_arrays(table).join(
        ctx.from_arrays(dim), "k", "dk", strategy="shuffle", expansion=2.0).collect()
    assert len(out["k"]) == 4 * ROWS
    assert np.array_equal(np.bincount(out["dv"].astype(np.int64)), [ROWS] * 4)
    dispatched = _dispatches(ctx.events.events())
    assert [e["boost"] for e in dispatched] == [1, 2]
    assert [e["xchg_elided"] for e in dispatched] == [2, 2]


@pytest.mark.parametrize("P", [1, 4])
def test_explain_says_what_one_partition_does_with_the_plans_exchange(P):
    """The plan is the same at every width; the legend of a context of
    one partition says its exchange is not traced."""
    from dryad_tpu.tools.explain import explain

    ctx = DryadContext(num_partitions_=P)
    text = explain(ctx.from_arrays(_narrow(np.random.default_rng(1))).order_by(["k"]))
    assert "exchange_range* | resize | local_sort" in text
    assert "1 exchanges (* = cross-partition collective" in text
    assert ("one partition an exchange and its resize trace nothing" in text) == (P == 1)
