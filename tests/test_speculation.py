"""Speculative duplication of straggling vertex tasks.

The reference detects outlier vertex executions with a robust duration
model and re-executes them, first-completion-wins
(``GraphManager/vertex/DrVertex.cpp:444`` RequestDuplicate,
``DrStageStatistics.cpp:93`` GetOutlierThreshold,
``DrStageManager.h:156`` CheckForDuplicates).  These tests run a
partition-local plan as independent vertex tasks across 2 worker
processes, inject a delay into one worker, and verify the job completes
at fast-worker speed with duplicate events in the log.
"""

import time

import numpy as np
import pytest

from dryad_tpu import DryadContext
from dryad_tpu.cluster.localjob import LocalJobSubmission

DELAY = 8.0


@pytest.fixture(scope="module")
def submission():
    with LocalJobSubmission(num_workers=2, devices_per_worker=1) as sub:
        yield sub


def _even(cols):
    # module-level: job packages pickle the plan, lambdas don't ship
    return cols["k"] % 2 == 0


def _etl_query(n: int = 4000):
    """A partition-local (exchange-free) ETL plan: where + project."""
    rng = np.random.default_rng(7)
    tbl = {
        "k": rng.integers(0, 100, n).astype(np.int32),
        "v": rng.standard_normal(n).astype(np.float32),
    }
    ctx = DryadContext(num_partitions_=1)
    q = ctx.from_arrays(tbl).where(_even).project(["k", "v"])
    expected_rows = int(np.sum(tbl["k"] % 2 == 0))
    return q, tbl, expected_rows


def test_partitioned_submission_correctness(submission):
    q, tbl, expected_rows = _etl_query()
    out = submission.submit_partitioned(q, nparts=6)
    assert len(out["k"]) == expected_rows
    mask = tbl["k"] % 2 == 0
    np.testing.assert_array_equal(np.sort(out["k"]), np.sort(tbl["k"][mask]))


def test_straggler_duplicated_first_completion_wins(submission):
    """One worker stalls DELAY seconds on its next vertex task; the
    duration model flags the outlier, the task is duplicated to the
    fast worker, and the job finishes long before the stall ends."""
    q, tbl, expected_rows = _etl_query()
    # Warm the package/compile caches on both workers so timing
    # variance reflects execution, not first-compile.
    submission.submit_partitioned(q, nparts=6)

    submission.inject_delay(worker=1, seconds=DELAY, count=1)
    t0 = time.monotonic()
    out = submission.submit_partitioned(q, nparts=6)
    dt = time.monotonic() - t0

    assert len(out["k"]) == expected_rows
    # Completed at fast-worker speed: well under the injected stall.
    assert dt < DELAY - 1.0, f"job took {dt:.1f}s, straggler not bypassed"
    kinds = [e["kind"] for e in submission.events.events()]
    assert "vertex_duplicate" in kinds, "no duplicate was requested"
    assert "vertex_duplicate_win" in kinds, "duplicate never won"


def test_partitioned_submission_string_columns(submission):
    """STRING columns decode at assembly: the driver registers host
    tokens before packing (workers re-encode with the same Hash64)."""
    vocab = np.array(["ant", "bee", "cat", "dog", "elk"], object)
    rng = np.random.default_rng(11)
    words = vocab[rng.integers(0, len(vocab), 400)]
    ctx = DryadContext(num_partitions_=1)
    q = ctx.from_arrays({"w": words}).project(["w"])
    out = submission.submit_partitioned(q, nparts=4)
    assert sorted(out["w"].tolist()) == sorted(words.tolist())


def test_auto_fanout_scales_with_data_size(submission):
    """nparts unset: the task count follows observed input size
    (DrDynamicRangeDistributor.cpp:54-110 consumer recomputation)."""
    small_ctx = DryadContext(num_partitions_=1)
    small = small_ctx.from_arrays(
        {"k": np.arange(100, dtype=np.int32)}
    ).project(["k"])
    assert submission._auto_fanout(small) == submission.n  # one wave

    # a small rows_per_vertex stands in for a big input: fan-out is
    # rows / rows_per_vertex, so the ratio is what's under test
    from dryad_tpu.utils.config import DryadConfig

    ctx = DryadContext(
        num_partitions_=1, config=DryadConfig(rows_per_vertex=50)
    )
    big = ctx.from_arrays(
        {"k": np.arange(50 * submission.n * 3, dtype=np.int32)}
    ).project(["k"])
    assert submission._auto_fanout(big) == submission.n * 3


def test_worker_death_survivors_finish_vertex_job():
    """A dead worker must not abort independent vertex tasks: its
    computer deregisters, its in-flight attempt fails and re-executes
    on a survivor, and the job completes (DrVertex.cpp:531
    InstantiateVersion re-execution semantics)."""
    with LocalJobSubmission(num_workers=2, devices_per_worker=1) as sub:
        q, tbl, expected_rows = _etl_query()
        sub.submit_partitioned(q, nparts=4)  # warm both workers
        # kill worker 1 between jobs
        sub.launcher.stop(sub._handles[1])
        out = sub.submit_partitioned(q, nparts=4)
        assert len(out["k"]) == expected_rows
        kinds = [e["kind"] for e in sub.events.events()]
        assert "worker_dead" in kinds


def test_exchange_plan_rejected(submission):
    """Plans with shuffles (beyond the terminal-group partial rewrite)
    are gang-SPMD jobs; partitioned submission must refuse them rather
    than compute wrong per-partition results."""
    ctx = DryadContext(num_partitions_=1)
    # (an order_by over a host input now ROUTES instead of rejecting —
    # see test_routed_order_by_as_vertex_tasks)
    # a Decomposable group_by has no driver-mergeable partial form
    import jax.numpy as jnp

    from dryad_tpu import ColumnType, Decomposable

    dec = Decomposable(
        seed=lambda cols: {"acc": cols["v"]},
        merge=lambda a, b: {"acc": jnp.maximum(a["acc"], b["acc"])},
        state_cols=["acc"],
        out_fields=[("acc", ColumnType.FLOAT32)],
    )
    q2 = ctx.from_arrays(
        {"k": np.arange(8, dtype=np.int32),
         "v": np.ones(8, np.float32)}
    ).group_by("k", decomposable=dec)
    with pytest.raises(ValueError, match="use submit"):
        submission.submit_partitioned(q2)


def _group_query(n: int = 4000):
    """A terminal builtin-agg group_by: runs as per-vertex PARTIAL
    reduction + driver-side final merge (DrDynamicAggregateManager
    machine-level partials)."""
    rng = np.random.default_rng(11)
    tbl = {
        "k": rng.integers(0, 20, n).astype(np.int32),
        "v": rng.standard_normal(n).astype(np.float32),
    }
    ctx = DryadContext(num_partitions_=1)
    q = ctx.from_arrays(tbl).group_by(
        "k", {"c": ("count", None), "s": ("sum", "v"),
              "mn": ("min", "v"), "m": ("mean", "v")}
    )
    return q, tbl


def _expected_groups(tbl):
    exp = {}
    for k in np.unique(tbl["k"]):
        vs = tbl["v"][tbl["k"] == k]
        exp[int(k)] = (len(vs), float(vs.sum()), float(vs.min()),
                       float(vs.mean()))
    return exp


def test_partitioned_group_by_partials(submission):
    q, tbl = _group_query()
    out = submission.submit_partitioned(q, nparts=6)
    exp = _expected_groups(tbl)
    assert sorted(out["k"].tolist()) == sorted(exp)
    for k, c, s, mn, m in zip(out["k"], out["c"], out["s"], out["mn"], out["m"]):
        ec, es, emn, em = exp[int(k)]
        assert int(c) == ec
        np.testing.assert_allclose(s, es, rtol=1e-4)
        np.testing.assert_allclose(mn, emn, rtol=1e-5)
        np.testing.assert_allclose(m, em, rtol=1e-4)
    kinds = [e["kind"] for e in submission.events.events()]
    assert "vertex_partials_merged" in kinds


def test_partitioned_group_by_straggler_duplicated(submission):
    """A group_by partial vertex that straggles is speculatively
    duplicated and the merged result is still correct."""
    q, tbl = _group_query()
    submission.submit_partitioned(q, nparts=4)  # warm caches

    submission.inject_delay(worker=0, seconds=DELAY, count=1)
    t0 = time.monotonic()
    out = submission.submit_partitioned(q, nparts=4)
    dt = time.monotonic() - t0

    exp = _expected_groups(tbl)
    assert sorted(out["k"].tolist()) == sorted(exp)
    for k, c, s in zip(out["k"], out["c"], out["s"]):
        ec, es, _, _ = exp[int(k)]
        assert int(c) == ec
        np.testing.assert_allclose(s, es, rtol=1e-4)
    assert dt < DELAY - 1.0, f"job took {dt:.1f}s, straggler not bypassed"
    kinds = [e["kind"] for e in submission.events.events()]
    assert "vertex_duplicate" in kinds and "vertex_duplicate_win" in kinds


def test_partitioned_scalar_aggregate_partials(submission):
    rng = np.random.default_rng(13)
    tbl = {"v": rng.standard_normal(3000).astype(np.float32)}
    ctx = DryadContext(num_partitions_=1)
    q = ctx.from_arrays(tbl).aggregate_as_query(
        {"s": ("sum", "v"), "n": ("count", None),
         "lo": ("min", "v"), "m": ("mean", "v")}
    )
    out = submission.submit_partitioned(q, nparts=5)
    assert len(out["s"]) == 1
    np.testing.assert_allclose(out["s"][0], tbl["v"].sum(), rtol=1e-4)
    assert int(out["n"][0]) == 3000
    np.testing.assert_allclose(out["lo"][0], tbl["v"].min(), rtol=1e-5)
    np.testing.assert_allclose(out["m"][0], tbl["v"].mean(), rtol=1e-4)


def test_partitioned_rejects_mid_plan_group_by(submission):
    """Only a TERMINAL group_by qualifies for the partial rewrite: a
    group_by feeding further ops would be merged too late."""
    rng = np.random.default_rng(17)
    tbl = {
        "k": rng.integers(0, 20, 500).astype(np.int32),
        "v": rng.standard_normal(500).astype(np.float32),
    }
    ctx = DryadContext(num_partitions_=1)
    q = (
        ctx.from_arrays(tbl)
        .group_by("k", {"s": ("sum", "v")})
        .where(_even)
    )
    with pytest.raises(ValueError, match="use submit"):
        submission.submit_partitioned(q, nparts=4)


def test_partitioned_group_by_first_merges_engine_order(submission):
    """'first' partials merge to the engine-order first because
    assembly concatenates partition results in part-id order."""
    n = 1200
    k = (np.arange(n, dtype=np.int32) % 7)
    v = np.arange(n, dtype=np.float32)  # engine order = ascending v
    ctx = DryadContext(num_partitions_=1)
    q = ctx.from_arrays({"k": k, "v": v}).group_by(
        "k", {"f": ("first", "v"), "c": ("count", None)}
    )
    out = submission.submit_partitioned(q, nparts=4)
    for kk, f in zip(out["k"], out["f"]):
        assert int(f) == int(kk)  # first occurrence of key kk is row kk


def test_store_backed_first_refuses_partial_merge(submission, tmp_path):
    """'first' over a STORE-backed input must not partial-merge:
    StoreParts.part deals store partitions round-robin, so part-id-concat
    order is not engine order there (code-review r4)."""
    src = DryadContext(num_partitions_=1)
    src.from_arrays(
        {"k": (np.arange(40, dtype=np.int32) % 5),
         "v": np.arange(40, dtype=np.float32)}
    ).to_store(str(tmp_path / "s1"))
    ctx = DryadContext(num_partitions_=1)
    q = ctx.from_store(str(tmp_path / "s1")).group_by(
        "k", {"f": ("first", "v")}
    )
    with pytest.raises(ValueError, match="exchange-free"):
        submission.submit_partitioned(q, nparts=4)


def test_partitioned_decomposable_partials(submission):
    """A typed-state Decomposable (state_fields) runs as per-vertex
    custom-combiner partials with a driver-side merge + finalize —
    the reference's machine-level partial aggregation for custom
    combiners."""
    import jax.numpy as jnp

    from dryad_tpu import ColumnType, Decomposable

    rng = np.random.default_rng(23)
    n = 3000
    tbl = {
        "k": rng.integers(0, 12, n).astype(np.int32),
        "v": rng.standard_normal(n).astype(np.float32),
    }
    dec = Decomposable(
        seed=lambda cols: {
            "cnt": jnp.ones_like(cols["v"]),
            "s1": cols["v"],
            "s2": cols["v"] * cols["v"],
        },
        merge=lambda a, b: {
            "cnt": a["cnt"] + b["cnt"],
            "s1": a["s1"] + b["s1"],
            "s2": a["s2"] + b["s2"],
        },
        state_cols=["cnt", "s1", "s2"],
        state_fields=[
            ("cnt", ColumnType.FLOAT32),
            ("s1", ColumnType.FLOAT32),
            ("s2", ColumnType.FLOAT32),
        ],
        finalize=lambda cols: {
            **cols,
            "var": cols["s2"] / cols["cnt"]
            - (cols["s1"] / cols["cnt"]) ** 2,
        },
        out_fields=[("var", ColumnType.FLOAT32)],
    )
    ctx = DryadContext(num_partitions_=1)
    q = ctx.from_arrays(tbl).group_by("k", decomposable=dec)
    out = submission.submit_partitioned(q, nparts=5)
    assert sorted(out["k"].tolist()) == sorted(
        np.unique(tbl["k"]).tolist()
    )
    for k, var in zip(out["k"], out["var"]):
        vs = tbl["v"][tbl["k"] == k]
        np.testing.assert_allclose(
            var, vs.var(), rtol=1e-3, atol=1e-4
        )
    kinds = [e["kind"] for e in submission.events.events()]
    assert "vertex_partials_merged" in kinds


def _join_queries():
    rng = np.random.default_rng(5)
    L = {"k": rng.integers(0, 200, 5000).astype(np.int32),
         "a": rng.integers(0, 9, 5000).astype(np.int32)}
    R = {"k": rng.integers(0, 200, 1500).astype(np.int32),
         "b": rng.integers(0, 9, 1500).astype(np.int32)}
    ctx = DryadContext(num_partitions_=1)
    q = ctx.from_arrays(L).join(ctx.from_arrays(R), ["k"], ["k"])
    import collections
    ridx = collections.defaultdict(list)
    for kk, bb in zip(R["k"].tolist(), R["b"].tolist()):
        ridx[kk].append(bb)
    exp = sorted((kk, aa, bb) for kk, aa in zip(L["k"].tolist(),
                                                L["a"].tolist())
                 for bb in ridx.get(kk, []))
    return q, exp


def test_routed_join_as_vertex_tasks(submission):
    """A shuffle-bearing JOIN runs as independent vertex tasks: the
    driver co-partitions both host inputs by key hash (the reference
    speculates every vertex kind — DrStageManager.h:156,
    DrVertex.cpp:444 — not just maps)."""
    q, exp = _join_queries()
    out = submission.submit_partitioned(q, nparts=4)
    got = sorted(zip(out["k"].tolist(), out["a"].tolist(),
                     out["b"].tolist()))
    assert got == exp
    evs = [e for e in submission.events.events()
           if e["kind"] == "vertex_routed"]
    assert evs and evs[-1]["plan_kind"] == "join"


def test_routed_join_straggler_duplicated(submission):
    """Speculation covers the routed join: a stalled worker's join
    vertex gets duplicated and the fast worker wins."""
    q, exp = _join_queries()
    submission.submit_partitioned(q, nparts=6)  # warm caches

    # join vertices run ~1s each on this host, so the stall must
    # dominate task time for the bypass to be provable
    stall = 20.0
    submission.inject_delay(worker=0, seconds=stall, count=1)
    t0 = time.monotonic()
    out = submission.submit_partitioned(q, nparts=6)
    dt = time.monotonic() - t0
    got = sorted(zip(out["k"].tolist(), out["a"].tolist(),
                     out["b"].tolist()))
    assert got == exp
    assert dt < stall - 2.0, f"join job took {dt:.1f}s"
    kinds = [e["kind"] for e in submission.events.events()]
    assert "vertex_duplicate" in kinds and "vertex_duplicate_win" in kinds


def test_routed_order_by_as_vertex_tasks(submission):
    """order_by runs as route-at-driver + sort-at-vertex tasks:
    driver-sampled splitters range-partition the input
    (DryadLinqSampler.cs:38-42 at the driver), parts concatenate in
    sort order."""
    rng = np.random.default_rng(6)
    T = {"x": rng.integers(0, 10 ** 6, 6000).astype(np.int32),
         "y": rng.integers(0, 50, 6000).astype(np.int32)}
    ctx = DryadContext(num_partitions_=1)
    q = ctx.from_arrays(T).order_by([("x", True), "y"])
    out = submission.submit_partitioned(q, nparts=4)
    exp = sorted(zip(T["x"].tolist(), T["y"].tolist()),
                 key=lambda t: (-t[0], t[1]))
    assert list(zip(out["x"].tolist(), out["y"].tolist())) == exp
    evs = [e for e in submission.events.events()
           if e["kind"] == "vertex_routed"]
    assert evs[-1]["plan_kind"] == "order_by"


def test_routed_join_with_terminal_partial_group(submission):
    """Routing composes with the terminal partial-group rewrite: join
    vertices emit per-partition partials, the driver merges."""
    q, exp = _join_queries()
    import collections
    q2 = q.group_by("k", {"c": ("count", None)})
    out = submission.submit_partitioned(q2, nparts=4)
    expc = collections.Counter(kk for kk, _a, _b in exp)
    got = {int(k): int(c) for k, c in zip(out["k"], out["c"])}
    assert got == dict(expc)


def test_unroutable_plan_still_rejected(submission):
    """select may rewrite join keys, so it blocks routing: the clear
    error stays."""
    rng = np.random.default_rng(7)
    ctx = DryadContext(num_partitions_=1)
    L = ctx.from_arrays({"k": rng.integers(0, 9, 100).astype(np.int32)})
    R = ctx.from_arrays({"k": rng.integers(0, 9, 50).astype(np.int32),
                         "b": np.arange(50, dtype=np.int32)})
    q = L.select(_twice).join(R, ["k"], ["k"])
    with pytest.raises(ValueError, match="use submit"):
        submission.submit_partitioned(q, nparts=4)


def _twice(cols):
    return {"k": cols["k"] * 2}


def test_self_join_different_keys_not_routed(submission):
    """A self-join on different key columns cannot ship two routings
    for one input node — it must fall back with the clear error, not
    silently drop matches (code-review r5)."""
    ctx = DryadContext(num_partitions_=1)
    t = ctx.from_arrays({
        "src": np.array([1, 2, 3, 1], np.int32),
        "dst": np.array([2, 3, 1, 3], np.int32),
    })
    q = t.join(t, ["src"], ["dst"], suffix="_r")
    with pytest.raises(ValueError, match="use submit"):
        submission.submit_partitioned(q, nparts=4)


def test_routed_plan_with_first_agg_rejected(submission):
    """Routing reorders rows by key hash; a terminal 'first' aggregate
    would become nparts-dependent — refuse loudly (code-review r5)."""
    rng = np.random.default_rng(8)
    ctx = DryadContext(num_partitions_=1)
    L = ctx.from_arrays({"k": rng.integers(0, 9, 200).astype(np.int32),
                         "g": rng.integers(0, 3, 200).astype(np.int32),
                         "v": rng.random(200).astype(np.float32)})
    R = ctx.from_arrays({"k": np.arange(9, dtype=np.int32)})
    q = L.join(R, ["k"], ["k"]).group_by("g", {"f": ("first", "v")})
    with pytest.raises(ValueError, match="first"):
        submission.submit_partitioned(q, nparts=4)
