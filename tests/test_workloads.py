"""The five BASELINE.json workload shapes, end-to-end.

Each test mirrors one reference workload config (BASELINE.json), runs it
through the distributed engine on the 8-device mesh AND through the
LocalDebug NumPy interpreter, and differentially validates
(the reference pattern: cluster run vs LINQ-to-Objects,
``DryadLinqTests/Utils.cs`` Validate.Check).

1. WordCount                      (DryadLinqTests/WordCount.cs:58-61)
2. GroupBy + Aggregate combiners  (GroupByReduceTests.cs)
3. RangePartition sort / TeraSort (RangePartitionAPICoverageTests.cs)
4. Apply + Fork multi-output DAG  (ApplyAndForkTests.cs)
5. Join + OrderBy two-input DAG   (BasicAPITests.cs)
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dryad_tpu import ColumnType, Decomposable, DryadContext, Schema
from oracle import check

TEXT = (
    "it was the best of times it was the worst of times it was the age "
    "of wisdom it was the age of foolishness it was the epoch of belief"
).split()


@pytest.fixture
def ctx(mesh8):
    return DryadContext(num_partitions_=8)


@pytest.fixture
def dbg():
    return DryadContext(local_debug=True)


# -- config 1: WordCount ----------------------------------------------------
def test_wordcount(ctx, dbg):
    """Tokenized lines -> per-word counts -> top words by count."""
    rng = np.random.default_rng(0)
    words = np.array(rng.choice(TEXT, 3000), dtype=object)

    def q(c):
        wc = (
            c.from_arrays({"word": words})
            .group_by("word", {"count": ("count", None)})
        )
        return wc.order_by([("count", True), "word"]).collect()

    a, e = q(ctx), q(dbg)
    check(a, e)
    # exact counts vs plain python
    py = {}
    for w in words:
        py[w] = py.get(w, 0) + 1
    got = dict(zip(a["word"], a["count"].tolist()))
    assert got == py


# -- config 2: GroupBy + Aggregate combiners --------------------------------
def test_groupby_aggregate_combiners(ctx, dbg):
    """Builtin decomposed aggregates + a user Decomposable in one query,
    exercising the Seed/Accumulate/Merge/Finalize path across a shuffle."""
    rng = np.random.default_rng(1)
    n = 4000
    tbl = {
        "k": rng.integers(0, 57, n).astype(np.int32),
        "v": rng.standard_normal(n).astype(np.float32),
    }

    def q_builtin(c):
        return (
            c.from_arrays(tbl)
            .group_by(
                "k",
                {
                    "total": ("sum", "v"),
                    "n": ("count", None),
                    "lo": ("min", "v"),
                    "hi": ("max", "v"),
                    "avg": ("mean", "v"),
                },
            )
            .collect()
        )

    a, e = q_builtin(ctx), q_builtin(dbg)
    ka, ke = np.argsort(a["k"]), np.argsort(e["k"])
    assert np.array_equal(a["k"][ka], e["k"][ke])
    for col, tol in [("total", 1e-4), ("lo", 1e-6), ("hi", 1e-6), ("avg", 1e-4)]:
        np.testing.assert_allclose(a[col][ka], e[col][ke], rtol=tol, atol=tol)
    assert a["n"].sum() == n

    # user combiner: log-sum-exp style max + stable accumulation
    dec = Decomposable(
        seed=lambda cols: {"mx": cols["v"], "cnt": jnp.ones_like(cols["v"])},
        merge=lambda x, y: {
            "mx": jnp.maximum(x["mx"], y["mx"]),
            "cnt": x["cnt"] + y["cnt"],
        },
        state_cols=["mx", "cnt"],
        out_fields=[("mx", ColumnType.FLOAT32), ("cnt", ColumnType.FLOAT32)],
    )

    def q_dec(c):
        return c.from_arrays(tbl).group_by("k", decomposable=dec).collect()

    a2, e2 = q_dec(ctx), q_dec(dbg)
    k2a, k2e = np.argsort(a2["k"]), np.argsort(e2["k"])
    np.testing.assert_allclose(a2["mx"][k2a], e2["mx"][k2e], rtol=1e-6)
    np.testing.assert_allclose(a2["cnt"][k2a], e2["cnt"][k2e])


# -- config 3: RangePartition sort (TeraSort shape) -------------------------
def test_terasort_shape(ctx, dbg):
    """Random keys -> range partition via sampled splitters -> local sort
    -> globally sorted output with payload intact."""
    rng = np.random.default_rng(2)
    n = 5000
    keys = rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)
    payload = np.arange(n, dtype=np.float32)

    def q(c):
        return (
            c.from_arrays({"key": keys, "payload": payload})
            .order_by(["key"])
            .collect()
        )

    a = q(ctx)
    # global sortedness
    assert np.all(np.diff(a["key"].astype(np.int64)) >= 0)
    # row conservation with payload
    assert len(a["key"]) == n
    order = np.argsort(keys, kind="stable")
    assert np.array_equal(a["key"], keys[order])
    e = q(dbg)
    assert np.array_equal(a["key"], e["key"])

    # explicit range_partition (no local sort) conserves rows
    rp = (
        ctx.from_arrays({"key": keys, "payload": payload})
        .range_partition("key")
        .collect()
    )
    assert sorted(rp["key"].tolist()) == sorted(keys.tolist())


# -- config 4: Apply + Fork multi-output DAG --------------------------------
@pytest.mark.parametrize("how", ["three_collects", "one_job"])
def test_apply_fork_dag(ctx, dbg, how):
    """Per-partition apply, then a fork producing two branches consumed
    by different downstream pipelines (multi-output DAG with a Tee);
    the three outputs collected one by one, and as ONE job
    (``collect_many``: the reference's ``SubmitAndWait`` of several)."""
    n = 800
    tbl = {"x": np.arange(n, dtype=np.int32)}
    s = Schema([("x", ColumnType.INT32)])

    def bump(batch):
        return batch.with_column("x", batch["x"] + 1)

    def split(batch):
        return (
            batch.filter(batch["x"] % 3 == 0),
            batch.filter(batch["x"] % 3 != 0),
        )

    def q(c):
        base = c.from_arrays(tbl).apply(bump)
        mult, rest = base.fork(split, [s, s])
        agg_m = mult.group_by(
            "x", {"c": ("count", None)}
        ).aggregate_as_query({"total": ("count", None)})
        if how == "one_job":
            return c.collect_many([mult, rest, agg_m])
        return mult.collect(), rest.collect(), agg_m.collect()

    am, ar, at = q(ctx)
    em, er, et = q(dbg)
    check(am, em)
    check(ar, er)
    assert at["total"][0] == et["total"][0] == len(em["x"])
    assert sorted(am["x"].tolist()) == [v for v in range(1, n + 1) if v % 3 == 0]


# -- config 5: Join + OrderBy two-input DAG ---------------------------------
def test_join_orderby_dag(ctx, dbg):
    """Two tables co-partitioned by key, joined, then globally ordered —
    the reference's two-input query shape with a shuffle on each input."""
    rng = np.random.default_rng(3)
    orders = {
        "cust": rng.integers(0, 40, 600).astype(np.int32),
        "amount": rng.integers(1, 100, 600).astype(np.int32),
    }
    customers = {
        "cust": np.arange(40, dtype=np.int32),
        "region": rng.integers(0, 5, 40).astype(np.int32),
    }

    def q(c):
        j = c.from_arrays(orders).join(c.from_arrays(customers), "cust")
        by_region = j.group_by("region", {"spend": ("sum", "amount")})
        return by_region.order_by([("spend", True)]).collect()

    a, e = q(ctx), q(dbg)
    assert np.array_equal(a["region"], e["region"])
    assert np.array_equal(a["spend"], e["spend"])
    assert np.all(np.diff(a["spend"]) <= 0)
