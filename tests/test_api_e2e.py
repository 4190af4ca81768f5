"""End-to-end query API tests: distributed engine vs LocalDebug oracle.

Mirrors the reference's test pattern: run the identical query through
the real engine (8-device mesh here; N-process local cluster there) and
through the in-process debug provider, then compare order-insensitively
(``DryadLinqTests/Utils.cs`` Validate.Check).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dryad_tpu import ColumnType, Decomposable, DryadContext, Schema
from oracle import check


@pytest.fixture
def ctx(mesh8):
    return DryadContext(num_partitions_=8)


@pytest.fixture
def dbg():
    return DryadContext(local_debug=True)


def _words(n=400):
    base = "the quick brown fox jumps over the lazy dog".split()
    rng = np.random.default_rng(7)
    return np.array(rng.choice(base, n), dtype=object)


def test_wordcount_both_paths(ctx, dbg):
    words = _words()
    tbl = {"word": words, "one": np.ones(len(words), np.int32)}

    def q(c):
        return (
            c.from_arrays(tbl)
            .group_by("word", {"n": ("count", None)})
            .collect()
        )

    check(q(ctx), q(dbg))
    got = q(ctx)
    assert sorted(got.keys()) == ["n", "word"]
    assert got["n"].sum() == len(words)


def test_select_where(ctx, dbg):
    tbl = {"x": np.arange(100, dtype=np.int32)}

    def q(c):
        return (
            c.from_arrays(tbl)
            .where(lambda cols: cols["x"] % 3 == 0)
            .select(lambda cols: {"y": cols["x"] * 2})
            .collect()
        )

    check(q(ctx), q(dbg))
    assert sorted(q(ctx)["y"].tolist()) == [6 * i for i in range(34)]


def test_group_by_sum_min_max_mean(ctx, dbg):
    rng = np.random.default_rng(3)
    tbl = {
        "k": rng.integers(0, 20, 500).astype(np.int32),
        "v": rng.standard_normal(500).astype(np.float32),
    }

    def q(c):
        return (
            c.from_arrays(tbl)
            .group_by(
                "k",
                {
                    "s": ("sum", "v"),
                    "c": ("count", None),
                    "lo": ("min", "v"),
                    "hi": ("max", "v"),
                    "avg": ("mean", "v"),
                },
            )
            .collect()
        )

    a, e = q(ctx), q(dbg)
    assert sorted(a.keys()) == sorted(e.keys())
    ka = np.argsort(a["k"])
    ke = np.argsort(e["k"])
    assert np.array_equal(a["k"][ka], e["k"][ke])
    np.testing.assert_allclose(a["s"][ka], e["s"][ke], rtol=2e-5, atol=1e-5)
    assert np.array_equal(a["c"][ka], e["c"][ke])
    np.testing.assert_allclose(a["lo"][ka], e["lo"][ke], rtol=1e-6)
    np.testing.assert_allclose(a["hi"][ka], e["hi"][ke], rtol=1e-6)
    np.testing.assert_allclose(a["avg"][ka], e["avg"][ke], rtol=2e-5, atol=1e-5)


def test_decomposable_groupby(ctx, dbg):
    rng = np.random.default_rng(4)
    tbl = {
        "k": rng.integers(0, 10, 200).astype(np.int32),
        "v": rng.standard_normal(200).astype(np.float32),
    }
    # variance via (count, sum, sumsq) decomposition
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
        finalize=lambda cols: {
            **{k: v for k, v in cols.items() if k not in ("cnt", "s1", "s2")},
            "var": cols["s2"] / cols["cnt"] - (cols["s1"] / cols["cnt"]) ** 2,
        },
        out_fields=[("var", ColumnType.FLOAT32)],
    )

    def q(c):
        return c.from_arrays(tbl).group_by("k", decomposable=dec).collect()

    a, e = q(ctx), q(dbg)
    ka, ke = np.argsort(a["k"]), np.argsort(e["k"])
    assert np.array_equal(a["k"][ka], e["k"][ke])
    np.testing.assert_allclose(a["var"][ka], e["var"][ke], rtol=1e-4, atol=1e-5)


def test_join_two_tables(ctx, dbg):
    rng = np.random.default_rng(5)
    left = {
        "id": rng.integers(0, 30, 200).astype(np.int32),
        "x": np.arange(200, dtype=np.float32),
    }
    right = {
        "id": rng.integers(0, 30, 60).astype(np.int32),
        "y": np.arange(60, dtype=np.float32),
    }

    def q(c):
        lt = c.from_arrays(left)
        rt = c.from_arrays(right)
        return lt.join(rt, "id").collect()

    check(q(ctx), q(dbg))


def test_order_by_take(ctx, dbg):
    rng = np.random.default_rng(6)
    tbl = {
        "a": rng.integers(-1000, 1000, 300).astype(np.int32),
        "b": rng.standard_normal(300).astype(np.float32),
    }

    def q(c):
        return c.from_arrays(tbl).order_by(["a", ("b", True)]).collect()

    a, e = q(ctx), q(dbg)
    # global order must match exactly (same sort semantics)
    assert np.array_equal(a["a"], e["a"])
    np.testing.assert_allclose(a["b"], e["b"], rtol=1e-6)

    top = ctx.from_arrays(tbl).order_by(["a"]).take(10).collect()
    expect = np.sort(tbl["a"])[:10]
    assert np.array_equal(np.sort(top["a"]), expect)


def test_distinct_union_intersect_except(ctx, dbg):
    a_tbl = {"v": np.array([1, 2, 2, 3, 4, 4, 4], np.int32)}
    b_tbl = {"v": np.array([3, 4, 5, 5], np.int32)}

    def q(c, op):
        qa = c.from_arrays(a_tbl)
        qb = c.from_arrays(b_tbl)
        return getattr(qa, op)(qb).collect()

    for op in ("union", "intersect", "except_"):
        check(q(ctx, op), q(dbg, op))
    assert sorted(q(ctx, "union")["v"].tolist()) == [1, 2, 3, 4, 5]
    assert sorted(q(ctx, "intersect")["v"].tolist()) == [3, 4]
    assert sorted(q(ctx, "except_")["v"].tolist()) == [1, 2]


def test_concat_and_distinct(ctx, dbg):
    t1 = {"v": np.array([1, 2, 3], np.int32)}
    t2 = {"v": np.array([3, 4], np.int32)}

    def q(c):
        return c.from_arrays(t1).concat(c.from_arrays(t2)).collect()

    check(q(ctx), q(dbg))


def test_scalar_aggregates(ctx, dbg):
    tbl = {"x": np.arange(1, 101, dtype=np.int32)}
    for c in (ctx, dbg):
        q = c.from_arrays(tbl)
        assert q.count() == 100
        assert q.sum_("x") == 5050
        assert q.min_("x") == 1
        assert q.max_("x") == 100
        assert abs(q.mean("x") - 50.5) < 1e-4


def test_apply_and_fork(ctx, dbg):
    tbl = {"x": np.arange(64, dtype=np.int32)}

    def double(batch):
        return batch.with_column("x", batch["x"] * 2)

    def q(c):
        return c.from_arrays(tbl).apply(double).collect()

    check(q(ctx), q(dbg))

    schema_even = Schema([("x", ColumnType.INT32)])

    def split(batch):
        even = batch.filter(batch["x"] % 2 == 0)
        odd = batch.filter(batch["x"] % 2 == 1)
        return (even, odd)

    def qf(c):
        even_q, odd_q = c.from_arrays(tbl).fork(split, [schema_even, schema_even])
        return even_q.collect(), odd_q.collect()

    ae, ao = qf(ctx)
    ee, eo = qf(dbg)
    check(ae, ee)
    check(ao, eo)
    assert sorted(ae["x"].tolist()) == [2 * i for i in range(32)]


def test_do_while(ctx, dbg):
    tbl = {"x": np.array([1.0, 2.0, 3.0, 4.0], np.float32)}

    def body(q):
        return q.select(lambda cols: {"x": cols["x"] * 2})

    def cond(q):
        # continue while max(x) < 100
        return q.aggregate_as_query({"m": ("max", "x")}).select(
            lambda cols: {"go": cols["m"] < 100.0}
        )

    def q(c):
        return c.from_arrays(tbl).do_while(body, cond, max_iter=20).collect()

    a, e = q(ctx), q(dbg)
    assert sorted(a["x"].tolist()) == sorted(e["x"].tolist())
    assert max(a["x"]) >= 100.0


def test_strings_groupby_and_join(ctx, dbg):
    words = _words(150)
    tbl = {"word": words, "v": np.ones(150, np.int32)}
    lookup = {
        "word": np.array(["the", "fox", "dog"], object),
        "weight": np.array([10, 20, 30], np.int32),
    }

    def q(c):
        wc = c.from_arrays(tbl).group_by("word", {"n": ("count", None)})
        lk = c.from_arrays(lookup)
        return wc.join(lk, "word").collect()

    check(q(ctx), q(dbg))
    got = q(ctx)
    assert set(got["word"]) <= {"the", "fox", "dog"}


def test_hash_partition_elides_second_shuffle(ctx):
    # plan-level check: group_by after hash_partition on same keys
    tbl = {"k": np.arange(50, dtype=np.int32)}
    q = ctx.from_arrays(tbl).hash_partition("k").group_by("k", {"n": ("count", None)})
    from dryad_tpu.plan.lower import lower

    sg = lower([q.node], ctx.config)
    kinds = [op.kind for s in sg.stages for op in s.ops]
    assert kinds.count("exchange_hash") == 1  # only the explicit partition
    got = q.collect()
    assert got["n"].sum() == 50


def test_query_iteration_triggers_job(ctx):
    tbl = {"k": np.arange(10, dtype=np.int32)}
    rows = list(ctx.from_arrays(tbl).where(lambda c: c["k"] < 3))
    assert sorted(r["k"] for r in rows) == [0, 1, 2]


def test_device_ingest_cache_reuse_and_eviction(rng):
    """Repeated submits over one table reuse the device-resident ingest
    (LRU by bytes, ProcessService Cache.cs:32 analog); a tiny budget
    evicts; 0 disables."""
    from dryad_tpu import DryadContext
    from dryad_tpu.utils.config import DryadConfig

    tbl = {"k": rng.integers(0, 9, 512).astype(np.int32)}
    ctx = DryadContext(num_partitions_=8)
    q = ctx.from_arrays(tbl)
    a = q.group_by("k", {"c": ("count", None)}).collect()
    assert ctx.inputs.holds(q.node.id)[2]
    cached = ctx.inputs.device_batch(q.node)
    encodes = [e for e in ctx.events.events() if e.get("name") == "encode"]
    b = q.group_by("k", {"s": ("count", None)}).collect()
    # reused, not re-ingested
    assert ctx.inputs.device_batch(q.node) is cached
    assert encodes == [e for e in ctx.events.events() if e.get("name") == "encode"]
    assert sorted(a["k"].tolist()) == sorted(b["k"].tolist())

    small = DryadContext(
        num_partitions_=8, config=DryadConfig(device_cache_bytes=1)
    )
    q1 = small.from_arrays(tbl)
    q2 = small.from_arrays({"k": np.arange(512, dtype=np.int32)})
    q1.count(); q2.count()
    # budget of 1 byte keeps only newest
    assert (small.inputs.holds(q1.node.id)[2], small.inputs.holds(q2.node.id)[2]) == (
        False, True)

    off = DryadContext(
        num_partitions_=8, config=DryadConfig(device_cache_bytes=0)
    )
    q3 = off.from_arrays(tbl)
    q3.count()
    assert not off.inputs.holds(q3.node.id)[2]


def test_device_cache_invalidated_on_rebinding(rng):
    """Rebinding a node (the worker _run_part per-part slice pattern)
    must MISS the device cache — a stale part-0 ingest served for every
    part would duplicate rows (code-review regression)."""
    from dryad_tpu import DryadContext

    ctx = DryadContext(num_partitions_=8)
    k = np.arange(64, dtype=np.int32)
    q = ctx.from_arrays({"k": k})
    pristine = ctx.inputs.snapshot()
    seen = []
    for part in range(2):
        ctx.inputs.restore(
            {nid: b.part(part, 2) for nid, b in pristine.items()}
        )
        out = q.collect()
        seen.append(sorted(out["k"].tolist()))
    assert seen[0] == list(range(32))
    assert seen[1] == list(range(32, 64))


def test_cache_materializes_and_branches(rng):
    """q.cache() executes once and downstream queries branch from the
    device-resident result (temp-table materialization,
    DryadLinqQueryable.cs:3948 isTemp analog)."""
    from dryad_tpu import DryadContext

    ctx = DryadContext(num_partitions_=8)
    n = 4000
    tbl = {"k": rng.integers(0, 100, n).astype(np.int32),
           "v": rng.standard_normal(n).astype(np.float32)}
    base = ctx.from_arrays(tbl).group_by(
        "k", {"s": ("sum", "v"), "c": ("count", None)}
    )
    cached = base.cache()
    jobs_after_cache = len(
        [e for e in ctx.executor.events.events() if e["kind"] == "job_complete"]
    )
    a = cached.where(lambda cols: cols["c"] > 1).count()
    b = cached.order_by([("s", True)]).take(5).collect()
    top = cached.aggregate_as_query({"m": ("max", "s")}).collect()
    ref_c = np.bincount(tbl["k"], minlength=100)
    ref_s = np.bincount(tbl["k"], weights=tbl["v"], minlength=100)
    assert a == int((ref_c[ref_c > 0] > 1).sum())
    np.testing.assert_allclose(
        b["s"], np.sort(ref_s[ref_c > 0])[::-1][:5], rtol=1e-4
    )
    assert abs(float(top["m"][0]) - ref_s[ref_c > 0].max()) < 1e-3
    # each downstream run starts from the device binding, not the
    # original pipeline: the group_by stage ran exactly once
    kinds = [e["kind"] for e in ctx.executor.events.events()]
    assert kinds.count("job_complete") >= jobs_after_cache + 3
    starts = [
        e for e in ctx.executor.events.events()
        if e["kind"] == "stage_start" and "group_by" in e.get("name", "")
    ]
    assert len(starts) == 1


def test_cache_local_debug(rng):
    from dryad_tpu import DryadContext

    dbg = DryadContext(local_debug=True)
    tbl = {"k": rng.integers(0, 10, 200).astype(np.int32)}
    c = dbg.from_arrays(tbl).group_by("k", {"n": ("count", None)}).cache()
    out = c.order_by(["k"]).collect()
    ref = np.bincount(tbl["k"], minlength=10)
    assert out["n"].tolist() == [int(x) for x in ref[ref > 0]]
    dbg.release(c)  # documented contract holds in debug mode too
    with pytest.raises(RuntimeError, match="no binding"):
        c.collect()


def test_cache_partition_claim_elides_downstream_exchange(rng):
    """A cached hash-partitioned result carries its claim: a downstream
    group_by on the same key skips the shuffle."""
    from dryad_tpu import DryadContext
    from dryad_tpu.plan.lower import lower
    from dryad_tpu.utils.config import DryadConfig

    ctx = DryadContext(
        num_partitions_=8, config=DryadConfig(auto_dense_strings=False)
    )
    tbl = {"k": rng.integers(0, 50, 2000).astype(np.int32),
           "v": rng.standard_normal(2000).astype(np.float32)}
    cached = ctx.from_arrays(tbl).group_by("k", {"s": ("sum", "v")}).cache()
    follow = cached.group_by("k", {"m": ("max", "s")})
    kinds = [
        op.kind
        for st in lower([follow.node], ctx.config, ctx.dictionary).stages
        for op in st.ops
    ]
    assert "exchange_hash" not in kinds
    out = follow.collect()
    assert len(out["k"]) == len(np.unique(tbl["k"]))


def test_cache_release_and_stale_binding_error(rng):
    from dryad_tpu import DryadContext

    ctx = DryadContext(num_partitions_=8)
    q = ctx.from_arrays(
        {"k": rng.integers(0, 5, 100).astype(np.int32)}
    ).group_by("k", {"c": ("count", None)})
    cached = q.cache()
    assert len(cached.collect()["k"]) <= 5
    ctx.release(cached)
    with pytest.raises(RuntimeError, match="no binding"):
        cached.collect()
    # releasing a source table or a derived query is a loud error
    src = ctx.from_arrays({"k": np.zeros(8, np.int32)})
    with pytest.raises(ValueError, match="release"):
        ctx.release(src)
    c2 = src.group_by("k", {"c": ("count", None)}).cache()
    with pytest.raises(ValueError, match="release"):
        ctx.release(c2.where(lambda cols: cols["c"] > 0))


def test_order_by_direction_strings(ctx):
    """("col", "asc"/"desc") string directions parse correctly — a bare
    bool() would read the truthy "asc" as DESCENDING (silent wrong
    order) — and unknown direction strings raise."""
    tbl = {"a": np.array([5, 1, 9, 3], np.int32)}
    up = ctx.from_arrays(tbl).order_by([("a", "asc")]).collect()
    assert list(up["a"]) == [1, 3, 5, 9]
    down = ctx.from_arrays(tbl).order_by([("a", "desc")]).collect()
    assert list(down["a"]) == [9, 5, 3, 1]
    with pytest.raises(ValueError, match="direction"):
        ctx.from_arrays(tbl).order_by([("a", "ascending")]).collect()
