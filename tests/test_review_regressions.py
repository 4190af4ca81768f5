"""Regressions for planner/metadata correctness bugs + newer operators."""

import numpy as np
import pytest

from dryad_tpu import ColumnType, DryadContext, Schema
from oracle import check


@pytest.fixture
def ctx(mesh8):
    return DryadContext(num_partitions_=8)


@pytest.fixture
def dbg():
    return DryadContext(local_debug=True)


def test_select_invalidates_partition_metadata(ctx, dbg):
    """select may rewrite key values; a following group_by must reshuffle."""
    tbl = {"k": np.arange(8, dtype=np.int32)}

    def q(c):
        return (
            c.from_arrays(tbl)
            .hash_partition("k")
            .select(lambda cols: {"k": cols["k"] % 2})
            .group_by("k", {"c": ("count", None)})
            .collect()
        )

    got = q(ctx)
    want = {int(k): int(v) for k, v in zip(got["k"], got["c"])}
    assert want == {0: 4, 1: 4}
    check(q(ctx), q(dbg))


def test_reorder_descending_after_ascending(ctx):
    """Direction-blind shuffle elision regression: desc after asc must
    re-exchange (or at least produce the right global order)."""
    a = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)
    got = (
        DryadContext(num_partitions_=8)
        .from_arrays({"a": a})
        .order_by(["a"])
        .order_by([("a", True)])
        .collect()
    )
    assert got["a"].tolist() == sorted(a.tolist(), reverse=True)


def test_store_partitions_fold_onto_smaller_mesh(tmp_path, mesh8):
    """A store written with more partitions than the mesh must not drop rows."""
    from dryad_tpu.columnar.io import read_store, write_store

    schema = Schema([("x", ColumnType.INT32)])
    parts = [
        {"x": np.array([0, 1], np.int32)},
        {"x": np.array([10, 11], np.int32)},
        {"x": np.array([20, 21], np.int32)},
        {"x": np.array([30, 31], np.int32)},
        {"x": np.array([40], np.int32)},
        {"x": np.array([50], np.int32)},
        {"x": np.array([60], np.int32)},
        {"x": np.array([70], np.int32)},
        {"x": np.array([80], np.int32)},
        {"x": np.array([90], np.int32)},
    ]
    path = str(tmp_path / "store10")
    write_store(path, parts, schema)
    ctx = DryadContext(num_partitions_=8)
    got = ctx.from_store(path).collect()
    want = sorted(v for p in parts for v in p["x"].tolist())
    assert sorted(got["x"].tolist()) == want


def test_join_suffix_on_split_columns(ctx, dbg):
    """Clashing non-key INT64/STRING columns must suffix logically."""
    left = {
        "k": np.arange(6, dtype=np.int32),
        "v": np.arange(6, dtype=np.int64) * 10,
    }
    right = {
        "k": np.arange(6, dtype=np.int32),
        "v": np.arange(6, dtype=np.int64) * 100,
    }

    def q(c):
        return c.from_arrays(left).join(c.from_arrays(right), "k").collect()

    got = q(ctx)
    assert sorted(got.keys()) == ["k", "v", "v_r"]
    order = np.argsort(got["k"])
    assert got["v"][order].tolist() == [i * 10 for i in range(6)]
    assert got["v_r"][order].tolist() == [i * 100 for i in range(6)]
    check(q(ctx), q(dbg))


def test_first_agg_on_split_column(ctx, dbg):
    tbl = {
        "g": np.array([1, 1, 2, 2], np.int32),
        "n": np.array([7, 8, 9, 10], np.int64),
        "w": np.array(["a", "b", "c", "d"], object),
    }

    def q(c):
        return (
            c.from_arrays(tbl)
            .group_by("g", {"fn": ("first", "n"), "fw": ("first", "w")})
            .collect()
        )

    got = q(ctx)
    by_g = {int(g): (int(n), w) for g, n, w in zip(got["g"], got["fn"], got["fw"])}
    # 'first' within a group is engine-order dependent; check membership.
    assert by_g[1][0] in (7, 8) and by_g[1][1] in ("a", "b")
    assert by_g[2][0] in (9, 10) and by_g[2][1] in ("c", "d")


def test_select_many_growth_no_boost_retry(ctx):
    """Stage growth must size resizes so select_many doesn't always
    trip the overflow retry."""
    tbl = {"x": np.arange(256, dtype=np.int32)}
    import jax.numpy as jnp

    def explode(cols):
        x = cols["x"]
        out = {"y": jnp.stack([x, x + 1000, x + 2000, x + 3000], axis=1)}
        valid = jnp.ones((x.shape[0], 4), jnp.bool_)
        return out, valid

    q = ctx.from_arrays(tbl).select_many(explode, 4).group_by(
        "y", {"c": ("count", None)}
    )
    got = q.collect()
    assert len(got["y"]) == 1024
    kinds = [e["kind"] for e in ctx.events.events()]
    assert "stage_overflow" not in kinds, "growth-aware resize should prevent retry"


def test_zip(ctx, dbg):
    a = {"x": np.arange(20, dtype=np.int32)}
    b = {"y": (np.arange(17) * 2).astype(np.int32)}

    def q(c):
        return c.from_arrays(a).zip_(c.from_arrays(b)).collect()

    got = q(ctx)
    assert len(got["x"]) == 17  # truncates to shorter
    pairs = sorted(zip(got["x"].tolist(), got["y"].tolist()))
    assert pairs == [(i, 2 * i) for i in range(17)]
    check(q(ctx), q(dbg))


def test_zip_clash_suffix(ctx):
    a = {"x": np.arange(10, dtype=np.int32)}
    b = {"x": (np.arange(10) + 100).astype(np.int32)}
    got = ctx.from_arrays(a).zip_(ctx.from_arrays(b)).collect()
    assert sorted(got.keys()) == ["x", "x_r"]
    pairs = sorted(zip(got["x"].tolist(), got["x_r"].tolist()))
    assert pairs == [(i, i + 100) for i in range(10)]


def test_sliding_window(ctx, dbg):
    tbl = {"x": np.arange(40, dtype=np.int32)}

    def q(c):
        return c.from_arrays(tbl).sliding_window(3, "x").collect()

    got = q(ctx)
    assert sorted(got.keys()) == ["x_w0", "x_w1", "x_w2"]
    rows = sorted(zip(got["x_w0"], got["x_w1"], got["x_w2"]))
    assert rows == [(i, i + 1, i + 2) for i in range(38)]
    check(q(ctx), q(dbg))


def test_group_join_count(ctx, dbg):
    left = {"k": np.array([1, 2, 3, 4], np.int32)}
    right = {"k": np.array([1, 1, 3, 3, 3, 9], np.int32)}

    def q(c):
        return (
            c.from_arrays(left)
            .group_join_count(c.from_arrays(right), "k")
            .collect()
        )

    got = q(ctx)
    by_k = dict(zip(got["k"].tolist(), got["match_count"].tolist()))
    assert by_k == {1: 2, 2: 0, 3: 3, 4: 0}
    check(q(ctx), q(dbg))


def test_from_text_trailing_empty_partitions(ctx):
    """9 tokens on 8 partitions: per=2 leaves partition 5+ empty."""
    got = ctx.from_text("a b c d e f g h i").collect()
    assert sorted(got["word"]) == sorted("a b c d e f g h i".split())
    # 1 token on 8 partitions: 7 empty partitions
    got1 = ctx.from_text("solo").collect()
    assert got1["word"].tolist() == ["solo"]


def test_compile_cache_not_fooled_by_id_reuse(ctx):
    """A GC'd lambda's id may be reused; the cache must not serve the
    old program for a structurally-identical op with a new fn."""
    tbl = {"x": np.arange(16, dtype=np.int32)}
    q1 = ctx.from_arrays(tbl).select(lambda c: {"x": c["x"] * 2})
    r1 = q1.collect()
    assert sorted(r1["x"].tolist()) == [2 * i for i in range(16)]
    del q1
    import gc

    gc.collect()
    q2 = ctx.from_arrays(tbl).select(lambda c: {"x": c["x"] + 1})
    r2 = q2.collect()
    assert sorted(r2["x"].tolist()) == [i + 1 for i in range(16)]


def test_take_negative_is_empty(ctx, dbg):
    tbl = {"x": np.arange(10, dtype=np.int32)}
    got = ctx.from_arrays(tbl).take(-3).collect()
    assert len(got["x"]) == 0
    got0 = ctx.from_arrays(tbl).take(0).collect()
    assert len(got0["x"]) == 0


def test_sliding_window_spans_multiple_partitions(ctx, dbg):
    # w-1 = 5 > rows per partition (40/8 = 5 dense, but filtering leaves
    # sparse partitions) -> windows must cross several partitions.
    tbl = {"x": np.arange(40, dtype=np.int32)}

    def q(c):
        return (
            c.from_arrays(tbl)
            .where(lambda cols: cols["x"] % 3 != 1)  # ragged partitions
            .sliding_window(6, "x")
            .collect()
        )

    got = q(ctx)
    xs = [x for x in range(40) if x % 3 != 1]
    expect = sorted(
        tuple(xs[i + j] for j in range(6)) for i in range(len(xs) - 5)
    )
    rows = sorted(zip(*[got[f"x_w{j}"] for j in range(6)]))
    assert [tuple(int(v) for v in r) for r in rows] == expect
    check(q(ctx), q(dbg))


def test_sliding_window_wider_than_partition(ctx, dbg):
    # Window of 12 over 8 partitions of ~3 rows each: halo needs 11 rows
    # from up to 4 successor partitions.
    tbl = {"x": np.arange(24, dtype=np.int32)}

    def q(c):
        return c.from_arrays(tbl).sliding_window(12, "x").collect()

    got = q(ctx)
    rows = sorted(zip(*[got[f"x_w{j}"] for j in range(12)]))
    assert [tuple(int(v) for v in r) for r in rows] == [
        tuple(range(i, i + 12)) for i in range(13)
    ]
    check(q(ctx), q(dbg))


def test_rank_limit_accepts_numpy_integers(ctx, dbg):
    """ADVICE r4: np.int32(2) is a valid positive rank_limit."""
    left = {"k": np.array([1, 1, 2], dtype=np.int32)}
    right = {"k": np.array([1, 1, 1, 2], dtype=np.int32),
             "v": np.arange(4, dtype=np.int32)}
    sel = lambda p: p.where(lambda c: c["gj_rank"] < 2).group_by(
        "gj_lid", {"s": ("sum", "v")})

    def q(c):
        return (
            c.from_arrays(left)
            .group_join(c.from_arrays(right), ["k"], ["k"],
                        selector=sel, order=["v"],
                        rank_limit=np.int32(2))
            .collect()
        )

    check(q(ctx), q(dbg))
    for bad in (np.int32(0), True, np.True_):
        c2 = DryadContext(num_partitions_=8)
        with pytest.raises(ValueError):
            c2.from_arrays(left).group_join(
                c2.from_arrays(right), ["k"], ["k"], selector=sel,
                rank_limit=bad)


def test_deferred_abort_emits_job_failed(ctx, monkeypatch):
    """ADVICE r4: a failed output transfer must close out the job in the
    event log (job_failed) instead of leaving it dangling."""
    from dryad_tpu.columnar.batch import ColumnBatch

    q = ctx.from_arrays({"x": np.arange(16, dtype=np.int32)}).select(
        lambda cols: {"x": cols["x"] + 1}
    )

    def boom(self, extra=(), tracer=None, metrics=None):
        raise RuntimeError("transfer died")

    monkeypatch.setattr(ColumnBatch, "fetch_host", boom)
    with pytest.raises(RuntimeError, match="transfer died"):
        q.collect()
    kinds = [e["kind"] for e in ctx.executor.events.events()]
    assert "job_failed" in kinds
