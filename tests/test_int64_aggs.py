"""INT64 aggregate arithmetic over split two-word device columns.

The reference supports Sum/Min/Max over all numeric types
(``LinqToDryad/DryadLinqQueryGen.cs:3439ff``); here int64 lives on
device as two uint32 words (``columnar/schema.py``) and the engine
reduces it with carry-propagating paired-word adds and
signed-lexicographic compares (``ops/segmented.py``).  Differential
tests against NumPy int64, including sums past 2^32.
"""

import numpy as np
import pytest

from dryad_tpu import DryadContext


def _run_group_by(tbl, aggs, order):
    ctx = DryadContext(num_partitions_=8)
    return ctx.from_arrays(tbl).group_by("k", aggs).order_by(order).collect()


def _oracle(tbl, aggs, order):
    dbg = DryadContext(local_debug=True)
    return dbg.from_arrays(tbl).group_by("k", aggs).order_by(order).collect()


@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_int64_group_aggregate_matches_numpy(op, rng):
    n = 2000
    tbl = {
        "k": rng.integers(0, 7, n).astype(np.int32),
        "v": rng.integers(-(2 ** 62), 2 ** 62, n).astype(np.int64),
    }
    out = _run_group_by(tbl, {"a": (op, "v")}, ["k"])
    assert out["a"].dtype == np.int64
    for i, k in enumerate(out["k"]):
        ref = getattr(np, op)(
            tbl["v"][tbl["k"] == k]
        ) if op != "sum" else tbl["v"][tbl["k"] == k].sum()
        assert out["a"][i] == ref, (k, op)


def test_int64_sum_past_2_32():
    """Carry propagation: many identical large values force low-word
    overflow into the high word."""
    n = 1024
    big = np.int64(3_000_000_007)  # > 2^31; n * big > 2^41
    tbl = {
        "k": (np.arange(n, dtype=np.int32) % 2),
        "v": np.full(n, big, np.int64),
    }
    out = _run_group_by(tbl, {"s": ("sum", "v")}, ["k"])
    assert out["s"].tolist() == [big * (n // 2)] * 2
    assert big * (n // 2) > 2 ** 32  # the test is vacuous otherwise


def test_int64_negative_min_max():
    """Signed-lexicographic compare: the high word is the signed word."""
    tbl = {
        "k": np.zeros(6, np.int32),
        "v": np.array(
            [-(2 ** 40), 2 ** 40, -1, 0, 5, -(2 ** 62)], np.int64
        ),
    }
    out = _run_group_by(
        tbl, {"lo": ("min", "v"), "hi": ("max", "v")}, ["k"]
    )
    assert out["lo"][0] == -(2 ** 62)
    assert out["hi"][0] == 2 ** 40


def test_int64_aggs_match_localdebug_oracle(rng):
    n = 1500
    tbl = {
        "k": rng.integers(0, 5, n).astype(np.int32),
        "v": rng.integers(-(2 ** 50), 2 ** 50, n).astype(np.int64),
        "f": rng.standard_normal(n).astype(np.float32),
    }
    aggs = {
        "s": ("sum", "v"), "mn": ("min", "v"), "mx": ("max", "v"),
        "c": ("count", None), "fs": ("sum", "f"),
    }
    out = _run_group_by(tbl, aggs, ["k"])
    ref = _oracle(tbl, aggs, ["k"])
    assert out["k"].tolist() == ref["k"].tolist()
    assert out["s"].tolist() == ref["s"].tolist()
    assert out["mn"].tolist() == ref["mn"].tolist()
    assert out["mx"].tolist() == ref["mx"].tolist()
    assert out["c"].tolist() == ref["c"].tolist()
    np.testing.assert_allclose(out["fs"], ref["fs"], rtol=1e-4)


def test_float64_preserved_roundtrip(rng):
    """float64 ingest is EXACT: order-preserving split-word storage
    round-trips every bit (no silent narrowing)."""
    vals = np.concatenate([
        rng.standard_normal(500) * 1e300,
        rng.standard_normal(500) * 1e-300,
        np.array([0.0, -0.0, np.inf, -np.inf, 1.5, -1.5]),
    ])
    ctx = DryadContext(num_partitions_=8)
    q = ctx.from_arrays({"x": vals})
    assert q.schema.field("x").ctype.value == "float64"
    out = ctx.from_arrays({"x": vals}).collect()
    assert out["x"].dtype == np.float64
    np.testing.assert_array_equal(np.sort(out["x"]), np.sort(vals))


def test_float64_order_by_min_max(rng):
    vals = rng.standard_normal(3000) * np.exp(
        rng.uniform(-200, 200, 3000)
    )
    k = rng.integers(0, 7, 3000).astype(np.int32)
    ctx = DryadContext(num_partitions_=8)
    srt = ctx.from_arrays({"x": vals}).order_by(["x"]).collect()
    np.testing.assert_array_equal(srt["x"], np.sort(vals))
    agg = (
        ctx.from_arrays({"k": k, "x": vals})
        .group_by("k", {"lo": ("min", "x"), "hi": ("max", "x")})
        .order_by(["k"])
        .collect()
    )
    for i, kk in enumerate(agg["k"]):
        sel = vals[k == kk]
        assert agg["lo"][i] == sel.min()
        assert agg["hi"][i] == sel.max()


def test_float64_sum_rejected_with_cast_hint(rng):
    ctx = DryadContext(num_partitions_=8)
    q = ctx.from_arrays(
        {"k": np.zeros(8, np.int32), "x": np.ones(8, np.float64)}
    ).group_by("k", {"s": ("sum", "x")})
    import pytest

    with pytest.raises(ValueError, match="float32"):
        q.collect()


def test_float64_ordered_image_bijection(rng):
    from dryad_tpu.columnar.schema import (
        f64_to_ordered_i64, ordered_i64_to_f64,
    )

    vals = np.concatenate([
        rng.standard_normal(2000) * np.exp(rng.uniform(-300, 300, 2000)),
        np.array([0.0, -0.0, np.inf, -np.inf]),
    ])
    img = f64_to_ordered_i64(vals)
    back = ordered_i64_to_f64(img)
    np.testing.assert_array_equal(back.view(np.uint64), vals.view(np.uint64))
    # order preservation: decoding the sorted images yields a
    # non-decreasing double sequence (the image orders -0.0 < +0.0,
    # which numpy's sort treats as a tie — hence <=, not array-equal)
    back_sorted = ordered_i64_to_f64(np.sort(img))
    assert np.all(back_sorted[:-1] <= back_sorted[1:])


def test_float64_survives_select(rng):
    """Schema inference keeps FLOAT64 for word pairs that survive a
    user select: a bare #h0/#h1 pair is ambiguous, so surviving names
    inherit the input type (review regression)."""
    vals = rng.standard_normal(256) * 1e200
    ctx = DryadContext(num_partitions_=8)
    out = (
        ctx.from_arrays({"x": vals})
        .select(lambda c: dict(c))
        .collect()
    )
    assert out["x"].dtype == np.float64
    np.testing.assert_array_equal(np.sort(out["x"]), np.sort(vals))


def test_scalar_aggregates_on_wide_types(rng):
    """sum_/min_/max_ scalar aggregates over int64 (exact, past 2^32)
    and float64 (totalOrder min/max), device vs LocalDebug oracle."""
    n = 3000
    tbl = {
        "v": rng.integers(-(2 ** 55), 2 ** 55, n).astype(np.int64),
        "d": rng.standard_normal(n) * np.exp(rng.uniform(-150, 150, n)),
    }
    dev = DryadContext(num_partitions_=8)
    dbg = DryadContext(local_debug=True)
    for ctx in (dev, dbg):
        q = ctx.from_arrays(tbl)
        assert q.sum_("v") == int(tbl["v"].sum())
        assert q.min_("v") == int(tbl["v"].min())
        assert q.max_("v") == int(tbl["v"].max())
        assert q.min_("d") == tbl["d"].min()
        assert q.max_("d") == tbl["d"].max()


def test_scalar_f64_sum_rejected(rng):
    ctx = DryadContext(num_partitions_=8)
    with pytest.raises(ValueError, match="float32"):
        ctx.from_arrays({"d": np.ones(8, np.float64)}).sum_("d")


def test_first_on_split_columns_matches_device(rng):
    """group_by first over STRING and INT64 columns: device expansion
    (per-word AggSpecs) vs the oracle's per-word first."""
    vocab = np.array(["aa", "bb", "cc", "dd"], object)
    n = 400
    tbl = {
        "k": rng.integers(0, 5, n).astype(np.int32),
        "s": vocab[rng.integers(0, 4, n)],
        "w": rng.integers(-(2 ** 40), 2 ** 40, n).astype(np.int64),
    }
    aggs = {"fs": ("first", "s"), "fw": ("first", "w")}
    dev = DryadContext(num_partitions_=8)
    out = dev.from_arrays(tbl).group_by("k", aggs).order_by(["k"]).collect()
    dbg = DryadContext(local_debug=True)
    ref = dbg.from_arrays(tbl).group_by("k", aggs).order_by(["k"]).collect()
    assert out["k"].tolist() == ref["k"].tolist()
    # first is position-dependent and engines enumerate rows in
    # different orders, so check TYPE fidelity + membership per group
    assert out["fw"].dtype == np.int64 and ref["fw"].dtype == np.int64
    for i, kk in enumerate(out["k"]):
        members_w = set(tbl["w"][tbl["k"] == kk].tolist())
        members_s = set(tbl["s"][tbl["k"] == kk].tolist())
        assert int(out["fw"][i]) in members_w and int(ref["fw"][i]) in members_w
        assert out["fs"][i] in members_s and ref["fs"][i] in members_s


def test_unsupported_split_aggs_raise_in_both_engines():
    tbl = {
        "k": np.zeros(8, np.int32),
        "w": np.ones(8, np.int64),
        "s": np.array(["x"] * 8, object),
    }
    for ctx in (DryadContext(num_partitions_=8), DryadContext(local_debug=True)):
        q = ctx.from_arrays(tbl).group_by("k", {"a": ("any", "w")})
        with pytest.raises(ValueError, match="unsupported"):
            q.collect()
        q2 = ctx.from_arrays(tbl).group_by("k", {"ss": ("sum", "s")})
        with pytest.raises(ValueError, match="unsupported"):
            q2.collect()


def test_int64_mean_group_and_scalar(rng):
    """Average over long (reference numeric overloads): exact sum64 +
    count partials, f32 divide — group and scalar forms, both engines."""
    n = 2000
    tbl = {
        "k": rng.integers(0, 6, n).astype(np.int32),
        "v": rng.integers(-(2 ** 45), 2 ** 45, n).astype(np.int64),
    }
    out = _run_group_by(tbl, {"m": ("mean", "v")}, ["k"])
    ref = _oracle(tbl, {"m": ("mean", "v")}, ["k"])
    assert out["k"].tolist() == ref["k"].tolist()
    np.testing.assert_allclose(out["m"], ref["m"], rtol=1e-5)
    for i, kk in enumerate(out["k"]):
        expect = tbl["v"][tbl["k"] == kk].astype(np.float64).mean()
        np.testing.assert_allclose(out["m"][i], expect, rtol=1e-5)

    dev = DryadContext(num_partitions_=8)
    got = dev.from_arrays(tbl).mean("v")
    np.testing.assert_allclose(
        got, tbl["v"].astype(np.float64).mean(), rtol=1e-5
    )


def test_empty_minmax_identity_matches_across_engines():
    """Empty-input 64-bit min/max via aggregate_as_query yields the op
    identity in BOTH engines (device pair-identity semantics)."""
    tbl = {"v": np.zeros(0, np.int64)}
    for ctx in (DryadContext(num_partitions_=8), DryadContext(local_debug=True)):
        out = ctx.from_arrays(tbl).aggregate_as_query(
            {"lo": ("min", "v"), "hi": ("max", "v")}
        ).collect()
        assert out["lo"][0] == np.iinfo(np.int64).max
        assert out["hi"][0] == np.iinfo(np.int64).min


def test_dense_group_by_rejects_wide_columns():
    tbl = {"k": np.zeros(8, np.int32), "w": np.ones(8, np.int64)}
    ctx = DryadContext(num_partitions_=8)
    with pytest.raises(ValueError, match="sort-based"):
        ctx.from_arrays(tbl).group_by("k", {"m": ("mean", "w")}, dense=4)


@pytest.mark.parametrize("op", ["sum", "min", "max", "mean"])
@pytest.mark.parametrize("rows,partitions", [(1, 1), (5, 8), (1000, 4), (4097, 1), (4097, 8)])
def test_whole_column_int64_reduce_is_exact_at_any_row_count(op, rows, partitions, rng):
    """The whole-column 64-bit reduce (``ops/segmented.py::
    pair_scalar_reduce``: a halving tree over the slots padded to a
    power of two, the partitions' pairs gathered and reduced the same
    way) over row counts that are no power of two, with rows a ``where``
    made invalid, wrapping past 2^63 as NumPy's int64 does."""
    v = rng.integers(-(2 ** 62), 2 ** 62, rows).astype(np.int64)
    keep = rng.random(rows) < 0.7
    keep[0] = True
    ctx = DryadContext(num_partitions_=partitions)
    q = ctx.from_arrays({"v": v, "keep": keep}).where(lambda c: c["keep"])
    got = {"sum": q.sum_, "min": q.min_, "max": q.max_, "mean": q.mean}[op]("v")
    with np.errstate(over="ignore"):
        want = {"sum": v[keep].sum(), "min": v[keep].min(), "max": v[keep].max(),
                "mean": np.float64(v[keep].sum()) / keep.sum()}[op]
    if op == "mean":
        assert got == pytest.approx(float(want), rel=1e-6, abs=1e-6)
    else:
        assert got == int(want)
