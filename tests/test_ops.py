"""Device op tests: hashing, sort keys, segmented reduce (single device)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dryad_tpu.columnar.batch import ColumnBatch
from dryad_tpu.columnar.schema import ColumnType, Schema
from dryad_tpu.ops.hash import hash_columns, partition_ids
from dryad_tpu.ops.segmented import AggSpec, distinct, group_combine, group_reduce
from dryad_tpu.ops.sortkeys import sort_order, to_sortable_u32


def test_hash_columns_deterministic():
    a = jnp.array([1, 2, 3, 1], dtype=jnp.int32)
    h1 = hash_columns([a])
    h2 = hash_columns([a])
    assert np.array_equal(np.asarray(h1), np.asarray(h2))
    assert np.asarray(h1)[0] == np.asarray(h1)[3]
    assert np.asarray(h1)[0] != np.asarray(h1)[1]


def test_partition_ids_range():
    a = jnp.arange(1000, dtype=jnp.int32)
    p = partition_ids([a], 8)
    p = np.asarray(p)
    assert p.min() >= 0 and p.max() < 8
    # roughly uniform
    counts = np.bincount(p, minlength=8)
    assert counts.min() > 60


def test_sortable_u32_orders():
    ints = np.array([-5, -1, 0, 3, 100], dtype=np.int32)
    k = np.asarray(to_sortable_u32(jnp.asarray(ints)))
    assert list(np.argsort(k)) == list(range(5))
    floats = np.array([-np.inf, -2.5, -0.0, 0.0, 1.5, np.inf], dtype=np.float32)
    kf = np.asarray(to_sortable_u32(jnp.asarray(floats)))
    assert (np.diff(kf.astype(np.int64)) >= 0).all()
    kd = np.asarray(to_sortable_u32(jnp.asarray(floats), descending=True))
    assert (np.diff(kd.astype(np.int64)) <= 0).all()


def test_sort_order_invalid_last():
    schema = Schema([("n", ColumnType.INT32)])
    b = ColumnBatch.from_numpy(
        schema, {"n": np.array([5, 1, 4, 2], dtype=np.int32)}, capacity=6
    )
    order = sort_order([b["n"]], b.valid)
    sb = b.take(order)
    assert np.array_equal(np.asarray(sb["n"])[:4], [1, 2, 4, 5])
    assert not np.asarray(sb.valid)[4:].any()


def _mk_kv(keys, vals, cap):
    schema = Schema([("k", ColumnType.INT32), ("v", ColumnType.FLOAT32)])
    return ColumnBatch.from_numpy(
        schema,
        {"k": np.array(keys, np.int32), "v": np.array(vals, np.float32)},
        capacity=cap,
    )


def test_group_reduce_sum_count_min_max_mean():
    b = _mk_kv([3, 1, 3, 2, 1, 3], [1.0, 2.0, 3.0, 4.0, 5.0, 6.0], cap=8)
    out = group_reduce(
        b,
        ["k"],
        [
            AggSpec("sum", "v", "s"),
            AggSpec("count", None, "c"),
            AggSpec("min", "v", "mn"),
            AggSpec("max", "v", "mx"),
            AggSpec("mean", "v", "avg"),
        ],
    )
    valid = np.asarray(out.valid)
    assert valid.sum() == 3
    k = np.asarray(out["k"])[valid]
    s = np.asarray(out["s"])[valid]
    c = np.asarray(out["c"])[valid]
    mn = np.asarray(out["mn"])[valid]
    mx = np.asarray(out["mx"])[valid]
    avg = np.asarray(out["avg"])[valid]
    got = {int(kk): (float(ss), int(cc), float(m1), float(m2), float(a)) for kk, ss, cc, m1, m2, a in zip(k, s, c, mn, mx, avg)}
    want = {
        1: (7.0, 2, 2.0, 5.0, 3.5),
        2: (4.0, 1, 4.0, 4.0, 4.0),
        3: (10.0, 3, 1.0, 6.0, 10.0 / 3),
    }
    assert sorted(got) == sorted(want)
    for kk, exp in want.items():
        np.testing.assert_allclose(got[kk], exp, rtol=1e-6)


def test_group_reduce_under_jit():
    b = _mk_kv([1, 1, 2], [1.0, 2.0, 3.0], cap=4)
    fn = jax.jit(
        lambda bb: group_reduce(bb, ["k"], [AggSpec("sum", "v", "s")])
    )
    out = fn(b)
    valid = np.asarray(out.valid)
    assert valid.sum() == 2


def test_group_combine_generic_merge():
    # accumulator = (sum, count) expressed as two state columns, merged pairwise
    b = _mk_kv([1, 2, 1, 1], [10.0, 20.0, 30.0, 2.0], cap=6)
    b = b.with_column("cnt", jnp.ones((6,), jnp.float32))

    def merge(a, c):
        return {"v": a["v"] + c["v"], "cnt": a["cnt"] + c["cnt"]}

    out = group_combine(b, ["k"], ["v", "cnt"], merge)
    valid = np.asarray(out.valid)
    k = np.asarray(out["k"])[valid]
    v = np.asarray(out["v"])[valid]
    cnt = np.asarray(out["cnt"])[valid]
    got = {int(kk): (float(vv), float(cc)) for kk, vv, cc in zip(k, v, cnt)}
    assert got == {1: (42.0, 3.0), 2: (20.0, 1.0)}


@pytest.mark.parametrize("n", [1, 2, 7, 64, 257])
def test_segmented_scan_keeps_the_order_of_a_merge_that_does_not_commute(n):
    """``segmented_scan`` (the doubling scan of ``group_combine``)
    against a fold row by row, under a merge that is associative and
    NOT commutative: affine maps composed, ``(a then b)(x) = b.m * (a.m
    x + a.c) + b.c`` in integers mod 2^32, with a two-word column beside
    it.  The earlier rows are always ``merge``'s first argument, and a
    segment's reduction stops at its start whatever the distance."""
    from dryad_tpu.ops.segmented import segmented_scan

    rng = np.random.default_rng(n)
    start = rng.random(n) < 0.2
    start[0] = True
    m = rng.integers(1, 1 << 16, n).astype(np.uint32)
    c = rng.integers(0, 1 << 16, n).astype(np.uint32)
    wide = rng.integers(0, 9, (n, 2)).astype(np.int32)

    def merge(a, b):
        return {"m": a["m"] * b["m"], "c": b["m"] * a["c"] + b["c"],
                "w": a["w"] + b["w"]}

    got = segmented_scan(jnp.asarray(start),
                         {"m": jnp.asarray(m), "c": jnp.asarray(c),
                          "w": jnp.asarray(wide)}, merge)
    want_m, want_c, want_w = m.copy(), c.copy(), wide.copy()
    with np.errstate(over="ignore"):  # mod 2^32, as on the device
        for i in range(1, n):
            if not start[i]:
                want_m[i] = want_m[i - 1] * m[i]
                want_c[i] = m[i] * want_c[i - 1] + c[i]
                want_w[i] = want_w[i - 1] + wide[i]
    assert np.array_equal(np.asarray(got["m"]), want_m)
    assert np.array_equal(np.asarray(got["c"]), want_c)
    assert np.array_equal(np.asarray(got["w"]), want_w)


def test_distinct():
    b = _mk_kv([1, 2, 1, 2, 3], [9.0, 9.0, 9.0, 9.0, 9.0], cap=8)
    out = distinct(b, ["k"])
    valid = np.asarray(out.valid)
    assert sorted(np.asarray(out["k"])[valid].tolist()) == [1, 2, 3]
