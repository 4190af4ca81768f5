"""A group's result is placed by ONE compaction of the run-end rows
(``ops/segmented.py::compact_rows``, PR 47), not by a scatter a column.

Four things are held here: the compaction against NumPy; the folds
built on it against the scatter fold they replaced, which moved into
this file as the oracle (as ``tests/test_shuffle.py::_scatter_exchange``
did in PR 43); the scan's passes as one loop body against the passes
unrolled, also kept here; and the lowered programs, which hold no
``stablehlo.scatter`` where the oracle's do.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dryad_tpu.columnar.batch import ColumnBatch
from dryad_tpu.ops.segmented import (
    PAIR_OPS,
    AggSpec,
    _pair_combine,
    compact_rows,
    distinct,
    group_combine,
    group_reduce,
    segmented_scan,
)
from dryad_tpu.ops.sort import sort_batch_by_operands
from dryad_tpu.ops.sortkeys import keys_equal_adjacent, to_sortable_u32

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- (a) the compaction against NumPy -----------------------------------------

def _mask(kind, n, rng):
    if kind == "none":
        return np.zeros(n, bool)
    if kind == "all":
        return np.ones(n, bool)
    if kind == "tail":  # every kept row has the whole array to cross
        return np.arange(n) >= n - max(1, n // 5)
    if kind == "head":
        return np.arange(n) < max(1, n // 5)
    return rng.random(n) < {"sparse": 0.02, "half": 0.5, "dense": 0.9}[kind]


@pytest.mark.parametrize("n", [1, 7, 8, 1000, 4096])
@pytest.mark.parametrize("kind", ["none", "all", "tail", "head", "sparse", "half", "dense"])
def test_the_compaction_is_numpys_mask_then_zeros(kind, n):
    rng = np.random.default_rng(n)
    keep = _mask(kind, n, rng)
    cols = {
        "i": rng.integers(-2**31, 2**31 - 1, n).astype(np.int32),
        "f": rng.standard_normal(n).astype(np.float32),
        "b": rng.random(n) < 0.5,
        "wide": rng.integers(0, 2**32, (n, 3), dtype=np.uint32),  # two-dimensional
    }
    got, origin = jax.jit(compact_rows)(
        jnp.asarray(keep), {k: jnp.asarray(c) for k, c in cols.items()})
    count = int(keep.sum())
    for name, col in cols.items():
        want = np.zeros_like(col)
        want[:count] = col[keep]
        assert np.asarray(got[name]).dtype == col.dtype
        assert np.array_equal(np.asarray(got[name]), want), name
    want = np.full(n, -1, np.int32)
    want[:count] = np.flatnonzero(keep)
    assert np.array_equal(np.asarray(origin), want)


# -- (b) the scatter fold the compaction replaced, as the oracle --------------

def _scatter_layout(batch, key_cols):
    cap = batch.capacity
    sb = sort_batch_by_operands(
        batch, [to_sortable_u32(batch.data[k]) for k in key_cols])
    v = sb.valid
    start = v & ~keys_equal_adjacent([sb.data[k] for k in key_cols])
    seg = jnp.where(v, jnp.cumsum(start.astype(jnp.int32)) - 1, cap)
    nxt_start = jnp.concatenate([start[1:], jnp.array([True])])
    nxt_valid = jnp.concatenate([v[1:], jnp.array([False])])
    last = v & (nxt_start | ~nxt_valid)
    return sb, v, start, last, seg, jnp.sum(start.astype(jnp.int32))


def _first_scatter(val, start, seg, cap):
    idx = jnp.where(start, seg, cap)
    return jnp.zeros((cap + 1,) + val.shape[1:], val.dtype).at[idx].set(val)[:cap]


def _scatter_pair_reduce(op, lo, hi, start, last, seg, cap):
    base = _pair_combine(op)

    def combine(a, b):
        fa, alo, ahi = a
        fb, blo, bhi = b
        mlo, mhi = base(alo, ahi, blo, bhi)
        return fa | fb, jnp.where(fb, blo, mlo), jnp.where(fb, bhi, mhi)

    _, slo, shi = jax.lax.associative_scan(combine, (start, lo, hi))
    idx = jnp.where(last, seg, cap)
    return (jnp.zeros((cap + 1,), lo.dtype).at[idx].set(slo)[:cap],
            jnp.zeros((cap + 1,), hi.dtype).at[idx].set(shi)[:cap])


def _scatter_group_reduce(batch, key_cols, aggs):
    """``group_reduce`` as it stood through PR 46."""
    sb, v, start, last, seg, nseg = _scatter_layout(batch, key_cols)
    cap, nsegments = sb.capacity, sb.capacity + 1
    out = {k: _first_scatter(sb.data[k], start, seg, cap) for k in key_cols}
    nvalid = jnp.sum(v.astype(jnp.int32))
    start_pos = (
        jnp.full((cap + 2,), nvalid, jnp.int32)
        .at[jnp.where(start, seg, cap + 2)]
        .set(jnp.arange(cap, dtype=jnp.int32), mode="drop")[: cap + 1]
    )
    seg_count = start_pos[1:] - start_pos[:cap]
    for a in aggs:
        if a.op == "count":
            out[a.out] = seg_count
            continue
        if a.op in PAIR_OPS:
            hi_col = a.col[: -len("#h0")] + "#h1"
            out[f"{a.out}#h0"], out[f"{a.out}#h1"] = _scatter_pair_reduce(
                a.op, sb.data[a.col], sb.data[hi_col], start, last, seg, cap)
            continue
        col = sb.data[a.col]
        if a.op == "sum":
            out[a.out] = jax.ops.segment_sum(col, seg, nsegments)[:cap]
        elif a.op == "min":
            out[a.out] = jax.ops.segment_min(col, seg, nsegments)[:cap]
        elif a.op == "max":
            out[a.out] = jax.ops.segment_max(col, seg, nsegments)[:cap]
        elif a.op == "mean":
            s = jax.ops.segment_sum(col.astype(jnp.float32), seg, nsegments)[:cap]
            out[a.out] = s / jnp.maximum(seg_count.astype(jnp.float32), 1.0)
        elif a.op == "any":
            m = jax.ops.segment_max(col.astype(jnp.int32), seg, nsegments)[:cap]
            out[a.out] = m.astype(jnp.bool_)
        elif a.op == "all":
            m = jax.ops.segment_min(
                jnp.where(v, col, True).astype(jnp.int32), seg, nsegments)[:cap]
            out[a.out] = m.astype(jnp.bool_)
        elif a.op == "first":
            out[a.out] = _first_scatter(col, start, seg, cap)
    return ColumnBatch(out, jnp.arange(cap, dtype=jnp.int32) < nseg)


def _unrolled_scan(start, vals, merge):
    """``segmented_scan`` as it stood through PR 46: a pass a distance,
    each reading ``i - d`` through a static slice."""
    n = start.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    flag, d = start, 1
    while d < n:
        def back(x, d=d):
            return jnp.concatenate([x[:d], x[:-d]])

        reach = pos >= d
        merged = merge({k: back(x) for k, x in vals.items()}, vals)
        take = reach & ~flag
        vals = {k: jnp.where(take.reshape((n,) + (1,) * (x.ndim - 1)), merged[k], x)
                for k, x in vals.items()}
        flag = flag | (reach & back(flag))
        d *= 2
    return vals


def _scatter_group_combine(batch, key_cols, state_cols, merge):
    """``group_combine`` as it stood through PR 46."""
    sb, v, start, last, seg, nseg = _scatter_layout(batch, key_cols)
    cap = sb.capacity
    scanned = _unrolled_scan(start, {c: sb.data[c] for c in state_cols}, merge)
    out = {k: _first_scatter(sb.data[k], start, seg, cap) for k in key_cols}
    idx = jnp.where(last, seg, cap)
    for c in state_cols:
        val = scanned[c]
        out[c] = jnp.zeros((cap + 1,) + val.shape[1:], val.dtype).at[idx].set(val)[:cap]
    return ColumnBatch(out, jnp.arange(cap, dtype=jnp.int32) < nseg)


def _scatter_distinct(batch, key_cols):
    others = [c for c in batch.columns if c not in set(key_cols)]
    return _scatter_group_reduce(
        batch, key_cols, [AggSpec("first", c, c) for c in others])


def _skew_job():
    spec = importlib.util.spec_from_file_location(
        "bench_job_groupby_skew_place",
        os.path.join(ROOT, "benchmarks", "jobs", "groupby_skew.py"))
    job = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(job)
    return job


CAP = 96
BATCHES = ["invalid_in_the_middle", "one_run", "every_row_its_own_run", "two_key_columns"]


def _batch(kind):
    """Rows for every fold at once: keys ``k`` (and ``k2``), an int32, an
    f32 and a bool value, a split 64-bit one, the skew job's state."""
    rng = np.random.default_rng(BATCHES.index(kind))
    valid = np.ones(CAP, bool)
    k2 = np.zeros(CAP, np.int32)
    if kind == "invalid_in_the_middle":
        k = rng.integers(-4, 9, CAP)
        valid[20:45] = False
        valid[-7:] = False
    elif kind == "one_run":
        k = np.full(CAP, 7)
        valid[-5:] = False
    elif kind == "every_row_its_own_run":
        k = rng.permutation(CAP) - 40  # no slot free: the last row ends the array
    else:
        k = rng.integers(0, 4, CAP)
        k2 = rng.integers(-2, 2, CAP)
        valid[rng.random(CAP) < 0.2] = False
    wide = rng.integers(-(2**62), 2**62, CAP).astype(np.int64).view(np.uint64)
    v = rng.standard_normal(CAP).astype(np.float32)
    data = {
        "k": k.astype(np.int32), "k2": k2.astype(np.int32),
        "i": rng.integers(-2**30, 2**30, CAP).astype(np.int32),
        "v": v, "b": rng.random(CAP) < 0.6,
        "w#h0": (wide & 0xFFFFFFFF).astype(np.uint32), "w#h1": (wide >> 32).astype(np.uint32),
        "n": np.ones(CAP, np.int32), "ts": rng.permutation(CAP).astype(np.int32),
        "last": v, "mean": v, "m2": np.zeros(CAP, np.float32),
    }
    return ColumnBatch({n: jnp.asarray(c) for n, c in data.items()}, jnp.asarray(valid))


def _keys(kind):
    return ["k", "k2"] if kind == "two_key_columns" else ["k"]


def _same_groups(got, want):
    """Equal where the oracle has a group, nothing past it: integers,
    words and flags to the bit, f32 to the tests' tolerance (an f32 sum
    is now added in the scan's order)."""
    assert got.columns == want.columns
    valid = np.asarray(want.valid)
    assert np.array_equal(np.asarray(got.valid), valid)
    for name in want.columns:
        g, w = np.asarray(got[name]), np.asarray(want[name])
        assert g.dtype == w.dtype, name
        if g.dtype == np.float32:
            np.testing.assert_allclose(g[valid], w[valid], rtol=1e-6, atol=1e-6, err_msg=name)
        else:
            assert np.array_equal(g[valid], w[valid]), name
        assert not g[~valid].any(), f"{name}: a slot past the last group is not zero"


AGGS = {
    "sum": AggSpec("sum", "v", "o"), "sum_int": AggSpec("sum", "i", "o"),
    "count": AggSpec("count", None, "o"),
    "min": AggSpec("min", "v", "o"), "max": AggSpec("max", "i", "o"),
    "mean": AggSpec("mean", "v", "o"), "mean_int": AggSpec("mean", "i", "o"),
    "any": AggSpec("any", "b", "o"), "all": AggSpec("all", "b", "o"),
    "first": AggSpec("first", "v", "o"),
    "sum64": AggSpec("sum64", "w#h0", "o"), "min64": AggSpec("min64", "w#h0", "o"),
    "max64": AggSpec("max64", "w#h0", "o"),
}


@pytest.mark.parametrize("kind", BATCHES)
@pytest.mark.parametrize("agg", list(AGGS))
def test_group_reduce_equals_the_scatter_fold(agg, kind):
    batch, keys = _batch(kind), _keys(kind)
    # with a count beside it, as every plan's combiner has one
    aggs = [AGGS[agg], AggSpec("count", None, "c")] if agg != "count" else [AGGS[agg]]
    _same_groups(jax.jit(lambda b: group_reduce(b, keys, aggs))(batch),
                 _scatter_group_reduce(batch, keys, aggs))


@pytest.mark.parametrize("kind", BATCHES)
def test_group_reduce_of_all_aggregates_at_once_equals_the_scatter_fold(kind):
    batch, keys = _batch(kind), _keys(kind)
    aggs = [AggSpec(a.op, a.col, name) for name, a in AGGS.items()]
    _same_groups(group_reduce(batch, keys, aggs), _scatter_group_reduce(batch, keys, aggs))


@pytest.mark.parametrize("kind", BATCHES)
def test_group_combine_under_the_skew_jobs_merge_equals_the_scatter_emit(kind):
    """The scan is the parent's, so every word is the parent's too."""
    batch, keys, job = _batch(kind), _keys(kind), _skew_job()
    state = ["n", "ts", "last", "mean", "m2"]
    batch = ColumnBatch({c: batch.data[c] for c in keys + state}, batch.valid)
    got = jax.jit(lambda b: group_combine(b, keys, state, job.merge))(batch)
    want = _scatter_group_combine(batch, keys, state, job.merge)
    _same_groups(got, want)
    valid = np.asarray(want.valid)
    for c in state:
        assert np.array_equal(np.asarray(got[c])[valid].view(np.uint32),
                              np.asarray(want[c])[valid].view(np.uint32)), c


@pytest.mark.parametrize("kind", BATCHES)
def test_distinct_equals_the_scatter_fold(kind):
    batch, keys = _batch(kind), _keys(kind)
    batch = ColumnBatch({c: batch.data[c] for c in ["k", "k2", "i", "v", "b"]}, batch.valid)
    _same_groups(distinct(batch, keys), _scatter_distinct(batch, keys))


def test_an_unknown_aggregate_is_refused():
    with pytest.raises(ValueError, match="unknown agg op 'median'"):
        group_reduce(_batch("one_run"), ["k"], [AggSpec("median", "v", "o")])


@pytest.mark.parametrize("n", [1, 2, 7, 8, 1000, 4096])
def test_the_scan_in_one_loop_body_equals_the_scan_unrolled(n):
    """The passes as ONE loop body with a traced distance give every
    slot the bits the unrolled passes gave it, under a merge that does
    not commute and over a two-dimensional channel."""
    rng = np.random.default_rng(n)
    start = jnp.asarray(rng.random(n) < 0.2)
    vals = {"f": jnp.asarray(rng.standard_normal(n).astype(np.float32)),
            "i": jnp.asarray(rng.integers(0, 1000, n).astype(np.int32)),
            "w": jnp.asarray(rng.integers(0, 99, (n, 2)).astype(np.int32))}

    def merge(a, b):
        return {"f": a["f"] * np.float32(0.5) + b["f"], "i": a["i"] * 3 + b["i"],
                "w": a["w"] * 5 + b["w"]}

    got = jax.jit(lambda s, v: segmented_scan(s, v, merge))(start, vals)
    want = _unrolled_scan(start, vals, merge)
    for name in vals:
        assert np.array_equal(np.asarray(got[name]).view(np.uint32),
                              np.asarray(want[name]).view(np.uint32)), name


# -- (c) the lowered programs --------------------------------------------------

def _fold(name):
    job = _skew_job()
    state = ["n", "ts", "last", "mean", "m2"]
    aggs = [AggSpec(a.op, a.col, n) for n, a in AGGS.items()]
    return {
        "group_reduce": (group_reduce, _scatter_group_reduce, (["k"], aggs)),
        "group_combine": (group_combine, _scatter_group_combine, (["k"], state, job.merge)),
        "distinct": (distinct, _scatter_distinct, (["k", "k2"],)),
    }[name]


@pytest.mark.parametrize("name", ["group_reduce", "group_combine", "distinct"])
def test_a_fold_lowers_to_no_scatter(name):
    """No ``stablehlo.scatter`` in the lowered fold, and no gather or
    sort in the scatters' place; the oracle scatters a column, so the
    check can see one (the form of
    ``test_exchange_lowers_to_slices_not_scatters``)."""
    fold, oracle, args = _fold(name)
    batch = _batch("two_key_columns")

    def lowered(fn):
        return jax.jit(lambda b: fn(b, *args)).lower(batch).as_text()

    before, text = lowered(oracle), lowered(fold)
    assert "stablehlo.scatter" in before
    assert "stablehlo.scatter" not in text
    # what the layout's carried sort needs (a wide row rides by its index), no more
    for op in ("stablehlo.gather", "stablehlo.sort"):
        assert text.count(op) == before.count(op), op
