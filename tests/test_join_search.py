"""The join's two searches without binary search: ``ops/sort.py::
sorted_ranks`` (both ranks of every left hash in the sorted right
hashes, by one merge) against ``np.searchsorted``, at every ratio of
the two lengths, alone and inside ``ops/join.py::_probe_ranges``; and
``ops/join.py::_slot_owners`` (a pair slot's left row by a scatter and a
running maximum) against the search it replaced, on every slot that
means anything."""

import numpy as np
import pytest

import jax.numpy as jnp

from dryad_tpu.columnar.batch import ColumnBatch
from dryad_tpu.ops import join as J
from dryad_tpu.ops import sort as S
from dryad_tpu.ops.hash import hash_columns

SENTINEL = 0xFFFFFFFF
# (n_queries, n_sorted): many queries into a short array, sides alike,
# a few queries into a long one
RATIOS = [(96, 64), (512, 512), (4, 4096)]


def duplicate_runs(rng, n):
    return np.sort(rng.integers(0, max(2, n // 4), n).astype(np.uint32) * 7 + 3)


def all_equal(rng, n):
    return np.full(n, 41, np.uint32)


def sentinel_tail(rng, n):
    """A join's right side: hashes under 2^31, then the invalid rows'."""
    a = np.sort(rng.integers(0, 1 << 31, n).astype(np.uint32) >> 8 << 8)
    a[n - n // 3:] = SENTINEL
    return a


def distinct(rng, n):
    return np.sort(rng.choice(1 << 20, n, replace=False).astype(np.uint32)) + 1


ARRAYS = {f.__name__: f for f in (duplicate_runs, all_equal, sentinel_tail, distinct)}


def queries_for(rng, a, n):
    """Present values, their neighbours below and above, both ends."""
    q = rng.choice(a, n).astype(np.int64) + rng.integers(-1, 2, n)
    q[:4] = (0, SENTINEL, int(a[0]), int(a[-1]))[:min(4, n)]
    return np.clip(q, 0, SENTINEL).astype(np.uint32)


def check_ranks(ranks, a, q):
    left, right = (np.asarray(r) for r in ranks(jnp.asarray(a), jnp.asarray(q)))
    assert left.dtype == right.dtype == np.int32
    assert np.array_equal(left, np.searchsorted(a, q, side="left"))
    assert np.array_equal(right, np.searchsorted(a, q, side="right"))


@pytest.mark.parametrize("n_q,n_s", RATIOS)
@pytest.mark.parametrize("array", ARRAYS)
def test_ranks_equal_numpys_at_every_ratio(array, n_q, n_s):
    rng = np.random.default_rng([27, n_s])
    a = ARRAYS[array](rng, n_s)
    check_ranks(S.sorted_ranks, a, queries_for(rng, a, n_q))


@pytest.mark.parametrize("n_q,n_s", [(1, 1), (1, 64), (64, 1), (2, 2), (1000, 37)])
@pytest.mark.parametrize("array", ARRAYS)
def test_the_merge_at_the_smallest_lengths(array, n_q, n_s):
    rng = np.random.default_rng([27, n_q, n_s])
    a = ARRAYS[array](rng, n_s)
    check_ranks(S.sorted_ranks, a, queries_for(rng, a, n_q))


@pytest.mark.parametrize("n_left,n_right", RATIOS + [(1, 128), (128, 1), (1000, 37)])
def test_probe_ranges_at_every_ratio(n_left, n_right):
    """The ranks as the join takes them: every left row's candidates are
    the run of its hash in the right side sorted by hash; invalid rows
    of either side find and offer none."""
    rng = np.random.default_rng([27, n_left, n_right])
    keys = max(2, n_right // 3)  # duplicate keys on both sides
    left = ColumnBatch({"k": jnp.asarray(rng.integers(0, keys + 2, n_left), jnp.int32)},
                       jnp.asarray(rng.random(n_left) < 0.9))
    right = ColumnBatch({"k": jnp.asarray(rng.integers(0, keys, n_right), jnp.int32),
                         "row": jnp.arange(n_right, dtype=jnp.int32)},
                        jnp.asarray(rng.random(n_right) < 0.8))
    rs, lhash, start, counts = J._probe_ranges(left, right, ["k"], ["k"])
    rhash = np.where(np.asarray(right.valid),
                     np.asarray(hash_columns([right.data["k"]]) >> 1), SENTINEL)
    order = np.argsort(rhash, kind="stable")
    assert np.array_equal(np.asarray(rs.data["row"]), order)
    assert np.array_equal(np.asarray(lhash), np.asarray(hash_columns([left.data["k"]]) >> 1))
    lo = np.searchsorted(rhash[order], np.asarray(lhash), side="left")
    hi = np.searchsorted(rhash[order], np.asarray(lhash), side="right")
    assert np.array_equal(np.asarray(start), lo)
    assert np.array_equal(np.asarray(counts), np.where(np.asarray(left.valid), hi - lo, 0))
    assert start.dtype == counts.dtype == jnp.int32


def owners_by_search(offsets, capacity):
    """What ``_expand_pairs`` computed before: the binary search."""
    slots = np.arange(capacity)
    li = np.searchsorted(offsets, slots, side="right") - 1
    return np.clip(li, 0, len(offsets) - 1)


COUNTS = {
    "dense": [2, 1, 3, 1],
    "zero_at_the_head": [0, 0, 2, 1, 1],
    "zeros_in_the_middle": [1, 0, 0, 0, 2, 0, 1],
    "zeros_at_the_tail": [2, 2, 0, 0, 0],
    "one_row": [5],
    "nothing_matches": [0, 0, 0, 0],
    "random": None,
}


@pytest.mark.parametrize("room", ["total_is_capacity", "total_under", "total_over"])
@pytest.mark.parametrize("case", COUNTS)
def test_slot_owners_equal_the_search_under_total(case, room):
    counts = COUNTS[case]
    if counts is None:
        rng = np.random.default_rng(27)
        counts = rng.integers(0, 4, 257) * (rng.random(257) < 0.6)
    counts = np.asarray(counts, np.int32)
    total = int(counts.sum())
    capacity = {"total_is_capacity": max(total, 1), "total_under": total + 3,
                "total_over": max(total - 2, 1)}[room]
    start = np.arange(len(counts), dtype=np.int32) * 10
    li, ri, pair_valid, overflow, offsets = (
        np.asarray(x) for x in J._expand_pairs(
            jnp.asarray(start), jnp.asarray(counts), capacity))
    want_offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    assert np.array_equal(offsets, want_offsets)
    assert bool(overflow) == (total > capacity)
    assert np.array_equal(pair_valid, np.arange(capacity) < total)
    assert li.dtype == ri.dtype == np.int32
    assert li.min() >= 0 and li.max() < len(counts)  # in range past total too
    live = min(total, capacity)
    want = owners_by_search(want_offsets, capacity)
    assert np.array_equal(li[:live], want[:live])
    assert np.array_equal(
        ri[:live], start[want[:live]] + np.arange(live) - want_offsets[want[:live]])
    # every live slot's row owns it
    assert np.all(counts[li[:live]] > 0)
