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
    li, base, pair_valid, overflow, offsets = (
        np.asarray(x) for x in J._expand_pairs(
            jnp.asarray(start), jnp.asarray(counts), capacity))
    assert base.dtype == np.int32 and np.array_equal(base, start - offsets)
    ri = base[li] + np.arange(capacity, dtype=np.int32)  # one gather a slot
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


# -- what a pair slot gathers (PR 42) ---------------------------------------
#
# No flavour reads a validity at a pair slot: the probe's contract makes
# both sides of every live slot valid rows.  Held here on batches with
# invalid rows on BOTH sides and duplicate keys on both, under hashes
# forced to collide and under a capacity that overflows; and every
# flavour against the NumPy interpreter (``exec/localdebug.py``), row
# for row.

N_LEFT, N_RIGHT = 512, 128


def four_bit_hash(monkeypatch):
    real = J.hash_columns
    monkeypatch.setattr(J, "hash_columns", lambda cols: real(cols) & jnp.uint32(0x1E))


# name -> (distinct keys, hashes forced to collide, out_capacity)
PAIR_CASES = {
    "duplicate_keys": (40, False, 8192),
    "few_keys": (5, False, 16384),
    "hashes_collide": (40, True, 16384),
    "left_keys_unmatched": (300, False, 4096),
    "overflow": (40, False, 600),
}


def pair_batches(case):
    keys = PAIR_CASES[case][0]
    rng = np.random.default_rng([42, keys, len(case)])
    left = ColumnBatch(
        {"k": jnp.asarray(rng.integers(0, keys, N_LEFT), jnp.int32),
         "lid": jnp.arange(N_LEFT, dtype=jnp.int32),
         "v": jnp.asarray(rng.standard_normal(N_LEFT), jnp.float32)},
        jnp.asarray(rng.random(N_LEFT) < 0.85))
    right = ColumnBatch(
        {"k": jnp.asarray(rng.integers(0, min(keys, 60), N_RIGHT), jnp.int32),
         "rid": jnp.arange(N_RIGHT, dtype=jnp.int32),
         "v": jnp.asarray(rng.standard_normal(N_RIGHT), jnp.float32)},
        jnp.asarray(rng.random(N_RIGHT) < 0.8))
    return left, right


def interpreted(left, right, kind, **params):
    """``LocalDebugInterpreter._n_join`` over the two batches' valid rows."""
    from types import SimpleNamespace

    from dryad_tpu.columnar.schema import ColumnType, Schema
    from dryad_tpu.exec.localdebug import LocalDebugInterpreter

    def table(b):
        keep = np.asarray(b.valid)
        return {n: np.asarray(c)[keep] for n, c in b.data.items()}

    def schema(b):
        return Schema([(n, ColumnType.FLOAT32 if c.dtype == jnp.float32 else ColumnType.INT32)
                       for n, c in b.data.items()])

    tables = [table(left), table(right)]
    interp = LocalDebugInterpreter(ctx=None)
    interp._in = lambda node, i=0: tables[i]
    node = SimpleNamespace(
        inputs=[SimpleNamespace(schema=schema(left)), SimpleNamespace(schema=schema(right))],
        params=dict(left_keys=["k"], right_keys=["k"], join_kind=kind, suffix="_r", **params))
    return interp._n_join(node)


def rows_of(batch, order=None):
    keep = np.asarray(batch.valid)
    out = {n: np.asarray(c)[keep] for n, c in batch.data.items()}
    return sorted_rows(out, order) if order else out


def sorted_rows(table, order):
    perm = np.lexsort([table[c] for c in reversed(order)])
    return {n: c[perm] for n, c in table.items()}


def same_rows(got, want):
    assert sorted(got) == sorted(want)
    for name in want:
        a, b = np.asarray(got[name]), np.asarray(want[name])
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.array_equal(a.view(np.uint32), b.view(np.uint32)), name


def check_live_slots_are_valid_rows(left, right, cap, overflows):
    rs, _, start, counts = J._probe_ranges(left, right, ["k"], ["k"])
    li, base, pair_valid, overflow, _ = J._expand_pairs(start, counts, cap)
    assert bool(overflow) == overflows
    live = np.asarray(pair_valid)
    assert live.any()
    li = np.asarray(li)
    ri = np.asarray(base)[li] + np.arange(cap, dtype=np.int32)
    assert np.asarray(left.valid)[li[live]].all()
    assert ri[live].min() >= 0 and ri[live].max() < N_RIGHT
    assert np.asarray(rs.valid)[ri[live]].all()
    # and so the exact match reads the same with the two terms and without
    lk, rk = np.asarray(left.data["k"])[li], np.asarray(rs.data["k"])[np.clip(ri, 0, N_RIGHT - 1)]
    with_terms = (live & np.asarray(left.valid)[li]
                  & np.asarray(rs.valid)[np.clip(ri, 0, N_RIGHT - 1)] & (lk == rk))
    lcols, rcols = J._materialize_pairs(
        {"k": left.data["k"]}, {"k": rs.data["k"]}, jnp.asarray(li), base)
    exact = J._exact_pair_match(lcols, rcols, ["k"], ["k"], pair_valid)
    assert np.array_equal(np.asarray(exact), with_terms)
    return int(np.count_nonzero(live & (lk != rk)))  # candidates by collision


def check_inner(left, right, cap, overflows):
    out, overflow = J.hash_join(left, right, ["k"], ["k"], cap)
    assert bool(overflow) == overflows
    want = interpreted(left, right, "inner")
    if not overflows:
        return same_rows(rows_of(out), want)
    # what came out are true pairs, the first of them
    got = rows_of(out)
    n = len(got["lid"])
    assert 0 < n < len(want["lid"])
    same_rows(got, {c: v[:n] for c, v in want.items()})


def check_outer(left, right, cap, overflows):
    out, overflow = J.hash_join_outer(
        left, right, ["k"], ["k"], cap, {"rid": jnp.int32(-7), "v": jnp.float32(0.5)})
    assert bool(overflow) == overflows
    if not overflows:
        want = interpreted(left, right, "left", right_defaults={"rid": -7, "v": 0.5})
        same_rows(rows_of(out, ["lid", "rid"]), sorted_rows(want, ["lid", "rid"]))


def check_counts(left, right, cap, overflows):
    counts, overflow = J.group_join_counts(left, right, ["k"], ["k"], cap)
    assert bool(overflow) == overflows
    if not overflows:
        want = interpreted(left, right, "count", out="n")
        assert np.array_equal(np.asarray(counts)[np.asarray(left.valid)], want["n"])
        assert not np.asarray(counts)[~np.asarray(left.valid)].any()


def check_exists(left, right, cap, overflows):
    mask, overflow = J.exists_mask(left, right, ["k"], ["k"], cap)
    assert bool(overflow) == overflows
    if not overflows:
        want = interpreted(left, right, "semi")
        assert np.array_equal(np.asarray(left.data["lid"])[np.asarray(mask)], want["lid"])


def check_ranked(left, right, cap, overflows, rank_limit=None):
    # forced collisions may hide matches past the clamped window: the
    # ladder's last rung, as the executor would reach it
    out, overflow = J.hash_join_ranked(
        left, right, ["k"], ["k"], cap, rank_name="rank",
        rank_limit=rank_limit, final_attempt=rank_limit is not None and cap > 8192)
    if rank_limit is None:
        assert bool(overflow) == overflows
    if not bool(overflow):
        params = {} if rank_limit is None else {"rank_limit": rank_limit}
        same_rows(rows_of(out), interpreted(left, right, "ranked", rank_out="rank", **params))


def check_ranked_limit(left, right, cap, overflows):
    check_ranked(left, right, cap, overflows, rank_limit=2)
    if overflows:  # the clamp keeps the candidates under the capacity
        out, overflow = J.hash_join_ranked(left, right, ["k"], ["k"], cap, rank_name="rank",
                                           rank_limit=1)
        assert not bool(overflow)
        same_rows(rows_of(out), interpreted(left, right, "ranked", rank_out="rank", rank_limit=1))


FLAVOURS = {f.__name__[6:]: f for f in (
    check_live_slots_are_valid_rows, check_inner, check_outer, check_counts,
    check_exists, check_ranked, check_ranked_limit)}


@pytest.mark.parametrize("flavour", FLAVOURS)
@pytest.mark.parametrize("case", PAIR_CASES)
def test_no_pair_slot_needs_a_validity(monkeypatch, case, flavour):
    _, collide, cap = PAIR_CASES[case]
    if collide:
        four_bit_hash(monkeypatch)
    left, right = pair_batches(case)
    collisions = FLAVOURS[flavour](left, right, cap, overflows=case == "overflow")
    if flavour == "live_slots_are_valid_rows":
        assert (collisions > 0) == collide


# A pair slot is filled through one call a shared index (``li``, then
# ``ri``), pinned where there is no chip: the StableHLO ``gather``s of a
# lowered join whose result spans the pair slots, at the schema of the
# ``join-topk-1c`` cell (int32 key + f32 payload x int32 key + f32
# weight).  The parent lowered nine for the inner join (offsets, start,
# the key twice, payload, two validities by ``li`` / ``ri``, dkey,
# weight); a gather a column is five, one stacked gather an index two.

SLOTS = 1288  # a length nothing else in the program has


def cell_batches(extra_left=None):
    rng = np.random.default_rng(42)
    left = {"key": jnp.asarray(rng.integers(0, 128, 1024), jnp.int32),
            "payload": jnp.asarray(rng.standard_normal(1024), jnp.float32)}
    left.update(extra_left or {})
    right = {"dkey": jnp.asarray(rng.permutation(128), jnp.int32),
             "weight": jnp.asarray(rng.standard_normal(128), jnp.float32)}
    return (ColumnBatch(left, jnp.ones((1024,), jnp.bool_)),
            ColumnBatch(right, jnp.ones((128,), jnp.bool_)))


BLOCK = 500  # of slots, where a test cuts the stacked gathers in blocks


def slot_gathers(lowered_text):
    """``(element type, share of the slots)`` of every gather over the
    pair slots: all of them, or a block (``ops/sort.py::take_rows``
    makes a stacked gather over ``STACK_BLOCK_SLOTS`` slots at a
    time)."""
    import re

    found = []
    for indices, result in re.findall(
            r'"stablehlo\.gather".*: \(tensor<[^>]*>, tensor<(\d+)x[^>]*>\) -> tensor<([^>]*)>',
            lowered_text):
        if int(indices) in (SLOTS, BLOCK, SLOTS % BLOCK):
            found.append((result.split("x")[-1], int(indices) / SLOTS))
    return found


def gathers_a_slot(gathers):
    return round(sum(share for _, share in gathers), 6)


JOINS = {
    "inner": lambda l, r: J.hash_join(l, r, ["key"], ["dkey"], SLOTS),
    "outer": lambda l, r: J.hash_join_outer(l, r, ["key"], ["dkey"], SLOTS, {}),
    "ranked": lambda l, r: J.hash_join_ranked(l, r, ["key"], ["dkey"], SLOTS, rank_limit=3),
    "counts": lambda l, r: J.group_join_counts(l, r, ["key"], ["dkey"], SLOTS),
}
# flavour -> (gathers at a gather a column, at one stacked gather an
# index, words stacked by li and by ri); ranked: + the row's first slot
# by li, + the running count at it
WANT_GATHERS = {"inner": (5, 2, 3, 2), "outer": (5, 2, 3, 2),
                "ranked": (7, 3, 4, 2), "counts": (3, 2, 2, 0)}


@pytest.mark.parametrize(
    "form", ["a_gather_a_column", "stacked", "stacked_in_blocks", "as_the_rule_says"])
@pytest.mark.parametrize("flavour", JOINS)
def test_a_pair_slot_is_gathered_once_an_index(monkeypatch, flavour, form):
    import jax

    if form != "as_the_rule_says":
        monkeypatch.setattr(S, "SHARED_GATHER_WORDS", 1 << 30 if form == "a_gather_a_column" else 2)
    if form == "stacked_in_blocks":  # three blocks: 500, 500, 288 slots
        monkeypatch.setattr(S, "STACK_BLOCK_SLOTS", BLOCK)
    left, right = cell_batches()
    with J.slot_gather_log() as seen:
        # a function a trace: jit would hand a second form the first's
        lowered = jax.jit(lambda l, r: JOINS[flavour](l, r)).lower(left, right).as_text()
    gathers = slot_gathers(lowered)
    single, stacked, by_li, by_ri = WANT_GATHERS[flavour]
    assert "i1" not in [dtype for dtype, _ in gathers]  # no validity is read at a slot
    assert gathers_a_slot(gathers) == seen["slot_gathers"] <= single
    words = seen["stacked_words"]
    if form == "a_gather_a_column":
        assert len(gathers) == single and words == {"li": 0, "ri": 0}
    elif form.startswith("stacked"):
        assert gathers_a_slot(gathers) == stacked and words == {"li": by_li, "ri": by_ri}
        blocks = 3 if form == "stacked_in_blocks" else 1
        # by itself: the ranked join's running count at a row's first
        # slot; the one right key of the counts
        by_itself = 1 if flavour in ("ranked", "counts") else 0
        assert len(gathers) == (stacked - by_itself) * blocks + by_itself
    else:  # the rule takes a form an index, from the words alone
        assert words["li"] in (0, by_li) and words["ri"] in (0, by_ri)
        saved = (by_li - 1 if words["li"] else 0) + (by_ri - 1 if words["ri"] else 0)
        assert gathers_a_slot(gathers) == single - saved


def test_columns_that_are_no_word_a_row_are_gathered_by_themselves(monkeypatch):
    """A ``pred`` and a column with trailing dimensions (a BYTES
    column's words) stay out of the stack; a split 64-bit column is two
    words of it."""
    import jax

    monkeypatch.setattr(S, "SHARED_GATHER_WORDS", 2)
    rng = np.random.default_rng(42)
    extra = {"flag": jnp.asarray(rng.random(1024) < 0.5),
             "bytes": jnp.asarray(rng.integers(0, 1 << 32, (1024, 3)), jnp.uint32),
             "big#h0": jnp.asarray(rng.integers(0, 1 << 32, 1024), jnp.uint32),
             "big#h1": jnp.asarray(rng.integers(0, 1 << 32, 1024), jnp.uint32)}
    left, right = cell_batches(extra)
    with J.slot_gather_log() as seen:
        lowered = jax.jit(lambda l, r: JOINS["inner"](l, r)).lower(left, right).as_text()
    gathers = slot_gathers(lowered)
    # by li: the stack of base, key, payload, big#h0, big#h1; flag; bytes; by ri: one stack
    assert sorted(gathers) == [("i1", 1.0), ("ui32", 1.0), ("ui32", 1.0), ("ui32", 1.0)]
    assert seen == {"slot_gathers": 4, "stacked_words": {"li": 5, "ri": 2}}
    out, _ = JOINS["inner"](left, right)
    keep = np.asarray(out.valid)
    rows = np.asarray(left.data["key"])  # every fact row finds its one dimension row
    assert keep.sum() == 1024
    for name in extra:
        assert np.array_equal(np.asarray(out.data[name])[keep], np.asarray(left.data[name]))
    assert np.array_equal(np.asarray(out.data["key"])[keep], rows)


@pytest.mark.parametrize("form", ["a_gather_a_column", "stacked"])
def test_the_joined_words_keep_their_bits(monkeypatch, form):
    """NaNs with distinct payload bits, both zeros, denormals and the
    infinities come out of the join bit for bit, on both sides."""
    monkeypatch.setattr(S, "SHARED_GATHER_WORDS", 2 if form == "stacked" else 1 << 30)
    odd = np.array([0x7FC00000, 0x7FC00001, 0xFFC12345, 0x7F800001, 0xFF800002,  # NaNs
                    0x80000000, 0x00000000, 0x00000001, 0x807FFFFF, 0x00400000,  # zeros, denormals
                    0x7F800000, 0xFF800000, 0x3F800000], np.uint32)
    rng = np.random.default_rng(42)
    payload = rng.choice(odd, 256)
    weight = rng.choice(odd, 32)
    left = ColumnBatch({"key": jnp.asarray(rng.integers(0, 32, 256), jnp.int32),
                        "payload": jnp.asarray(payload.view(np.float32))},
                       jnp.ones((256,), jnp.bool_))
    right = ColumnBatch({"dkey": jnp.asarray(rng.permutation(32), jnp.int32),
                         "weight": jnp.asarray(weight.view(np.float32))},
                        jnp.ones((32,), jnp.bool_))
    out, overflow = J.hash_join(left, right, ["key"], ["dkey"], 320)
    keep = np.asarray(out.valid)
    assert not bool(overflow) and keep.sum() == 256
    key = np.asarray(left.data["key"])
    by_key = np.empty(32, np.uint32)
    by_key[np.asarray(right.data["dkey"])] = weight
    assert np.array_equal(np.asarray(out.data["key"])[keep], key)
    assert np.array_equal(np.asarray(out.data["payload"])[keep].view(np.uint32), payload)
    assert np.array_equal(np.asarray(out.data["weight"])[keep].view(np.uint32), by_key[key])
