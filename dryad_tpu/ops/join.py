"""On-device hash join (equi-join) with static output shapes.

The reference executes Join/GroupJoin inside vertices after co-hash-
partitioning both inputs (``DryadLinqQueryNode.cs`` DLinqJoinNode;
vertex-side implementations in ``LinqToDryad/DryadLinqVertex.cs``).
The TPU-native version: both sides arrive co-partitioned by key hash;
locally we sort the right side by a 32-bit key hash, rank every left
hash in it to get candidate ranges, expand candidate pairs into a
fixed-capacity output via prefix sums, and mask to exact key equality
(hash collisions only ever add masked-off candidates).  Output overflow
is reported for executor retry, like the shuffle's padded buckets.

Neither search is a binary search by ``gather``: the ranks come from
one merge of the left hashes into the sorted right side
(``ops/sort.py::sorted_ranks``), and a pair slot's left row from a
scatter of the rows' first slots and a running maximum
(:func:`_slot_owners`).

What a pair slot gathers.  A slot has two indices, its left row ``li``
and its right row ``ri``, and on the TPU a gather is priced by the
index, not by the bytes it fetches.  So everything a slot needs from
its left row goes through the gathers of ONE call by ``li`` -- the
base of ``ri`` (``start - offsets``, one word a left row: ``ri`` is
``base[li] + slot``) beside the left columns -- and everything from
its right row through one call by ``ri`` (:func:`_materialize_pairs`;
``ops/sort.py::take_rows`` sends the 4-byte columns of a call through
one gather over their stacked words where that is the cheaper form).
The key columns are gathered there once and the exact match compares
what was gathered.  NO validity is gathered: the probe's contract
(:func:`_probe_ranges`) already makes both sides of every live slot
valid rows.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from dryad_tpu.columnar.batch import ColumnBatch
from dryad_tpu.ops.hash import hash_columns
from dryad_tpu.ops.sort import (
    sort_batch_by_operands,
    sort_carry,
    sorted_ranks,
    stacked_words,
    take_rows,
)


# No column's: a physical name has its logical base before any "#".
_FIRST_SLOT = "#first_slot"


def _suffixed(phys_name: str, suffix: str) -> str:
    """Apply a clash suffix to the *logical* base of a physical name:
    'v#h0' -> 'v{suffix}#h0' so split columns stay consistent with the
    suffixed logical field in the output schema."""
    if "#" in phys_name:
        base, word = phys_name.split("#", 1)
        return f"{base}{suffix}#{word}"
    return f"{phys_name}{suffix}"


@jax.named_scope("dryad.join.probe")
def _probe_ranges(
    left: ColumnBatch,
    right: ColumnBatch,
    left_keys: Sequence[str],
    right_keys: Sequence[str],
) -> Tuple[ColumnBatch, jax.Array, jax.Array, jax.Array]:
    """Sort right by key hash; per valid left row the candidate range.

    Returns (right_sorted, lhash, start, counts): left row i's
    candidates are ``right_sorted[start[i] : start[i] + counts[i]]``,
    the two ranks of its hash in the sorted right hashes
    (``sorted_ranks``).  Invalid right rows sort to the end with a
    sentinel hash that can never match a valid probe (probe hashes have
    their top bit cleared; the sentinel is 2^32-1).

    The contract every flavour leans on, so that no pair slot reads a
    validity: (1) ``counts[i] == 0`` for an invalid left row, so it
    owns no slot (a caller may clamp ``counts`` down, never raise it);
    (2) the sentinel hash sits on invalid right rows only and equals no
    probe hash, and a candidate's hash EQUALS its left row's, so every
    row of ``[start[i], start[i] + counts[i])`` is a valid right row.
    """
    rhash = hash_columns([right.data[k] for k in right_keys]) >> 1
    rhash = jnp.where(right.valid, rhash, jnp.uint32(0xFFFFFFFF))
    # Stable sort by hash carrying the batch + the hash itself through
    # lax.sort (sentinel rows last — valid-first ordering is identical
    # here because only invalid rows hold the sentinel hash).
    names = right.columns
    vs, (rhash_sorted,), carried = sort_carry(
        [rhash], right.valid, [right.data[n] for n in names]
    )
    rs = ColumnBatch(dict(zip(names, carried)), vs)

    lhash = hash_columns([left.data[k] for k in left_keys]) >> 1
    start, end = sorted_ranks(rhash_sorted, lhash)
    counts = jnp.where(left.valid, end - start, 0)
    return rs, lhash, start, counts


def _slot_owners(
    offsets: jax.Array, counts: jax.Array, total: jax.Array, out_capacity: int
) -> jax.Array:
    """The left row each pair slot belongs to: the inverse of the prefix
    sum ``offsets``.  Every row that owns slots writes its index at its
    first one (the targets are distinct; a row with none, or with its
    first slot past the capacity, drops) and a running maximum fills
    each row's range.  On the slots under ``total = sum(counts)`` this
    is ``searchsorted(offsets, slot, "right") - 1``.

    A slot past them is dead: nothing reads what it gathers, but the
    gathers run over every slot, and a gather costs by its addresses as
    well as by its shape (``ops/sort.py``'s table by kind of index).
    So a dead slot reads the row of its OWN number, wrapped into the
    table (``slot % rows``): ascending addresses, the same on every
    chip and for every table.  The running maximum alone would leave
    the whole tail on ONE row, the last that owns a slot, which moves
    with the data, and one row read by every slot costs 17 - 18 or 28
    ns a slot BY THE ROW on the v5e (against 21.2 ascending): with 11 -
    20 M of a chip's 2^25 slots dead the ``li`` gather of the
    ``join-hash-4c`` cell read 0.634 - 0.857 s by chip and table that
    way and reads 0.712 s on every chip and table this way, each of
    its 32 blocks 22.22 - 22.28 ms (``PERF.md`` section 6, PR 45 and PR
    46).  What a dead slot's ``ri = base[li] + slot`` then reads
    follows from the row it landed on and stays the data's."""
    rows = counts.shape[0]
    first = jnp.where(counts > 0, offsets, out_capacity)
    heads = jnp.zeros((out_capacity,), jnp.int32).at[first].set(
        jnp.arange(rows, dtype=jnp.int32), mode="drop"
    )
    slots = jnp.arange(out_capacity, dtype=jnp.int32)
    return jnp.where(slots < total, jax.lax.cummax(heads), slots % rows)


class _SlotGathers(threading.local):
    seen = None
    pairs = None


_slot_gathers = _SlotGathers()


@contextlib.contextmanager
def slot_gather_log() -> Iterator[Dict[str, object]]:
    """Trace-time record of the gathers over the pair slots that the
    joins traced under it emit: ``slot_gathers``, how many, and
    ``stacked_words``, for each index (``li`` / ``ri``) the 4-byte
    words that went through ONE stacked gather (0: a gather a column).
    What the ``join_plan`` event says of the mechanism."""
    before = _slot_gathers.seen
    seen = _slot_gathers.seen = dict(
        slot_gathers=0, stacked_words=dict(li=0, ri=0)
    )
    try:
        yield seen
    finally:
        _slot_gathers.seen = before


def take_pairs() -> Optional[jax.Array]:
    """The candidate pairs in the pair buffer of the join traced last
    under :func:`slot_gather_log`, a traced scalar
    (:func:`_expand_pairs`), handed over once."""
    pairs, _slot_gathers.pairs = _slot_gathers.pairs, None
    return pairs


def _take_slots(
    columns: Sequence[jax.Array], index: jax.Array, which: Optional[str] = None
) -> List[jax.Array]:
    """``columns`` at the pair slots' ``index`` (``take_rows``: one
    stacked gather or a gather a column), counted for
    :func:`slot_gather_log`."""
    seen = _slot_gathers.seen
    if seen is not None:
        words = stacked_words(columns)
        seen["slot_gathers"] += len(columns) - words + (words > 0)
        if which is not None:
            seen["stacked_words"][which] += words
    return take_rows(columns, index)


@jax.named_scope("dryad.join.expand_pairs")
def _expand_pairs(
    start: jax.Array, counts: jax.Array, out_capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Enumerate candidate (left_row, right_row) pairs into fixed slots.

    Returns (left_idx, base, pair_valid, overflow, offsets) where
    ``offsets[i]`` is the first slot of left row i's candidate range
    (slots for one left row are contiguous, rows in order),
    ``left_idx`` is :func:`_slot_owners`' answer and ``base`` is
    ``start - offsets``, a word a LEFT row: slot s's right row is
    ``base[left_idx[s]] + s`` (``start[li] + (s - offsets[li])`` with
    the subtraction done before the gather, so one gather a slot finds
    it; int32 wraps the same either way).  Only the slots under
    ``pair_valid`` mean anything; what the others read is
    :func:`_slot_owners`' to say.
    """
    offsets = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]]
    )
    total = jnp.sum(counts)
    overflow = total > out_capacity

    li = _slot_owners(offsets, counts, total, out_capacity)
    base = (start - offsets).astype(jnp.int32)
    pair_valid = jnp.arange(out_capacity, dtype=jnp.int32) < total
    if _slot_gathers.seen is not None:
        _slot_gathers.pairs = jnp.minimum(total, out_capacity)
    return li, base, pair_valid, overflow, offsets


@jax.named_scope("dryad.join.materialize")
def _materialize_pairs(
    lcols: Dict[str, jax.Array],
    rcols: Dict[str, jax.Array],
    li: jax.Array,
    base: jax.Array,
) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """Everything the pair slots read, in one call an index: by ``li``
    the right row's ``base`` and the left columns ``lcols`` (any word a
    left row has for its slots can ride here under a name of its own:
    the ranked join's first slot does); then by ``ri = base[li] +
    slot`` the right columns ``rcols``.  Returns both at the slots."""
    by_li = _take_slots([base, *lcols.values()], li, "li")
    ri = by_li[0] + jnp.arange(li.shape[0], dtype=jnp.int32)
    by_ri = _take_slots(list(rcols.values()), ri, "ri")
    return dict(zip(lcols, by_li[1:])), dict(zip(rcols, by_ri))


def _joined_columns(
    lcols: Dict[str, jax.Array],
    rcols: Dict[str, jax.Array],
    right_keys: Sequence[str],
    suffix: str,
) -> Tuple[Dict[str, jax.Array], Dict[str, str]]:
    """The joined rows' columns: every left column, every right column
    but the keys (they equal the left's).

    Returns (data, right_out): ``right_out`` maps a right column's name
    to its name in ``data`` (a name clashing with a left column's takes
    ``suffix``)."""
    data = dict(lcols)
    rk = set(right_keys)
    right_out: Dict[str, str] = {}
    for name, col in rcols.items():
        if name in rk:
            continue
        right_out[name] = _suffixed(name, suffix) if name in data else name
        data[right_out[name]] = col
    return data, right_out


def hash_join(
    left: ColumnBatch,
    right: ColumnBatch,
    left_keys: Sequence[str],
    right_keys: Sequence[str],
    out_capacity: int,
    suffix: str = "_r",
) -> Tuple[ColumnBatch, jax.Array]:
    """Local inner equi-join; inputs must already be co-partitioned.

    Output columns: all left columns plus right columns (right key
    columns dropped — they equal the left's; other right names clashing
    with left names get ``suffix``).  Returns (batch, overflow).
    """
    rs, lhash, start, counts = _probe_ranges(left, right, left_keys, right_keys)
    li, base, pair_valid, overflow, _ = _expand_pairs(start, counts, out_capacity)
    lcols, rcols = _materialize_pairs(left.data, rs.data, li, base)
    valid = _exact_pair_match(lcols, rcols, left_keys, right_keys, pair_valid)
    data, _ = _joined_columns(lcols, rcols, right_keys, suffix)
    return ColumnBatch(data, valid), overflow


@jax.named_scope("dryad.join.exact")
def _exact_pair_match(
    lcols: Dict[str, jax.Array],
    rcols: Dict[str, jax.Array],
    left_keys: Sequence[str],
    right_keys: Sequence[str],
    pair_valid: jax.Array,
) -> jax.Array:
    """Candidate pairs that match on ALL key columns (kills collisions),
    from the key columns as :func:`_materialize_pairs` gathered them.

    Neither side's validity is read: under ``pair_valid`` both are true
    by :func:`_probe_ranges`' contract.  A slot's left row owns it, so
    its ``counts`` is positive and the row valid; its right row lies in
    that row's candidate range, whose hashes equal a probe hash, which
    the sentinel of the invalid right rows never does."""
    exact = pair_valid
    for lk, rkey in zip(left_keys, right_keys):
        exact = exact & (lcols[lk] == rcols[rkey])
    return exact


def _exact_per_left(li: jax.Array, exact: jax.Array, n: int) -> jax.Array:
    """Per-left-row count of exact pairs (scatter-add over pair slots)."""
    return (
        jnp.zeros((n,), jnp.int32)
        .at[li]
        .add(exact.astype(jnp.int32), mode="drop")
    )


def hash_join_outer(
    left: ColumnBatch,
    right: ColumnBatch,
    left_keys: Sequence[str],
    right_keys: Sequence[str],
    out_capacity: int,
    right_defaults: Dict[str, jnp.ndarray],
    suffix: str = "_r",
) -> Tuple[ColumnBatch, jax.Array]:
    """Left-outer equi-join: inner pairs plus unmatched left rows with
    default-valued right columns (the GroupJoin left-outer shape,
    reference ``DryadLinqQueryGen.cs`` GroupJoin + DefaultIfEmpty
    pattern).  Output capacity is ``out_capacity + left.capacity`` —
    the unmatched tail is statically reserved so it can never overflow.
    """
    rs, lhash, start, counts = _probe_ranges(left, right, left_keys, right_keys)
    li, base, pair_valid, overflow, _ = _expand_pairs(start, counts, out_capacity)
    lcols, rcols = _materialize_pairs(left.data, rs.data, li, base)
    exact = _exact_pair_match(lcols, rcols, left_keys, right_keys, pair_valid)

    # Per-left-row exact-match count -> unmatched mask for the tail.
    matched = _exact_per_left(li, exact, left.capacity)
    unmatched = left.valid & (matched == 0)

    data, right_out = _joined_columns(lcols, rcols, right_keys, suffix)
    for name, col in left.data.items():
        data[name] = jnp.concatenate([data[name], col])
    for name, out_name in right_out.items():
        col = rs.data[name]
        dflt = right_defaults.get(name, jnp.zeros((), col.dtype))
        tail = jnp.broadcast_to(
            jnp.asarray(dflt, col.dtype), (left.capacity,) + col.shape[1:]
        )
        data[out_name] = jnp.concatenate([data[out_name], tail])
    valid = jnp.concatenate([exact, unmatched])
    return ColumnBatch(data, valid), overflow


def group_join_counts(
    left: ColumnBatch,
    right: ColumnBatch,
    left_keys: Sequence[str],
    right_keys: Sequence[str],
    out_capacity: int,
) -> Tuple[jax.Array, jax.Array]:
    """Per-left-row count of exactly-matching right rows (GroupJoin's
    shape; aggregations over the group compose on the joined output)."""
    rs, _lhash, start, counts = _probe_ranges(left, right, left_keys, right_keys)
    li, base, pair_valid, overflow, _ = _expand_pairs(start, counts, out_capacity)
    lcols, rcols = _materialize_pairs(  # the keys alone
        {k: left.data[k] for k in left_keys},
        {k: rs.data[k] for k in right_keys}, li, base,
    )
    exact = _exact_pair_match(lcols, rcols, left_keys, right_keys, pair_valid)
    cnt = _exact_per_left(li, exact, left.capacity)
    return cnt, overflow


def hash_join_ranked(
    left: ColumnBatch,
    right: ColumnBatch,
    left_keys: Sequence[str],
    right_keys: Sequence[str],
    out_capacity: int,
    suffix: str = "_r",
    rank_name: str = "gj_rank",
    order_operands: Sequence[jax.Array] = (),
    rank_limit: Optional[int] = None,
    boost: int = 1,
    final_attempt: bool = False,
) -> Tuple[ColumnBatch, jax.Array]:
    """Inner equi-join that also emits each pair's group-local rank —
    the position of the matching right row within its left row's match
    group, as an INT32 column.  This is full GroupJoin's enumerable
    group (reference ``DryadLinqQueryable.cs`` GroupJoin overloads with
    a result selector): downstream segmented selection over
    (left-row-id, rank) expresses top-k-per-key and concat-style
    selectors.

    With ``order_operands`` (uint32 sort operands over the UNSORTED
    right batch, e.g. from ``plan.keys.ordering_operands``), ranks
    follow that value order within each group — deterministic across
    partitionings.  Without, ranks follow the right side's engine order.

    ``rank_limit=k`` bounds the enumerable group to its first k
    matches (pairs with rank >= k are dropped BEFORE expansion, so a
    hot key's pair count stops growing quadratically): each left row
    expands only its first ``k * boost`` hash-candidates.  Candidates
    in that window that fail the exact-key check are collisions; when
    a clamped row yields fewer than k exact matches, the overflow flag
    requests a retry (the caller re-runs at doubled ``boost``, widening
    the window until the collisions are covered).  Rows whose full
    candidate range fits inside the window never retry.

    ``final_attempt=True`` (the caller's LAST boost level) drops the
    window clamp entirely: a pathological row — its key hash-colliding
    into a huge run it can never cover geometrically — degrades to the
    unclamped expansion (exactly the no-rank_limit cost) instead of
    failing a query that would succeed without ``rank_limit``.  The
    rank < k output contract is unconditional either way.
    """
    if len(order_operands):
        right = sort_batch_by_operands(right, order_operands)
    # _probe_ranges' hash sort is stable (sort_carry, is_stable=True),
    # so the operand order survives within each equal-hash run.
    rs, lhash, start, counts = _probe_ranges(left, right, left_keys, right_keys)
    full_counts = counts
    if rank_limit is not None and not final_attempt:
        counts = jnp.minimum(counts, jnp.int32(rank_limit * boost))
    li, base, pair_valid, overflow, offsets = _expand_pairs(
        start, counts, out_capacity
    )
    # a slot's row's first slot rides the gather by ``li`` with the columns
    lcols, rcols = _materialize_pairs(
        {**left.data, _FIRST_SLOT: offsets.astype(jnp.int32)}, rs.data, li, base
    )
    seg = lcols.pop(_FIRST_SLOT)
    exact = _exact_pair_match(lcols, rcols, left_keys, right_keys, pair_valid)

    # Group-local rank among EXACT matches: a left row's candidate
    # slots are contiguous ([offsets[i], offsets[i]+counts[i])), so the
    # rank is the count of exact slots in [offsets[li], slot] minus 1.
    # Hash-collision candidates inside the range fail `exact` and are
    # skipped by the subtraction.
    cs = jnp.cumsum(exact.astype(jnp.int32))
    (at_seg,) = _take_slots([cs], jnp.clip(seg - 1, 0, out_capacity - 1))
    before = jnp.where(seg > 0, at_seg, 0)
    rank = jnp.where(exact, cs - 1 - before, 0).astype(jnp.int32)

    if rank_limit is not None:
        if not final_attempt:
            # A clamped row (candidates beyond the window exist) that
            # found fewer than rank_limit exact matches may be missing
            # matches hiding behind collisions — retry with a wider
            # window.
            exact_cnt = _exact_per_left(li, exact, full_counts.shape[0])
            short = (
                left.valid
                & (full_counts > counts)
                & (exact_cnt < jnp.int32(rank_limit))
            )
            overflow = overflow | jnp.any(short)
        # The contract is EXACTLY the rank < k subset, independent of
        # the boost-widened window.
        exact = exact & (rank < jnp.int32(rank_limit))

    data, _ = _joined_columns(lcols, rcols, right_keys, suffix)
    data[rank_name] = rank
    return ColumnBatch(data, exact), overflow


def exists_mask(
    left: ColumnBatch,
    right: ColumnBatch,
    left_keys: Sequence[str],
    right_keys: Sequence[str],
    out_capacity: int,
) -> Tuple[jax.Array, jax.Array]:
    """Per-left-row 'has an exactly-matching right row' (semi/anti join)."""
    counts, overflow = group_join_counts(
        left, right, left_keys, right_keys, out_capacity
    )
    return counts > 0, overflow
