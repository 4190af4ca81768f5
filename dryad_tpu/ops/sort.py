"""Global sort / range partition: sampling, splitters, bucketing.

The reference's TeraSort pattern: a sampler stage reads ~0.1% of rows
(``DryadLinqSampler.cs:38-42``), the GM computes range splitters and
dynamically sizes the consumer stage (``DrDynamicRangeDistributor.cpp:
23-110``), and a range-exchange plus per-partition merge-sort yields a
globally sorted dataset.  TPU-native: sampling, splitter election and
bucketing all happen on device inside the same compiled program —
``sample_splitters`` uses an ``all_gather`` over ICI instead of a
sampler stage + host round-trip.  Equal keys always land in the same
partition (searchsorted semantics), so secondary sort keys stay local.
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from dryad_tpu.columnar.batch import ColumnBatch
from dryad_tpu.ops.sortkeys import to_sortable_u32


def sort_order_by_operands(
    operands: Sequence[jax.Array], valid: jax.Array
) -> jax.Array:
    """Stable permutation: valid rows first, lexicographic by uint32 operands.

    For an order that must be applied to something that cannot ride the
    sort.  Sorted DATA comes from :func:`sort_carry` /
    :func:`sort_batch_by_operands`, which every permutation of a whole
    batch goes through.
    """
    n = valid.shape[0]
    ops: List[jax.Array] = [jnp.logical_not(valid).astype(jnp.uint32)]
    ops.extend(o.astype(jnp.uint32) for o in operands)
    ops.append(jnp.arange(n, dtype=jnp.int32))
    res = jax.lax.sort(tuple(ops), num_keys=len(ops) - 1, is_stable=True)
    return res[-1]


def _carry_profitable() -> bool:
    """Platform split for the payload-movement strategy.  On TPU,
    carrying payload through ``lax.sort`` is nearly free while a
    post-sort XLA ``gather`` of each column is not (``PERF.md``
    section 6, PR 25: ``sort-1c`` ``requery_s`` 6.14 -> 1.98 s when the
    exchange layout's gathers became carried sorts); on CPU it
    inverts — gathers are cheap and extra variadic sort operands are
    not.  Both forms produce the identical stable permutation; only
    data movement differs."""
    from dryad_tpu.ops.pallas_bucket import _on_tpu

    return _on_tpu()


# The scope of a payload that moves apart from its sort (the row-index
# form of :func:`sort_carry` where the row's width chose it).
PAYLOAD_SCOPE = "dryad.sort.payload"

# How a row's columns go through a carried sort: as extra ``lax.sort``
# operands ("ride"), or gathered afterwards by one carried row index
# ("index").
RIDE, INDEX = "ride", "index"

# The widest row, in 4-byte words, that rides its sorts on the TPU and
# that is gathered a column at a time elsewhere.  The compile time of
# a variadic ``lax.sort`` on the TPU grows far faster than its
# operands: the ``order_by`` of a 26-word row (the sort benchmark's
# 100-byte record), three carried sorts of 28 - 29 operands, had not
# compiled after 1,200 s on the chip's host, where the 4-operand sorts
# of ``sort-1c`` take 86 s together (``PERF.md`` section 6, PR 32).  A
# wider row goes by the row index, all its words in one stacked
# gather.  No row of the cells that ride is wider than 5 words; where
# between 5 and 26 the two forms cross has not been measured.
WIDE_ROW_WORDS = 8


def carry_form(row_words: int) -> str:
    """The form :func:`sort_carry` takes for a row of ``row_words``
    4-byte words, from what a trace can see: the platform and the
    row's width."""
    rides = _carry_profitable() and row_words <= WIDE_ROW_WORDS
    return RIDE if rides else INDEX


def _words(arrays: Sequence[jax.Array]) -> int:
    """4-byte words a row of these columns takes (shapes only)."""
    return sum(
        -(-a.dtype.itemsize * math.prod(a.shape[1:]) // 4) for a in arrays
    )


class _WidestRow(threading.local):
    words = 0


_widest_row = _WidestRow()


@contextlib.contextmanager
def widest_row():
    """Trace-time record of the widest row (4-byte words of the carried
    columns) any :func:`sort_carry` under it moved: the ``row_words``
    stat of a stage's ``dispatch`` span.  Yields a one-element list
    that holds the reading once the block has ended."""
    before, _widest_row.words = _widest_row.words, 0
    seen = [0]
    try:
        yield seen
    finally:
        seen[0] = _widest_row.words
        _widest_row.words = max(before, seen[0])


@jax.named_scope("dryad.sort.carry")
def sort_carry(
    operands: Sequence[jax.Array],
    valid: jax.Array,
    carry: Sequence[jax.Array] = (),
    form: Optional[str] = None,
) -> Tuple[jax.Array, List[jax.Array], List[jax.Array]]:
    """Stable sort (valid rows first, lexicographic by uint32 operands)
    carrying payload arrays along.

    Returns ``(sorted_valid, sorted_operands, sorted_carry)``.  The
    permutation is identical to ``take(sort_order_by_operands(...))``
    (same stable key comparison).  On TPU the payload rides the sort
    as extra ``lax.sort`` operands — cheaper there than
    sort-index-then-gather (:func:`_carry_profitable`); elsewhere the
    payload is gathered by the sorted row index (cheaper off-TPU).
    ``lax.sort`` operands share one shape, so a payload with trailing
    dimensions never rides: it is gathered by one carried row index,
    which is sorted only when such a payload is present.  ``form``
    (``RIDE`` / ``INDEX``) is what :func:`carry_form` says of the
    row's width unless a caller that compares the two forces one.
    """
    inv = jnp.logical_not(valid).astype(jnp.uint32)
    ops = (inv,) + tuple(o.astype(jnp.uint32) for o in operands)
    row_words = _words(carry)
    _widest_row.words = max(_widest_row.words, row_words)
    chosen = form or carry_form(row_words)
    rides = [chosen == RIDE and c.ndim == 1 for c in carry]
    riders = tuple(c for c, r in zip(carry, rides) if r)
    if not all(rides):  # the row index, last, for what cannot ride
        riders += (jnp.arange(valid.shape[0], dtype=jnp.int32),)
    res = jax.lax.sort(ops + riders, num_keys=len(ops), is_stable=True)
    rode, order = iter(res[len(ops):]), res[-1]
    sorted_valid = res[0] == 0
    with jax.named_scope(PAYLOAD_SCOPE):  # what moves apart from the sort
        apart = iter(_take_rows(
            [c for c, r in zip(carry, rides) if not r], order,
            stacked=row_words > WIDE_ROW_WORDS,
        ))
    moved = [next(rode) if r else next(apart) for r in rides]
    return sorted_valid, list(res[1:len(ops)]), moved


def _stacks(c: jax.Array) -> bool:
    """A column whose rows are one 4-byte word each: what a stacked
    gather can carry (a ``pred`` and a column with trailing dimensions,
    a BYTES column's words, go by themselves; a split 64-bit column is
    two such columns)."""
    return c.ndim == 1 and c.dtype.itemsize == 4


def _take_rows(
    columns: Sequence[jax.Array], order: jax.Array, stacked: bool
) -> List[jax.Array]:
    """``[c[order] for c in columns]``.  ``stacked``: the 4-byte 1-D
    columns go as one ``[columns, rows]`` array through ONE gather
    along its rows, which on the TPU costs a tenth of a gather a
    column (26 words over 2^24 slots: 0.663 s against 6.27 s;
    ``PERF.md`` section 6, PR 32)."""
    stack = [stacked and _stacks(c) for c in columns]
    words = [
        jax.lax.bitcast_convert_type(c, jnp.uint32)
        for c, s in zip(columns, stack) if s
    ]
    if len(words) < 2:
        return [c[order] for c in columns]
    taken = iter(jnp.stack(words)[:, order])
    return [
        jax.lax.bitcast_convert_type(next(taken), c.dtype) if s else c[order]
        for c, s in zip(columns, stack)
    ]


# The narrowest row, in 4-byte words, whose columns share ONE stacked
# gather when they share an index that is no sort's permutation
# (:func:`take_rows`; the join's pair slots): every row of two words or
# more.  Chip-measured on one v5e (PR 42, ``PERF.md`` section 6),
# seconds a call over 10,485,760 slots, the stack and the unstack
# included, a gather a column against one stacked gather:
#
#   words   ascending index into 2^23 rows   random index into 2^16 rows
#     1     0.0910                           0.0908
#     2     0.3891    0.0653                 0.1694    0.0345
#     3     0.6868    0.2350                 0.2478    0.0418
#     4     0.9849    0.2360                 0.3258    0.0489
#     6     1.5797    0.2572                 0.4820    0.0633
#
# The stacked gather wins at every width and both kinds of index, so
# the rule is a width test with no other term.  Two things the table
# does not say by itself: gathers a column in one program cost more
# than as many programs of one (0.389 s for two, 0.091 s for one), and
# the stacked gather has a cliff at the TABLE's size, not at a width:
# from a table of 64 MiB or less it costs 3.3 - 6.3 ns a slot, from a
# larger one 12.6 - 21.7 (2 words x 2^23 rows 0.0653 s, 3 words 0.2273;
# 4 words x 2^22 rows 0.0667), whatever the words beyond it.
SHARED_GATHER_WORDS = 2


def stacked_words(columns: Sequence[jax.Array]) -> int:
    """How many of ``columns`` :func:`take_rows` sends through ONE
    stacked gather; 0 where every column is gathered by itself."""
    words = sum(_stacks(c) for c in columns)
    return words if words >= SHARED_GATHER_WORDS else 0


# The most slots one stacked gather of :func:`take_rows` covers.  Where
# the table is small (2^18 rows or fewer on the v5e: its rows, padded
# to the 128 lanes, then fit the chip's vector memory) the TPU's
# compiler hands the stacked gather's result over with every slot's
# words padded to 128 lanes, 512 B a slot whatever the words: 5.4 GB
# of the program's temporaries for the 10,485,760 pair slots of the
# ``join-topk-1c`` cell, which ``memory_stats()`` does not count and a
# join a few times larger would not compile under.  A block at a time,
# that buffer is 512 MiB and dies with its block.  Chip-measured (PR
# 42; seconds a call and the compiled program's temporaries, MB, over
# 10,485,760 slots): 2 words from 2^16 rows by a random index, whole
# 0.0344 / 5,369, blocks of 2^20 0.0349 / 619, of 2^21 0.1031 / 1,152;
# 3 words from 2^23 rows by an ascending index, whole 0.2272 / 302,
# blocks of 2^20 0.2106 / 244; the join's pair-slot part at the cell's
# shapes, whole 0.3078 / 5,369, blocks of 2^20 0.2944 / 626, of 2^21
# 0.3645 / 1,182 (``tests/test_tpu_compile.py`` holds the bound).
# :func:`sort_carry`'s own stacked gather needs no blocks: its slots
# are its table's rows, so a table small enough to be padded makes a
# result of at most 2^18 slots, 128 MiB.
STACK_BLOCK_SLOTS = 1 << 20


def take_rows(
    columns: Sequence[jax.Array], index: jax.Array
) -> List[jax.Array]:
    """``[c[index] for c in columns]`` for columns that share an index
    which is no sort's permutation (the join's pair slots): the form is
    :func:`stacked_words`' rule, bit-exact either way, the stacked one
    over ``STACK_BLOCK_SLOTS`` slots at a time."""
    if not stacked_words(columns):
        return _take_rows(columns, index, stacked=False)
    blocks = [
        _take_rows(columns, index[at:at + STACK_BLOCK_SLOTS], stacked=True)
        for at in range(0, index.shape[0], STACK_BLOCK_SLOTS)
    ]
    if len(blocks) == 1:
        return blocks[0]
    return [jnp.concatenate(taken) for taken in zip(*blocks)]


def sort_batch_by_operands(
    batch: ColumnBatch, operands: Sequence[jax.Array]
) -> ColumnBatch:
    """Sort a whole batch by uint32 operands (valid rows first); with
    no operands, stable compaction.  The one way a stable permutation
    is applied to a batch (data movement per :func:`sort_carry`)."""
    names = batch.columns
    valid, _, carried = sort_carry(
        operands, batch.valid, [batch.data[n] for n in names]
    )
    return ColumnBatch(dict(zip(names, carried)), valid)


def sorted_ranks(
    sorted_u32: jax.Array, queries_u32: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Both ranks of every query in an ascending uint32 array:
    ``(searchsorted(..., "left"), searchsorted(..., "right"))``, int32,
    in query order, by a merge and not by ``log n`` rounds of gather.
    One stable ``lax.sort`` of the sorted side followed by the queries,
    carrying each query's index, leaves the sorted side's elements first
    inside every run of equal values.  There the inclusive running count
    of sorted-side elements is the right rank, and the exclusive count
    at a run's head, carried along the run by a running maximum, the
    left rank; a second sort, on the carried index, brings both back to
    query order.  Chip-measured on one v5e, seconds a call (PR 27): 2^23
    queries into 2^16, the two ``jnp.searchsorted`` 2.0465,
    ``searchsorted(method="sort")`` twice 0.2331, this 0.0672 (0.1445
    with two scatters for the way back); 2^18 into 2^23, the largest
    ratio measured, 0.1992, 0.1671 and 0.0656.  Far fewer queries into
    a long array would be cheaper searched, by at most this merge, which
    costs no more than the sort that made the array ascending: no shape
    measured is there, so there is one form."""
    n_s, n_q = sorted_u32.shape[0], queries_u32.shape[0]
    keys = jnp.concatenate([sorted_u32, queries_u32])
    # the query's index; -1 marks the sorted side and sorts it first on
    # the way back
    who = jnp.concatenate(
        [jnp.full((n_s,), -1, jnp.int32), jnp.arange(n_q, dtype=jnp.int32)]
    )
    keys, who = jax.lax.sort((keys, who), num_keys=1, is_stable=True)
    from_sorted = (who < 0).astype(jnp.int32)
    right = jnp.cumsum(from_sorted)
    head = jnp.concatenate([jnp.ones((1,), jnp.bool_), keys[1:] != keys[:-1]])
    left = jax.lax.cummax(jnp.where(head, right - from_sorted, 0))
    _, left, right = jax.lax.sort((who, left, right), num_keys=1)
    return left[n_s:], right[n_s:]


@jax.named_scope("dryad.sort.splitters")
def sample_splitters(
    key_u32: jax.Array,
    valid: jax.Array,
    num_partitions: int,
    samples_per_partition: int,
    axis_name: str = "p",
) -> jax.Array:
    """Elect P-1 range splitters from per-device samples (replicated).

    Each device contributes ``samples_per_partition`` evenly spaced
    values from its sorted valid keys; an ``all_gather`` pools them; the
    pooled sorted sample is cut at P-1 evenly spaced ranks.  The analog
    of sampler stage + ``DrDynamicRangeDistributionManager`` splitter
    election, minus the host round-trip.
    """
    P, m = num_partitions, samples_per_partition
    _, (ks,), _ = sort_carry([key_u32], valid)
    count = jnp.sum(valid.astype(jnp.int32))

    # Evenly spaced sample positions in the valid prefix.
    pos = (jnp.arange(m, dtype=jnp.float32) + 0.5) * count.astype(jnp.float32) / m
    idx = jnp.clip(pos.astype(jnp.int32), 0, jnp.maximum(count - 1, 0))
    sample = ks[idx]
    sample_valid = jnp.full((m,), count > 0)

    all_samples = jax.lax.all_gather(sample, axis_name, tiled=True)
    all_valid = jax.lax.all_gather(sample_valid, axis_name, tiled=True)

    total = jnp.sum(all_valid.astype(jnp.int32))
    sorted_ops = jax.lax.sort(
        (jnp.where(all_valid, all_samples, jnp.uint32(0xFFFFFFFF)),),
        num_keys=1,
    )[0]
    ranks = (jnp.arange(1, P, dtype=jnp.float32) * total.astype(jnp.float32) / P)
    sidx = jnp.clip(ranks.astype(jnp.int32), 0, jnp.maximum(total - 1, 0))
    return sorted_ops[sidx]


def range_dest(key_u32: jax.Array, splitters: jax.Array) -> jax.Array:
    """Destination partition per row: searchsorted into the splitters.

    ``side='right'`` so rows equal to a splitter go right — equal keys
    always share a partition, keeping secondary ordering purely local.
    """
    return jnp.searchsorted(splitters, key_u32, side="right").astype(jnp.int32)


# -- skew-proof multi-word variant (automatic heavy-key mitigation) --------

@jax.named_scope("dryad.sort.splitters")
def sample_splitters_multi(
    words: Sequence[jax.Array],
    valid: jax.Array,
    num_partitions: int,
    samples_per_partition: int,
    axis_name: str = "p",
) -> List[jax.Array]:
    """Splitter election over a LEXICOGRAPHIC multi-word key.

    The automatic skew mitigation (reference
    ``DrDynamicDistributor.h:26,79`` redistributes by observed size):
    callers append a uniform synthetic tiebreak word, so a heavy key —
    which would pin its entire run to one range partition and force
    boost-doubling — is split across partitions in sample-estimated
    proportions.  Returns one ``(P-1,)`` splitter array per word.
    """
    P, m = num_partitions, samples_per_partition
    _, sorted_words, _ = sort_carry(list(words), valid)
    count = jnp.sum(valid.astype(jnp.int32))
    pos = (jnp.arange(m, dtype=jnp.float32) + 0.5) * count.astype(jnp.float32) / m
    idx = jnp.clip(pos.astype(jnp.int32), 0, jnp.maximum(count - 1, 0))
    samples = [w[idx] for w in sorted_words]
    sample_valid = jnp.full((m,), count > 0)

    gathered = [
        jax.lax.all_gather(s, axis_name, tiled=True) for s in samples
    ]
    all_valid = jax.lax.all_gather(sample_valid, axis_name, tiled=True)
    total = jnp.sum(all_valid.astype(jnp.int32))
    # invalid samples sort to +inf in every word
    ops = tuple(
        jnp.where(all_valid, g, jnp.uint32(0xFFFFFFFF)).astype(jnp.uint32)
        for g in gathered
    )
    sorted_ops = jax.lax.sort(ops, num_keys=len(ops))
    ranks = jnp.arange(1, P, dtype=jnp.float32) * total.astype(jnp.float32) / P
    sidx = jnp.clip(ranks.astype(jnp.int32), 0, jnp.maximum(total - 1, 0))
    return [so[sidx] for so in sorted_ops]


def range_dest_multi(
    words: Sequence[jax.Array], splitters: Sequence[jax.Array]
) -> jax.Array:
    """Destination by lexicographic compare against multi-word splitters
    (side='right' semantics: a row passes every splitter <= it)."""
    n = words[0].shape[0]
    pm1 = splitters[0].shape[0]
    lt = jnp.zeros((n, pm1), jnp.bool_)  # splitter < row, decided so far
    eq = jnp.ones((n, pm1), jnp.bool_)
    for w, s in zip(words, splitters):
        w2 = w.astype(jnp.uint32)[:, None]
        s2 = s.astype(jnp.uint32)[None, :]
        lt = lt | (eq & (s2 < w2))
        eq = eq & (s2 == w2)
    return jnp.sum((lt | eq).astype(jnp.int32), axis=1)


def spread_word(n: int) -> jax.Array:
    """Uniform synthetic tiebreak word (Knuth multiplicative hash of the
    row index): equal keys get distinct, evenly distributed tiebreaks,
    so splitter election can cut inside a heavy key's run."""
    return (
        jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(2654435761)
    ).astype(jnp.uint32)
