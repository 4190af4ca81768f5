"""On-device segmented (group-by) reduction.

The TPU-native replacement for the reference's GroupBy combiner machinery:
sort rows by key, detect segment boundaries, reduce per segment with XLA
scatter-adds / segmented scans — instead of hash tables inside vertex
processes (reference ``LinqToDryad/DryadLinqVertex.cs`` GroupBy operators)
and GM-built aggregation trees (``DrDynamicAggregateManager.h:35-168``).
The machine→pod→overall tree becomes: per-chip partial reduce (this
module, pre-shuffle) + post-shuffle final reduce — the
Seed/Accumulate/RecursiveAccumulate/FinalReduce decomposition of
``LinqToDryad/IDecomposable.cs:35-71``.

Kernel-strategy note: raw scatter-adds serialize on TPU, so the
general path stays sort-based and the bounded-key fast path stays the
MXU kernel (``group_by(dense=K)``, auto-selected for dictionary STRING
and ingest-bounded INT32 keys).  Within the sort path, the sort
carries all columns as ``lax.sort`` operands (``ops/sort.py``: moving
rows by XLA ``gather`` instead was 62-77% of the exchange cells' device
time, ``PERF.md`` section 6, PR 25) and counts come from one shared
start-position scatter.  What the fold costs on the chip is in
``PERF.md`` section 5 (``group_reduce.fold``).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from dryad_tpu.columnar.batch import ColumnBatch
from dryad_tpu.ops.sort import sort_batch_by_operands
from dryad_tpu.ops.sortkeys import keys_equal_adjacent, to_sortable_u32


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """One built-in aggregation over a physical column.

    op: sum | count | min | max | mean | any | all | first
    col: input physical column (None for count)
    out: output physical column name
    """

    op: str
    col: Optional[str]
    out: str


def _segment_layout(
    batch: ColumnBatch, key_cols: Sequence[str],
    scope: str = "dryad.group_reduce.layout",
) -> Tuple[ColumnBatch, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sort+compact by keys; return (sorted batch, valid, start, seg, nseg).

    ``seg`` maps each row to its segment id, with invalid rows mapped to
    the sentinel segment ``capacity`` (dropped on slice).  ``scope`` is
    the caller's name for the pass in a device trace.
    """
    cap = batch.capacity
    with jax.named_scope(scope):
        sb = sort_batch_by_operands(
            batch, [to_sortable_u32(batch.data[k]) for k in key_cols]
        )
        v = sb.valid
        eq = keys_equal_adjacent([sb.data[k] for k in key_cols])
        start = v & ~eq
        seg_id = jnp.cumsum(start.astype(jnp.int32)) - 1
        seg = jnp.where(v, seg_id, cap)
        nseg = jnp.sum(start.astype(jnp.int32))
    return sb, v, start, seg, nseg


def _first_scatter(
    val: jax.Array, start: jax.Array, seg: jax.Array, cap: int
) -> jax.Array:
    """Per-segment value from the segment's first row."""
    idx = jnp.where(start, seg, cap)
    return jnp.zeros((cap + 1,) + val.shape[1:], val.dtype).at[idx].set(val)[:cap]


PAIR_OPS = ("sum64", "min64", "max64")


def _pair_combine(op: str):
    """The 64-bit word-pair combine for ``op`` — the ONE source of truth
    for the paired-u32 arithmetic (carry-propagating add for ``sum64``;
    signed-lexicographic select — high word signed, low word unsigned —
    for ``min64``/``max64``), shared by the segmented and scalar
    reducers.  jax x64 stays off: int64/float64 live as two u32 device
    words (``columnar/schema.py``); the reference's numeric aggregate
    surface is ``DryadLinqQueryGen.cs:3439ff``."""
    if op == "sum64":
        def combine(alo, ahi, blo, bhi):
            slo = alo + blo  # uint32 wraps mod 2^32
            carry = (slo < blo).astype(jnp.uint32)
            return slo, ahi + bhi + carry
    else:
        def combine(alo, ahi, blo, bhi):
            ahs, bhs = ahi.astype(jnp.int32), bhi.astype(jnp.int32)
            a_less = (ahs < bhs) | ((ahs == bhs) & (alo < blo))
            take_a = a_less if op == "min64" else ~a_less
            return (
                jnp.where(take_a, alo, blo),
                jnp.where(take_a, ahi, bhi),
            )

    return combine


def _pair_identity(op: str) -> Tuple[jax.Array, jax.Array]:
    if op == "sum64":
        return jnp.uint32(0), jnp.uint32(0)
    if op == "min64":  # +max signed-64 pair
        return jnp.uint32(0xFFFFFFFF), jnp.uint32(0x7FFFFFFF)
    return jnp.uint32(0), jnp.uint32(0x80000000)  # max64: min signed-64


def _segmented_pair_reduce(
    op: str,
    lo: jax.Array,
    hi: jax.Array,
    v: jax.Array,
    start: jax.Array,
    seg: jax.Array,
    cap: int,
) -> Tuple[jax.Array, jax.Array]:
    """Per-segment 64-bit reduce over a split (low, high) uint32 column:
    a flagged segmented ``associative_scan`` wrapping
    :func:`_pair_combine`."""
    flags = start
    base = _pair_combine(op)

    def combine(a, b):
        fa, alo, ahi = a
        fb, blo, bhi = b
        mlo, mhi = base(alo, ahi, blo, bhi)
        return (
            fa | fb,
            jnp.where(fb, blo, mlo),
            jnp.where(fb, bhi, mhi),
        )

    _, slo, shi = jax.lax.associative_scan(combine, (flags, lo, hi))

    # Segment results live at each segment's LAST valid row (invalid
    # rows sort to the tail, so they never contaminate gathered rows).
    nxt_start = jnp.concatenate([start[1:], jnp.array([True])])
    nxt_valid = jnp.concatenate([v[1:], jnp.array([False])])
    last = v & (nxt_start | ~nxt_valid)
    idx = jnp.where(last, seg, cap)
    out_lo = jnp.zeros((cap + 1,), lo.dtype).at[idx].set(slo)[:cap]
    out_hi = jnp.zeros((cap + 1,), hi.dtype).at[idx].set(shi)[:cap]
    return out_lo, out_hi


def pair_to_f32(lo: jax.Array, hi: jax.Array) -> jax.Array:
    """Approximate f32 value of a split signed-64 word pair
    (hi signed * 2^32 + lo unsigned) — the ONE decode used by every
    mean64 finalize."""
    return (
        hi.astype(jnp.int32).astype(jnp.float32) * jnp.float32(4294967296.0)
        + lo.astype(jnp.float32)
    )


def pair_scalar_reduce(
    op: str, lo: jax.Array, hi: jax.Array, valid: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Whole-array 64-bit reduce of a split (low, high) word column to
    one (lo, hi) scalar pair — :func:`_pair_combine` without segment
    flags (Sum/Min/Max over int64/float64 columns without x64).
    Invalid rows are replaced by the op's identity, so an all-invalid
    input reduces to the identity pair (neutral under further
    combining), and the scan's last element is the total.
    """
    ilo, ihi = _pair_identity(op)
    lo = jnp.where(valid, lo, ilo)
    hi = jnp.where(valid, hi, ihi)
    base = _pair_combine(op)

    def combine(a, b):
        return base(a[0], a[1], b[0], b[1])

    slo, shi = jax.lax.associative_scan(combine, (lo, hi))
    return slo[-1], shi[-1]


def group_reduce(
    batch: ColumnBatch,
    key_cols: Sequence[str],
    aggs: Sequence[AggSpec],
) -> ColumnBatch:
    """Group rows by key columns and reduce; output capacity == input.

    Output batch holds one row per distinct key (rows 0..nseg-1 valid):
    the key columns plus one column per AggSpec.
    """
    sb, v, start, seg, nseg = _segment_layout(batch, key_cols)
    return _segmented_fold(sb, v, start, seg, nseg, key_cols, aggs)


@jax.named_scope("dryad.group_reduce.fold")
def _segmented_fold(
    sb: ColumnBatch, v, start, seg, nseg,
    key_cols: Sequence[str], aggs: Sequence[AggSpec],
) -> ColumnBatch:
    """The fold of :func:`group_reduce` over rows already laid out by
    segment (:func:`_segment_layout`): one output row a segment."""
    cap = sb.capacity
    nsegments = cap + 1  # includes the invalid-row sentinel segment

    out: Dict[str, jax.Array] = {}
    for k in key_cols:
        out[k] = _first_scatter(sb.data[k], start, seg, cap)

    seg_count = None
    if any(a.op in ("count", "mean") for a in aggs):
        # Per-segment row counts WITHOUT a segment_sum: one shared
        # scatter of segment-start row positions, then adjacent
        # differences.  Scatter-ADD cost grows with same-address
        # run length on the TPU, while a scatter-set of distinct
        # segment ids does not.  Non-start rows get an out-of-range
        # index and are dropped (mode="drop"); the surviving
        # in-bounds writes go to distinct slots, so no
        # unique_indices promise is needed.
        nvalid = jnp.sum(v.astype(jnp.int32))
        idx = jnp.where(start, seg, cap + 2)
        start_pos = (
            jnp.full((cap + 2,), nvalid, jnp.int32)
            .at[idx]
            .set(jnp.arange(cap, dtype=jnp.int32), mode="drop")[: cap + 1]
        )
        seg_count = start_pos[1:] - start_pos[:cap]

    for a in aggs:
        if a.op == "count":
            out[a.out] = seg_count
            continue
        if a.op in PAIR_OPS:
            # a.col names the LOW word of a split 64-bit column; the
            # high word lives alongside it and the output writes both.
            lo_col = a.col
            hi_col = lo_col[: -len("#h0")] + "#h1"
            out_lo, out_hi = _segmented_pair_reduce(
                a.op, sb.data[lo_col], sb.data[hi_col], v, start, seg, cap
            )
            out[f"{a.out}#h0"] = out_lo
            out[f"{a.out}#h1"] = out_hi
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
            c = seg_count.astype(jnp.float32)
            out[a.out] = s / jnp.maximum(c, 1.0)
        elif a.op == "any":
            m = jax.ops.segment_max(col.astype(jnp.int32), seg, nsegments)[:cap]
            out[a.out] = m.astype(jnp.bool_)
        elif a.op == "all":
            m = jax.ops.segment_min(
                jnp.where(v, col, True).astype(jnp.int32), seg, nsegments
            )[:cap]
            out[a.out] = m.astype(jnp.bool_)
        elif a.op == "first":
            out[a.out] = _first_scatter(col, start, seg, cap)
        else:
            raise ValueError(f"unknown agg op {a.op!r}")

    valid = jnp.arange(cap, dtype=jnp.int32) < nseg
    return ColumnBatch(out, valid)


# -- generic user decompositions ------------------------------------------

MergeFn = Callable[[Dict[str, jax.Array], Dict[str, jax.Array]], Dict[str, jax.Array]]


def segmented_scan(
    start: jax.Array, vals: Dict[str, jax.Array], merge: MergeFn
) -> Dict[str, jax.Array]:
    """Inclusive segmented scan of ``vals`` under ``merge`` by doubling
    (Hillis-Steele): after the pass at distance ``d`` every slot holds
    its segment's reduction over the last ``2 d`` slots, so ``ceil(log2
    n)`` passes, each ONE elementwise fusion over the whole array that
    reads the state at ``i - d`` through a static slice.  ``merge(a, b)``
    is always handed the earlier rows as ``a``.

    Not ``lax.associative_scan``: its tree of odd/even slices is work-
    efficient, but every level is a handful of fusions of a shape of its
    own, and for the TPU the flagged six-channel scan of the
    ``groupby-skew-4c`` cell came to 230 MB of generated code at 2^20
    slots and no program at all at 2^23 (the compile was cut after
    1,500 s on the chip's host; PERF.md section 6, PR 41).  These passes
    compile in seconds at any size and move ``3 log2 n`` times the
    state through HBM, which at 2^24 slots is a tenth of what the
    scatters beside them cost."""
    n = start.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)

    def over(mask, x):  # ``mask`` against a column of any width
        return mask.reshape((n,) + (1,) * (x.ndim - 1))

    flag, d = start, 1
    while d < n:
        def back(x, d=d):  # x[i - d]; the first d slots are masked below
            return jnp.concatenate([x[:d], x[:-d]])

        reach = pos >= d
        merged = merge({k: back(x) for k, x in vals.items()}, vals)
        take = reach & ~flag  # no segment starts inside the slot's own window
        vals = {
            k: jnp.where(over(take, x), merged[k], x) for k, x in vals.items()
        }
        flag = flag | (reach & back(flag))
        d *= 2
    return vals


def group_combine(
    batch: ColumnBatch,
    key_cols: Sequence[str],
    state_cols: Sequence[str],
    merge: MergeFn,
) -> ColumnBatch:
    """Segmented reduce with an arbitrary associative ``merge``.

    ``state_cols`` name accumulator columns already produced by the
    user's Seed/Accumulate step; ``merge`` is RecursiveAccumulate
    (reference ``IDecomposable.cs:35-71``), applied pairwise and
    vectorized over rows.  Three passes, each under a scope of its own
    in a device trace: the rows sorted by key with the state carried
    (``dryad.group_combine.layout``), a flagged segmented scan
    (``.scan``, :func:`segmented_scan`) whose result at a segment's
    last row is the segment's reduction, and one scatter-set a column
    that puts it at the segment's slot (``.emit``).
    """
    cap = batch.capacity
    sb, v, start, seg, nseg = _segment_layout(
        batch, key_cols, scope="dryad.group_combine.layout"
    )

    with jax.named_scope("dryad.group_combine.scan"):
        scanned = segmented_scan(
            start, {c: sb.data[c] for c in state_cols}, merge
        )

    with jax.named_scope("dryad.group_combine.emit"):
        # Last row of each segment: next row starts a new segment / is
        # invalid / EOF.
        nxt_start = jnp.concatenate([start[1:], jnp.array([True])])
        nxt_valid = jnp.concatenate([v[1:], jnp.array([False])])
        last = v & (nxt_start | ~nxt_valid)

        out: Dict[str, jax.Array] = {}
        for k in key_cols:
            out[k] = _first_scatter(sb.data[k], start, seg, cap)
        idx = jnp.where(last, seg, cap)
        for c in state_cols:
            val = scanned[c]
            out[c] = jnp.zeros((cap + 1,) + val.shape[1:], val.dtype).at[idx].set(val)[:cap]

        valid = jnp.arange(cap, dtype=jnp.int32) < nseg
    return ColumnBatch(out, valid)


def distinct(batch: ColumnBatch, key_cols: Sequence[str]) -> ColumnBatch:
    """Distinct rows over key columns (reference Distinct operator):
    group with per-segment 'first' on every non-key column."""
    others = [c for c in batch.columns if c not in set(key_cols)]
    aggs = [AggSpec("first", c, c) for c in others]
    return group_reduce(batch, key_cols, aggs)
