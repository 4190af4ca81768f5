"""On-device segmented (group-by) reduction.

The TPU-native replacement for the reference's GroupBy combiner machinery:
sort rows by key, detect segment boundaries, reduce each segment by a
segmented scan read at the segment's last row, and put those rows in
their slots by ONE order-preserving compaction shared by every output
column (no scatter, no scatter-add, no gather) — instead of hash tables
inside vertex processes (reference ``LinqToDryad/DryadLinqVertex.cs`` GroupBy operators)
and GM-built aggregation trees (``DrDynamicAggregateManager.h:35-168``).
The machine→pod→overall tree becomes: per-chip partial reduce (this
module, pre-shuffle) + post-shuffle final reduce — the
Seed/Accumulate/RecursiveAccumulate/FinalReduce decomposition of
``LinqToDryad/IDecomposable.cs:35-71``.

Kernel-strategy note: a TPU scatter pays by the update, not by the
rows that land, and pays again for every column, so the general path
stays sort-based and the bounded-key fast path stays the MXU kernel
(``group_by(dense=K)``, auto-selected for dictionary STRING and
ingest-bounded INT32 keys).  Within the sort path, the sort carries all
columns as ``lax.sort`` operands (``ops/sort.py``: moving rows by XLA
``gather`` instead was 62-77% of the exchange cells' device time,
``PERF.md`` section 6, PR 25).  The rows being sorted, a group is a run
and its slot is its run's number: placing the run-end rows is a
monotone move (:func:`compact_rows`), where the scatter a column it
replaced was 73% of ``groupby-4c``'s device time and 65% of
``groupby-skew-4c``'s, ten times what reducing the rows cost
(``PERF.md`` section 6, PR 47).  Counts are differences of the run
ends' positions, which the compaction hands back for nothing.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from dryad_tpu.columnar.batch import ColumnBatch
from dryad_tpu.ops import wide
from dryad_tpu.ops.sort import sort_batch_by_operands
from dryad_tpu.ops.sortkeys import keys_equal_adjacent, to_sortable_u32


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """One built-in aggregation over a physical column.

    op: sum | count | min | max | mean | any | all | first, and the
        64-bit forms sum64 | min64 | max64 | mean64 (``plan/lower.py``)
    col: input physical column (None for count); for a 64-bit form the
        ``#h0`` word of a split column, or a 32-bit column to be carried
        sign-extended (:func:`pair_words`)
    out: output physical column name
    scale: of a DECIMAL input, the digits a ``mean64`` divides away so
        that the mean comes out in units
    """

    op: str
    col: Optional[str]
    out: str
    scale: int = 0


def _segment_layout(
    batch: ColumnBatch, key_cols: Sequence[str],
    scope: str = "dryad.group_reduce.layout",
) -> Tuple[ColumnBatch, jax.Array]:
    """Sort+compact by keys; return (sorted batch, start): valid rows
    first, a segment a run of them, ``start`` its first row.  ``scope``
    is the caller's name for the pass in a device trace.
    """
    with jax.named_scope(scope):
        sb = sort_batch_by_operands(
            batch, [to_sortable_u32(batch.data[k]) for k in key_cols]
        )
        eq = keys_equal_adjacent([sb.data[k] for k in key_cols])
        start = sb.valid & ~eq
    return sb, start


PAIR_OPS = ("sum64", "min64", "max64")


def _pair_combine(op: str):
    """The 64-bit word-pair combine for ``op``, from the one
    implementation of paired-u32 arithmetic (``ops/wide.py``: the
    carry-propagating add for ``sum64``; the signed compare, high word
    signed and low word unsigned, for ``min64`` / ``max64``), shared by
    the segmented and scalar reducers.  jax x64 stays off: int64 /
    float64 / wide DECIMAL live as two u32 device words
    (``columnar/schema.py``); the reference's numeric aggregate surface
    is ``DryadLinqQueryGen.cs:3439ff``."""
    if op == "sum64":
        return wide.add64

    def combine(alo, ahi, blo, bhi):
        a_less = wide.less64(alo, ahi, blo, bhi)
        take_a = a_less if op == "min64" else ~a_less
        return jnp.where(take_a, alo, blo), jnp.where(take_a, ahi, bhi)

    return combine


def _pair_identity(op: str) -> Tuple[jax.Array, jax.Array]:
    if op == "sum64":
        return jnp.uint32(0), jnp.uint32(0)
    if op == "min64":  # +max signed-64 pair
        return jnp.uint32(0xFFFFFFFF), jnp.uint32(0x7FFFFFFF)
    return jnp.uint32(0), jnp.uint32(0x80000000)  # max64: min signed-64


# Approximate f32 value of a split signed-64 word pair: the ONE decode
# used by every mean64 finalize.
pair_to_f32 = wide.pair_to_f32


def pair_mean(lo: jax.Array, hi: jax.Array, count: jax.Array, scale: int = 0) -> jax.Array:
    """The f32 mean every ``mean64`` ends in: the exact 64-bit sum
    rounded to f32, over the count (at least 1), over ``10^scale`` where
    the sum is a DECIMAL's, so that the mean is in units."""
    mean = pair_to_f32(lo, hi) / jnp.maximum(count.astype(jnp.float32), 1.0)
    return mean / jnp.float32(10.0**scale) if scale else mean


def pair_words(data: Dict[str, jax.Array], col: str) -> Tuple[jax.Array, jax.Array]:
    """The (lo, hi) words a 64-bit aggregate reads for ``col``: the
    ``#h0`` / ``#h1`` pair of a split column (``col`` names the low
    word), or a 32-bit column (a narrow DECIMAL) sign-extended, so that
    its sum is carried in 64 bits without the table holding them."""
    if col.endswith("#h0"):
        return data[col], data[col[: -len("#h0")] + "#h1"]
    return wide.widen(data[col])


def pair_scalar_reduce(
    op: str, lo: jax.Array, hi: jax.Array, valid: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Whole-array 64-bit reduce of a split (low, high) word column to
    one (lo, hi) scalar pair — :func:`_pair_combine` without segment
    flags (Sum/Min/Max over int64/float64 columns without x64).
    Invalid rows are replaced by the op's identity, so an all-invalid
    input reduces to the identity pair (neutral under further
    combining).  A halving tree (``ops/wide.py::tree_reduce``), not
    ``lax.associative_scan``: the scan builds every prefix to hand back
    the last, and its tree of odd/even slices is the form that gave no
    TPU program at 2^23 slots (:func:`segmented_scan`).
    """
    ilo, ihi = _pair_identity(op)
    return wide.tree_reduce(
        _pair_combine(op), (ilo, ihi),
        jnp.where(valid, lo, ilo), jnp.where(valid, hi, ihi),
    )


# -- the two passes every fold is made of -----------------------------------

MergeFn = Callable[[Dict[str, jax.Array], Dict[str, jax.Array]], Dict[str, jax.Array]]


def _over(mask: jax.Array, x: jax.Array) -> jax.Array:
    """A per-row ``mask`` against a column of any width."""
    return mask.reshape(mask.shape + (1,) * (x.ndim - 1))


def _doubling(n: int, one_pass, state):
    """``state`` after ``ceil(log2 n)`` passes of ``one_pass(d, state)``
    at distances ``d`` = 1, 2, 4, ...: ONE loop body with ``d`` traced,
    not a body a pass.  Unrolled (a static slice a pass) the compaction
    is no faster on the chip (six columns at 2^25 slots: 0.1956 s for
    0.1922) and a six-channel scan 12% faster (0.1475 for 0.1671), and
    both compile far slower everywhere: a group-by query 2.55 s for
    0.75 on the CPU mesh, where tier-1 lives, and 52 MB of generated
    code for 20 MB at 2^25 slots on the TPU (PERF.md section 6, PR 47)."""
    def body(bit, state):
        return one_pass(jnp.left_shift(jnp.int32(1), bit), state)

    return jax.lax.fori_loop(0, max(n - 1, 0).bit_length(), body, state)


def _shifted(x: jax.Array, d: jax.Array, fill) -> jax.Array:
    """``x[i + d]`` for a traced ``d`` of either sign, ``fill`` where
    that runs off the array: one dynamic slice of the padded column
    (the pad fuses into the slice's consumer and is never made, and an
    unaligned dynamic start costs nothing on the TPU: PERF.md section
    6, PR 43)."""
    n = x.shape[0]
    padded = jax.lax.pad(
        x, jnp.asarray(fill, x.dtype), [(n, n, 0)] + [(0, 0, 0)] * (x.ndim - 1)
    )
    return jax.lax.dynamic_slice_in_dim(padded, n + d, n)


def segmented_scan(
    start: jax.Array, vals: Dict[str, jax.Array], merge: MergeFn
) -> Dict[str, jax.Array]:
    """Inclusive segmented scan of ``vals`` under ``merge`` by doubling
    (Hillis-Steele): after the pass at distance ``d`` every slot holds
    its segment's reduction over the last ``2 d`` slots, so ``ceil(log2
    n)`` passes (:func:`_doubling`), each elementwise over the whole
    array, reading the state at ``i - d`` through a slice.  ``merge(a,
    b)`` is always handed the earlier rows as ``a``.

    Not ``lax.associative_scan``: its tree of odd/even slices is work-
    efficient, but every level is a handful of fusions of a shape of its
    own, and for the TPU the flagged six-channel scan of the
    ``groupby-skew-4c`` cell came to 230 MB of generated code at 2^20
    slots and no program at all at 2^23 (the compile was cut after
    1,500 s on the chip's host; PERF.md section 6, PR 41).  These passes
    compile in seconds at any size and move a few times the state
    through HBM a pass, which at 2^24 slots was a tenth of what the
    scatters beside them cost."""
    pos = jnp.arange(start.shape[0], dtype=jnp.int32)

    def one_pass(d, state):
        vals, flag = state
        merged = merge({k: _shifted(x, -d, 0) for k, x in vals.items()}, vals)
        take = (pos >= d) & ~flag  # no segment starts inside the slot's own window
        vals = {
            k: jnp.where(_over(take, x), merged[k], x) for k, x in vals.items()
        }
        return vals, flag | _shifted(flag, -d, False)

    return _doubling(start.shape[0], one_pass, (vals, start))[0]


def compact_rows(
    keep: jax.Array, cols: Dict[str, jax.Array]
) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Order-preserving compaction: the rows of ``cols`` where ``keep``
    at slots ``0 .. count-1`` in their order, zeros behind them; with
    them ``origin``, the position each placed row came from (-1 behind).

    A kept row moves left by ``shift``, the rows before it that are not
    kept, one bit of it a pass (:func:`_doubling`), LOWEST bit first: in
    the pass at distance ``d`` a slot takes the state of slot ``i + d``
    if that slot is live and its shift has bit ``d`` set, keeps its own
    if it is live with that bit clear, and is dead otherwise (a dead
    slot's shift is -1: the live flag rides in the sign).  The move is
    monotone (two kept rows ``i < j`` have ``shift_j - shift_i <= j - i
    - 1``), so after any number of low bits they are still apart: no
    slot is asked for twice.  Each pass is elementwise over (columns,
    shift), shared by all columns; no scatter, no gather, no sort.
    What it replaced and what each form costs on the chip: ``PERF.md``
    section 6, PR 47."""
    n = keep.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    shift = jnp.where(keep, pos - (jnp.cumsum(keep.astype(jnp.int32)) - 1), -1)

    def one_pass(d, state):
        cols, shift = state
        ahead = _shifted(shift, d, -1)
        inc = (ahead >= 0) & ((ahead & d) != 0)
        stay = (shift >= 0) & ((shift & d) == 0)
        cols = {
            k: jnp.where(_over(inc, x), _shifted(x, d, 0), x)
            for k, x in cols.items()
        }
        # a row's bits under d are spent, so its shift travels as it is
        return cols, jnp.where(inc, ahead, jnp.where(stay, shift, -1))

    cols, shift = _doubling(n, one_pass, (cols, shift))
    live = shift >= 0
    cols = {
        k: jnp.where(_over(live, x), x, jnp.zeros((), x.dtype))
        for k, x in cols.items()
    }
    return cols, jnp.where(live, pos + shift, -1)


def _place_groups(
    sb: ColumnBatch, start: jax.Array, key_cols: Sequence[str],
    scanned: Dict[str, jax.Array],
) -> Tuple[Dict[str, jax.Array], jax.Array, jax.Array]:
    """One row a segment at the segment's slot: its keys and ``scanned``
    as they stand at its last row (the next row starts a segment, is
    invalid, for invalid rows sort to the tail, or does not exist).
    Returns (columns, the last rows' positions, which slots hold a
    segment)."""
    v = sb.valid
    nxt_start = jnp.concatenate([start[1:], jnp.array([True])])
    nxt_valid = jnp.concatenate([v[1:], jnp.array([False])])
    cols, origin = compact_rows(
        v & (nxt_start | ~nxt_valid),
        {**{k: sb.data[k] for k in key_cols}, **scanned},
    )
    return cols, origin, origin >= 0


# -- built-in aggregates -----------------------------------------------------

_MERGES = {
    "sum": jnp.add,
    "mean": jnp.add,
    "min": jnp.minimum,
    "max": jnp.maximum,
    "any": jnp.logical_or,
    "all": jnp.logical_and,
    "first": lambda a, b: a,
}


def _agg_channels(
    data: Dict[str, jax.Array], aggs: Sequence[AggSpec]
) -> Tuple[Dict[str, jax.Array], MergeFn]:
    """The scan's channels for ``aggs``, named as the outputs are, and
    the merge over them (``count`` needs none: it is a difference of
    run-end positions)."""
    vals: Dict[str, jax.Array] = {}
    ops: Dict[str, Callable] = {}
    pairs: Dict[str, Callable] = {}
    for a in aggs:
        if a.op == "count":
            continue
        if a.op in PAIR_OPS:
            vals[f"{a.out}#h0"], vals[f"{a.out}#h1"] = pair_words(data, a.col)
            pairs[a.out] = _pair_combine(a.op)
            continue
        if a.op not in _MERGES:
            raise ValueError(f"unknown agg op {a.op!r}")
        col = data[a.col]
        if a.op == "mean":
            col = col.astype(jnp.float32)
        elif a.op in ("any", "all"):
            col = col.astype(jnp.bool_)
        vals[a.out], ops[a.out] = col, _MERGES[a.op]

    def merge(a, b):
        out = {k: op(a[k], b[k]) for k, op in ops.items()}
        for name, combine in pairs.items():
            lo, hi = f"{name}#h0", f"{name}#h1"
            out[lo], out[hi] = combine(a[lo], a[hi], b[lo], b[hi])
        return out

    return vals, merge


def fold_stats(key_cols: Sequence[str], aggs: Sequence[AggSpec]) -> Dict[str, int]:
    """What one :func:`group_reduce` carries a slot, from its arguments
    alone: ``group_keys`` (physical key columns), ``agg_channels`` (the
    scan's channels: a ``count`` needs none), ``agg64_channels`` (those
    of them that are word pairs) and ``agg_state_words`` (4-byte words
    of scan state: two a pair, one any other channel).  The stats of a
    group-by stage's ``dispatch`` span."""
    channels = [a for a in aggs if a.op != "count"]
    pairs = sum(a.op in PAIR_OPS for a in channels)
    return dict(
        group_keys=len(key_cols), agg_channels=len(channels),
        agg64_channels=pairs, agg_state_words=len(channels) + pairs,
    )


def group_reduce(
    batch: ColumnBatch,
    key_cols: Sequence[str],
    aggs: Sequence[AggSpec],
) -> ColumnBatch:
    """Group rows by key columns and reduce; output capacity == input.

    Output batch holds one row per distinct key (rows 0..nseg-1 valid):
    the key columns plus one column per AggSpec.  :func:`group_combine`
    with a merge built from the AggSpecs, under scopes of its own
    (``dryad.group_reduce.layout``, ``.fold`` with the scan and the
    compaction as ``fold/scan`` and ``fold/place``).
    """
    sb, start = _segment_layout(batch, key_cols)
    with jax.named_scope("dryad.group_reduce.fold"):
        vals, merge = _agg_channels(sb.data, aggs)
        with jax.named_scope("scan"):
            scanned = segmented_scan(start, vals, merge)
        with jax.named_scope("place"):
            placed, origin, valid = _place_groups(sb, start, key_cols, scanned)
        # valid rows come first, so a run's rows are those after the
        # run end before it
        before = jnp.concatenate([jnp.array([-1], jnp.int32), origin[:-1]])
        count = jnp.where(valid, origin - before, 0)

        out = {k: placed[k] for k in key_cols}
        for a in aggs:
            if a.op == "count":
                out[a.out] = count
            elif a.op in PAIR_OPS:
                out[f"{a.out}#h0"] = placed[f"{a.out}#h0"]
                out[f"{a.out}#h1"] = placed[f"{a.out}#h1"]
            elif a.op == "mean":
                c = count.astype(jnp.float32)
                out[a.out] = placed[a.out] / jnp.maximum(c, 1.0)
            else:
                out[a.out] = placed[a.out]
    return ColumnBatch(out, valid)


# -- generic user decompositions ------------------------------------------


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
    last row is the segment's reduction, and one compaction of those
    rows, key and state together, that puts each at its segment's slot
    (``.emit``, :func:`compact_rows`).
    """
    sb, start = _segment_layout(
        batch, key_cols, scope="dryad.group_combine.layout"
    )

    with jax.named_scope("dryad.group_combine.scan"):
        scanned = segmented_scan(
            start, {c: sb.data[c] for c in state_cols}, merge
        )

    with jax.named_scope("dryad.group_combine.emit"):
        out, _, valid = _place_groups(sb, start, key_cols, scanned)
    return ColumnBatch(out, valid)


def distinct(batch: ColumnBatch, key_cols: Sequence[str]) -> ColumnBatch:
    """Distinct rows over key columns (reference Distinct operator):
    group with per-segment 'first' on every non-key column."""
    others = [c for c in batch.columns if c not in set(key_cols)]
    aggs = [AggSpec("first", c, c) for c in others]
    return group_reduce(batch, key_cols, aggs)
