"""Per-partition kernels for each StageOp, composed into one stage fn.

The analog of the generated vertex method body: where the reference
CodeDOM-generates one C# method per stage chaining operator calls over
channel readers/writers (``DryadLinqCodeGen.cs:1910`` AddVertexMethod),
we compose jit-traceable kernels over ColumnBatch slots and let XLA fuse
the chain.  All shapes are static: capacities derive from entry
capacities, stage growth, and the executor's retry ``boost``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from dryad_tpu.columnar.batch import ColumnBatch
from dryad_tpu.ops import join as J
from dryad_tpu.ops import segmented as SEG
from dryad_tpu.ops import shuffle as SH
from dryad_tpu.ops import sort as SORT
from dryad_tpu.ops.hash import partition_ids
from dryad_tpu.parallel.mesh import AXIS
from dryad_tpu.plan import xchgplan as XP


def _round8(n: float) -> int:
    return max(8, int(math.ceil(n / 8.0)) * 8)


# (op kind, param name) pairs whose values are RUNTIME OPERANDS under
# ``stringcode_runtime_tables``: the executor keys its compile cache on
# the param's ``operand_signature()`` (shape-palette tier) instead of
# its content, and the arrays arrive through the stage fn's replicated
# input slot at call time (``exec.operands.DeviceOperandPool``).
# Kernels MUST read these params' arrays via ``ctx.operand(...)`` —
# materializing them with np/jnp.asarray inside the traced body would
# silently re-bake the content as compiled constants (the AST lint in
# tests/test_operand_lint.py enforces this in both directions).
OPERAND_PARAMS = frozenset({
    ("string_code", "table"),
    ("group_reduce_dense", "decode"),
})


def stage_operand_objs(stage) -> List[Any]:
    """Operand-protocol objects of a stage's OPERAND-registered params,
    in deterministic (op order, param name) order and deduplicated by
    identity — the ONE enumeration shared by the trace-time binding
    (``build_stage_fn``), the executor's cache key, and the call-time
    operand upload, so the replicated tuple always lines up."""
    from dryad_tpu.exec.operands import is_operand_capable

    objs: List[Any] = []
    seen = set()
    for op in stage.ops:
        for k in sorted(op.params):
            if (op.kind, k) not in OPERAND_PARAMS:
                continue
            v = op.params[k]
            if v is None or not is_operand_capable(v) or id(v) in seen:
                continue
            seen.add(id(v))
            objs.append(v)
    return objs


class StageContext:
    """Mutable trace-time state while composing one stage function."""

    def __init__(self, P: int, slack: float, boost: int,
                 axes: Tuple[str, ...] = (AXIS,),
                 axis_sizes: Tuple[int, ...] = (),
                 window: int = 0):
        self.P = P
        self.axes = axes
        self.axis_sizes = axis_sizes if axis_sizes else (P,)
        self.slack = slack
        self.boost = boost
        # Staged-exchange bucket window (config.exchange_window);
        # 0 = flat all_to_all.
        self.window = window
        # Static per-round exchange byte accounting, appended by
        # _exchange at trace time and surfaced by the executor as
        # exchange_round events (no device readback involved).
        self.xchg_log: List[Dict[str, int]] = []
        # Exchanges this trace skipped because the mesh has one
        # partition (``_elided``); the ``xchg_elided`` stat of
        # the stage's ``dispatch`` spans.
        self.xchg_elided = 0
        # One record a join kernel of what ``_apply_join_strategy``
        # decided at trace time; the executor emits each as a
        # ``join_plan`` event, once a compile.
        self.join_log: List[Dict[str, Any]] = []
        # What the trace's exchanges and join kernels saw, a third
        # output of the stage fn beside the overflow flag: one
        # replicated int32 array each, a column a chip, ``(3, P)`` an
        # exchange (``_observe_exchange``) and ``(1, P)`` a join
        # (``_traced_join``).  ``seen_log`` says of each array, in the
        # same order, what only the trace knows: its ``kind``, and the
        # capacity a chip holds the rows or the pairs in.
        self.seen: List[jax.Array] = []
        self.seen_log: List[Dict[str, Any]] = []
        # ``(slot, valid)`` of the batch a combiner (``COMBINERS``) was
        # handed, while that combiner is the op just run (``apply_op``):
        # the exchange that follows counts its rows as
        # ``combine_rows_in``.
        self.combined: Any = None
        # The stage's ops after the one being traced
        # (``build_stage_fn``): where a ``resize`` finds the kernel
        # that reads its slot next (:func:`_next_reader`).
        self.ahead: Sequence[Any] = ()
        # The kind of the op being traced (``apply_op``): a join's own
        # ``resize``s ask which flavour they are in (:func:`_join_side`).
        self.kind: Optional[str] = None
        # slot -> capacity: the cut a ``resize`` left to the kernel
        # that reads the slot next, taken from that kernel's output
        # (``apply_op``).
        self.cuts: Dict[int, int] = {}
        self.slots: Dict[int, ColumnBatch] = {}
        self.entry_caps: Dict[int, int] = {}
        # id(param object) -> tuple of traced operand arrays (bound
        # from the replicated inputs by build_stage_fn); empty on the
        # legacy baked-constant path
        self.operand_map: Dict[int, Tuple] = {}
        self.overflow = jnp.zeros((), jnp.bool_)
        # Rows whose STRING hash words missed the context dictionary
        # (runtime-fabricated values the dense path would silently
        # drop); surfaced by the executor after the job drains.
        self.dict_miss = jnp.zeros((), jnp.int32)

    def operand(self, obj) -> Any:
        """Traced device arrays for an OPERAND-registered param object,
        or None when the stage runs the legacy baked-constant path."""
        return self.operand_map.get(id(obj))

    def bind_inputs(self, batches: Tuple[ColumnBatch, ...]) -> None:
        for i, b in enumerate(batches):
            self.slots[i] = b
            self.entry_caps[i] = b.capacity

    def base_cap(self, slot: int) -> int:
        return self.entry_caps.get(slot, max(self.entry_caps.values() or [64]))


# The kernels that fold a partition's rows by key: when one is the op
# just before a hash exchange it is that exchange's combiner.
COMBINERS = frozenset({"group_reduce", "group_combine", "distinct"})

# The kernels whose first act is a stable sort of their slot that puts
# valid rows first (``ops/sort.py::sort_carry``: the folds through
# ``_segment_layout``), and whose output has them at the front.
SORTS_VALID_FIRST = COMBINERS | {"local_sort"}

# The kernels of two slots.  Each sorts its RIGHT side by key hash,
# invalid rows last under a sentinel (``ops/join.py::_probe_ranges``);
# its left rows are gathered where they lie (:func:`_join_side`).
JOINS = frozenset({"join", "semi", "group_join_count", "join_ranked"})


def apply_op(ctx: StageContext, kind: str, p: Dict[str, Any]) -> None:
    fn = _KERNELS.get(kind)
    if fn is None:
        raise NotImplementedError(f"no kernel for stage op {kind!r}")
    handed = (
        (p["slot"], ctx.slots[p["slot"]].valid) if kind in COMBINERS
        else None
    )
    ctx.kind = kind
    # names the operator in every device operation's ``tf_op`` path
    with jax.named_scope(f"dryad.{kind}"):
        fn(ctx, p)
        if kind in SORTS_VALID_FIRST and p["slot"] in ctx.cuts:
            # the cut the ``resize`` before left to this kernel
            ctx.slots[p["slot"]] = SH.cut(
                ctx.slots[p["slot"]], ctx.cuts.pop(p["slot"])
            )
    ctx.combined = handed  # what the NEXT op finds: set by a combiner alone


# -- row-wise --------------------------------------------------------------

def _k_select(ctx: StageContext, p) -> None:
    b = ctx.slots[p["slot"]]
    out_cols = p["fn"](dict(b.data))
    ctx.slots[p["slot"]] = ColumnBatch(dict(out_cols), b.valid)


def _k_where(ctx: StageContext, p) -> None:
    b = ctx.slots[p["slot"]]
    ctx.slots[p["slot"]] = b.filter(p["fn"](dict(b.data)))


def _k_project(ctx: StageContext, p) -> None:
    b = ctx.slots[p["slot"]]
    ctx.slots[p["slot"]] = b.select(p["cols"])


def _k_seed(ctx: StageContext, p) -> None:
    b = ctx.slots[p["slot"]]
    new_cols = p["fn"](dict(b.data))
    data = dict(b.data)
    data.update(new_cols)
    ctx.slots[p["slot"]] = ColumnBatch(data, b.valid)


def _k_select_many(ctx: StageContext, p) -> None:
    b = ctx.slots[p["slot"]]
    factor = int(p["factor"])
    n = b.capacity
    out_cols, out_valid = p["fn"](dict(b.data))
    data = {}
    for name, col in out_cols.items():
        if col.shape[:2] != (n, factor):
            raise ValueError(
                f"select_many column {name!r} must be ({n},{factor},...), got {col.shape}"
            )
        data[name] = col.reshape((n * factor,) + col.shape[2:])
    valid = (b.valid[:, None] & out_valid).reshape(n * factor)
    ctx.slots[p["slot"]] = ColumnBatch(data, valid)


def _k_apply(ctx: StageContext, p) -> None:
    b = ctx.slots[p["slot"]]
    if p.get("with_index"):
        out = p["fn"](b, jax.lax.axis_index(ctx.axes))
    else:
        out = p["fn"](b)
    if not isinstance(out, ColumnBatch):
        raise TypeError("apply fn must return a ColumnBatch")
    ctx.slots[p["slot"]] = out


# -- exchanges -------------------------------------------------------------

def _fanout(ctx: StageContext, nparts) -> int:
    """Effective destination count for a fan-reduced exchange (stage-
    level fan-out adaptation, ``DrDynamicRangeDistributor.cpp:54-110``):
    rows concentrate onto the first ``nparts`` partitions; the rest run
    the stage masked-empty.  On hybrid (2-axis) meshes the tree
    exchange ignores nparts, so reduction is disabled there outright —
    a half-applied reduction would inflate the paired resize by P/P_eff
    while the data actually spread full-width."""
    if not nparts or len(ctx.axes) != 1:
        return ctx.P
    return min(int(nparts), ctx.P)


def _elided(ctx: StageContext, exchange: bool) -> bool:
    """The ONE rule for a mesh of one partition (every axis of size 1):
    a repartition is the identity there, so ``exchange_hash``,
    ``exchange_range`` and the ``resize`` paired with each trace NOTHING
    — no splitter sample, no bucket layout, no send buffer, no
    collective, no compaction, no growth of the capacity by the slack.
    The slot's batch goes on as it is (invalid rows where they were,
    which every kernel downstream masks as it does after a ``where``;
    on more partitions a ``resize`` leaves them so only for a kernel
    that sorts them away itself, :func:`_reader_sorts`),
    ``ctx.overflow`` untouched, and no ``exchange_round`` is accounted.
    Decided here, from the partition count the program is traced for,
    and nowhere else: ``lower()`` emits the same stage ops at every
    width (four of its callers do not know P).  ``exchange`` says
    whether the caller is an exchange, counted into
    ``ctx.xchg_elided``, or the ``resize`` that follows one."""
    if ctx.P != 1:
        return False
    ctx.xchg_elided += int(exchange)
    return True


def _exchange(
    ctx: StageContext, b: ColumnBatch, dest, P: int, B: int, axes
) -> Tuple[ColumnBatch, jax.Array]:
    """Route one repartition through the flat or staged exchange.

    ``ctx.window >= 1`` lowers the all-to-all into the planner's
    ppermute schedule (``plan.xchgplan``), bounding peak extra HBM at
    O(window * B) per device; 0 keeps the flat single-collective path.
    Either way the round-by-round byte accounting — a trace-time
    constant — lands on ``ctx.xchg_log`` for the executor to emit as
    ``exchange_round`` events.
    """
    if len(axes) == 2:
        dcn = ctx.axis_sizes[0]
    elif len(ctx.axes) == 2 and axes[0] == ctx.axes[0]:
        dcn = P  # exchange over the DCN axis alone: every hop crosses
    else:
        dcn = 1
    per_row = SH.row_bytes(b)
    if ctx.window < 1 or P == 1:
        ctx.xchg_log.append(XP.flat_accounting(P, dcn, B, per_row))
        return SH.exchange(b, dest, P, B, axes)
    schedule = XP.plan_exchange(P, ctx.window, dcn)
    ctx.xchg_log.extend(schedule.accounting(B, per_row))
    return SH.exchange_staged(b, dest, P, B, axes, schedule)


def _a_column_a_chip(ctx: StageContext, mine: jax.Array) -> jax.Array:
    """This chip's counts ``mine`` in its own column of a ``(len(mine),
    P)`` array, replicated by one ``psum``.  Kept a chip so that no sum
    is formed in int32 on the device."""
    me = jax.lax.axis_index(ctx.axes)
    at = (jnp.arange(ctx.P, dtype=jnp.int32) == me).astype(jnp.int32)
    return jax.lax.psum(mine.astype(jnp.int32)[:, None] * at[None, :], ctx.axes)


def _observe_exchange(ctx: StageContext, slot: int, sent: ColumnBatch) -> None:
    """What an exchange knows and the host does not: the rows this
    chip's combiner was handed (those it sent, where no combiner came
    just before), the rows it sent, the rows it received, a column a
    chip (:func:`_a_column_a_chip`); the array leaves the program
    beside the overflow flag and rides that flag's readback
    (``GraphExecutor._exchange_observed``).  The capacity a chip holds
    the received rows in is the ``resize``'s to say
    (:func:`_do_resize`)."""
    rows_out = sent.count()
    fed = ctx.combined
    rows_in = (
        jnp.sum(fed[1].astype(jnp.int32))
        if fed is not None and fed[0] == slot else rows_out
    )
    mine = jnp.stack([rows_in, rows_out, ctx.slots[slot].count()])
    ctx.seen.append(_a_column_a_chip(ctx, mine))
    ctx.seen_log.append(dict(kind="exchange", slot=slot, capacity=None))


def _do_exchange_hash(
    ctx: StageContext, slot: int, keys, tree=None, nparts=None
) -> None:
    if _elided(ctx, exchange=True):
        return
    b = ctx.slots[slot]
    if tree is not None and len(ctx.axes) == 2:
        _tree_exchange_hash(ctx, slot, keys, tree)
    else:
        P_eff = _fanout(ctx, nparts)
        dest = partition_ids([b.data[k] for k in keys], P_eff)
        B = SH.bucket_capacity(b.capacity, P_eff, ctx.slack * ctx.boost)
        out, ovf = _exchange(ctx, b, dest, ctx.P, B, ctx.axes)
        ctx.slots[slot] = out
        ctx.overflow = ctx.overflow | ovf
    _observe_exchange(ctx, slot, b)


def _tree_exchange_hash(ctx: StageContext, slot: int, keys, tree) -> None:
    """Hierarchical shuffle on a hybrid mesh: ICI hop -> per-slice
    combine -> DCN hop.

    The reference's machine→pod→overall aggregation tree
    (``DrDynamicAggregateManager.h:35-168``) in collective form: rows
    for global partition g first travel over ICI to local device
    g %% P_ici within their slice, duplicate keys are combined there,
    and only the per-slice partials cross DCN to slice g // P_ici —
    cutting DCN bytes by the per-slice duplication factor.  The final
    combine after the DCN hop is the stage's own downstream op.
    """
    D, P_in = ctx.axis_sizes[0], ctx.axis_sizes[1]
    slack = ctx.slack * ctx.boost

    def dest_global(batch):
        return partition_ids([batch.data[k] for k in keys], ctx.P)

    # Hop 1: within-slice exchange over ICI to local index g %% P_ici.
    b = ctx.slots[slot]
    B1 = SH.bucket_capacity(b.capacity, P_in, slack)
    out, ovf = _exchange(
        ctx, b, dest_global(b) % P_in, P_in, B1, (ctx.axes[1],)
    )
    ctx.overflow = ctx.overflow | ovf
    # the combine below sorts valid rows first itself (``SH.resize``)
    per_slice = _round8(b.capacity * ctx.slack)
    out, ovf = SH.resize(out, per_slice, reader_sorts=True)
    ctx.overflow = ctx.overflow | ovf

    # Per-slice combine (RecursiveAccumulate analog; idempotent specs).
    if tree.get("distinct"):
        out = SEG.distinct(out, tree["keys"])
    elif "merge" in tree:
        out = SEG.group_combine(
            out, tree["keys"], tree["state_cols"], tree["merge"]
        )
    else:
        out = SEG.group_reduce(out, tree["keys"], tree["aggs"])
    out = SH.cut(out, per_slice)

    # Hop 2: cross-slice exchange over DCN to slice g // P_ici.
    B2 = SH.bucket_capacity(out.capacity, D, slack)
    out2, ovf = _exchange(
        ctx, out, dest_global(out) // P_in, D, B2, (ctx.axes[0],)
    )
    ctx.overflow = ctx.overflow | ovf
    ctx.slots[slot] = out2


def _slots_of(p: Dict[str, Any]) -> Tuple[int, ...]:
    """The slots a stage op's params name."""
    named = [p.get(k) for k in ("slot", "left_slot", "right_slot", "out_slot")]
    named += [*p.get("slots", ()), *p.get("out_slots", ())]
    return tuple(s for s in named if s is not None)


def _next_reader(ctx: StageContext, slot: int) -> Optional[str]:
    """The kernel that reads ``slot`` next: the kind of the first op
    ahead in the stage (the fused region's member) that names it,
    ``join.left`` / ``join.right`` for the sides of a kernel of two
    slots; None where the slot leaves the stage as it is."""
    for op in ctx.ahead:
        if slot not in _slots_of(op.params):
            continue
        if op.kind in JOINS:
            return _join_side(op.kind, op.params, slot)
        return op.kind
    return None


def _join_side(kind: str, p: Dict[str, Any], slot: int) -> str:
    """What a join of ``kind`` is to ``slot`` as a reader: ``join.right``
    (sorted by the probe), ``join.left`` where the left rows leave only
    through the gathers over the pair slots (an inner ``join``, a
    ``join_ranked``), else ``kind`` itself: a ``semi``, a
    ``group_join_count`` and an outer ``join`` hand the left batch on
    slot for slot, holes and all."""
    if p["right_slot"] == slot:
        return "join.right"
    gathered = kind == "join_ranked" or (kind == "join" and not p.get("outer"))
    return "join.left" if gathered else kind


def _reader_sorts(reader: Optional[str], target: int, capacity: int) -> bool:
    """The ONE rule for the ``resize`` after an exchange on more than
    one partition: whether a compaction here would be lost on the kernel
    that reads the slot next.  It is where that kernel sorts valid rows
    first itself (``SORTS_VALID_FIRST``), whatever the target: a cut is
    then taken from its output (``SH.resize``).  A join sorts its right
    side so too (``JOINS``), and the left rows of one that puts out its
    pair buffer alone (:func:`_join_side`) are read by a gather whose
    indices ascend wherever the rows lie: without the left's compaction
    a ``join-hash-4c`` job was 0.207 s shorter than with it, more than
    the sort's own 0.147 s (``PERF.md`` section 6, PR 48).  But a join
    puts out nothing of either table that could be cut afterwards, and
    sizes its pair buffer from the capacities it is handed
    (:func:`_apply_join_strategy`): it reads the slots as they are only
    where none has to go.  Anything else - a row-wise kernel, another
    exchange, a join that hands its left batch on, nothing at all (a
    ``hash_partition`` that is read back) - takes the rows compacted:
    holes cost at egress what the sort saves here.  Decided from what
    the trace can see and nowhere else; ``lower()`` emits the same ops
    whoever reads."""
    if reader in SORTS_VALID_FIRST:
        return True
    return reader in ("join.left", "join.right") and target >= capacity


def _do_resize(
    ctx: StageContext, slot: int, factor: float, nparts=None,
    reader: Optional[str] = None,
) -> None:
    """The capacity after an exchange (every ``resize`` stage op follows
    one, ``plan/lower.py``): entry capacity x growth x boost x slack,
    the overflow flag a count of the valid rows against it, and a
    compaction only where ``reader``, the kernel that reads the slot
    next (looked up in the stage where the caller does not say), would
    not sort them itself (:func:`_reader_sorts`).  On one partition the
    exchange before it moved nothing, so there is nothing to count or
    to make room for."""
    if _elided(ctx, exchange=False):
        return
    b = ctx.slots[slot]
    # A fan-reduced exchange concentrates ~P/P_eff partitions' rows
    # onto each live partition; scale the post-shuffle capacity so the
    # concentration itself never trips the overflow retry.
    conc = ctx.P / _fanout(ctx, nparts)
    target = _round8(
        ctx.base_cap(slot) * factor * conc * ctx.boost * ctx.slack
    )
    skips = _reader_sorts(
        reader or _next_reader(ctx, slot), target, b.capacity
    )
    out, ovf = SH.resize(b, target, reader_sorts=skips)
    if out.capacity > target:
        ctx.cuts[slot] = target  # from the reader's output (``apply_op``)
    ctx.slots[slot] = out
    if target < b.capacity:  # else false as traced: nothing to OR in
        ctx.overflow = ctx.overflow | ovf
    for said in reversed(ctx.seen_log):  # the exchange this one follows
        if said.get("slot") == slot:
            said["capacity"] = target
            said["resize_sorts"] = int(not skips)
            break


# Op kinds whose kernels never set the overflow flag: a stage composed
# only of these has a statically-False overflow, so the driver skips the
# host sync on it and lets JAX async dispatch pipeline it with
# independent stages (the message-pump overlap of the reference GM,
# DrMessagePump.h:116-180, recovered through XLA's async runtime).
NON_OVERFLOW_OPS = frozenset({
    "select", "where", "project", "select_many", "apply", "fork",
    "group_reduce", "group_combine", "group_reduce_dense", "distinct",
    "local_sort", "concat", "scalar_agg", "topk", "string_code",
})


def _k_exchange_hash(ctx: StageContext, p) -> None:
    _do_exchange_hash(
        ctx, p["slot"], p["keys"], p.get("tree"), p.get("nparts")
    )


def _k_exchange_range(ctx: StageContext, p) -> None:
    """Repartition by range: elect splitters from a sample of every
    shard, send each row to the partition whose range holds it.  On a
    mesh of one partition it emits nothing (``_elided``), and an
    ``order_by`` is its ``local_sort`` alone."""
    if _elided(ctx, exchange=True):
        return
    b = ctx.slots[p["slot"]]
    operands = p["operands_fn"](b)
    # Splitter sample count = sample_rate fraction of the partition
    # (reference 0.1% sampler, DryadLinqSampler.cs:38-42), clamped to
    # [16, 512] so tiny partitions still elect meaningful splitters and
    # huge ones bound the all_gather.  An overflow retry REFINES the
    # election alongside the capacity boost — rate and clamp scale with
    # ctx.boost, so a retry caused by unlucky splitters (a dense value
    # cluster the small sample missed) converges by better splitters,
    # not just by doubling every partition's memory (the data-size
    # recomputation of DrDynamicRangeDistributor.cpp:54-110).
    rate = float(p.get("rate", 0.001)) * ctx.boost
    m = int(min(512 * ctx.boost, max(16 * ctx.boost, b.capacity * rate)))
    P_eff = _fanout(ctx, p.get("nparts"))
    if p.get("spread"):
        # Skew-proof variant for pure ordering (order_by): splitters
        # elected over ALL sort operands plus a uniform synthetic
        # tiebreak, so a heavy key's run is cut across partitions in
        # sampled proportions instead of pinning one partition and
        # boost-doubling everybody (automatic analog of
        # DrDynamicDistributor.h:26,79).  Global order still holds —
        # partition boundaries respect the extended lexicographic key.
        # Not used for range_partition, which promises key colocation.
        words = [o.astype(jnp.uint32) for o in operands]
        words.append(SORT.spread_word(b.capacity))
        splitters = SORT.sample_splitters_multi(
            words, b.valid, P_eff, m, ctx.axes
        )
        dest = SORT.range_dest_multi(words, splitters)
    else:
        splitters = SORT.sample_splitters(
            operands[0], b.valid, P_eff, m, ctx.axes
        )
        dest = SORT.range_dest(operands[0], splitters)
    B = SH.bucket_capacity(b.capacity, P_eff, ctx.slack * ctx.boost)
    out, ovf = _exchange(ctx, b, dest, ctx.P, B, ctx.axes)
    ctx.slots[p["slot"]] = out
    ctx.overflow = ctx.overflow | ovf
    _observe_exchange(ctx, p["slot"], b)


def _k_resize(ctx: StageContext, p) -> None:
    # Post-shuffle capacity: entry capacity x pipeline growth x retry
    # boost x slack (hash placement has variance, so the uniform
    # expectation alone overflows regularly).
    _do_resize(ctx, p["slot"], p["factor"], p.get("nparts"))


# -- grouping / sorting ----------------------------------------------------

def _k_group_reduce(ctx: StageContext, p) -> None:
    b = ctx.slots[p["slot"]]
    ctx.slots[p["slot"]] = SEG.group_reduce(b, p["keys"], p["aggs"])


def _k_group_combine(ctx: StageContext, p) -> None:
    b = ctx.slots[p["slot"]]
    ctx.slots[p["slot"]] = SEG.group_combine(
        b, p["keys"], p["state_cols"], p["merge"]
    )


# Rows one bucket-kernel call may fold: its counts accumulate in f32 on
# the MXU, exact only below 2^24 per bucket.  Larger partitions fold in
# static blocks of this many rows (tests patch it down).
_DENSE_BLOCK_ROWS = 1 << 24


def _bucket_fold(key, vals, in_range, num_buckets: int):
    """``bucket_sum_count`` over a partition of ANY capacity: static
    blocks of at most ``_DENSE_BLOCK_ROWS`` rows, each block's f32
    counts rounded to int32 and added as integers (so counts stay exact
    at every row count), f32 sums added block by block.  Returns
    ``(sums, int32 counts)``."""
    from dryad_tpu.ops.pallas_bucket import bucket_sum_count

    n = key.shape[0]
    cnt = None
    sums: List[jax.Array] = []
    for lo in range(0, max(n, 1), _DENSE_BLOCK_ROWS):
        hi = min(n, lo + _DENSE_BLOCK_ROWS)
        with jax.named_scope("dryad.pallas_bucket"):
            bs, bc = bucket_sum_count(
                key[lo:hi], [v[lo:hi] for v in vals], in_range[lo:hi],
                num_buckets,
            )
        bc = jnp.round(bc).astype(jnp.int32)
        cnt = bc if cnt is None else cnt + bc
        sums = bs if not sums else [a + b for a, b in zip(sums, bs)]
    return sums, cnt


def _k_group_reduce_dense(ctx: StageContext, p) -> None:
    """Dense-key GroupBy: per-partition MXU bucket reduce (Pallas on
    TPU, ``ops/pallas_bucket.py``) + one ``psum_scatter`` over the mesh.

    Output partition i holds buckets [i*per, (i+1)*per); rows for keys
    outside [0, K) are dropped (API contract).  Per-partition counts
    accumulate in f32 on the MXU, exact below 2^24 rows per kernel call
    — ``_bucket_fold`` keeps every call under that and adds the blocks
    as int32 — and cross the mesh as int32, so the global count is
    exact at any row count the int32 guard below admits.  SUM columns
    accumulate in f32 end-to-end: integer sums silently lose exactness
    once a per-bucket total exceeds 2^24 (documented at the API,
    query.py ``dense=``); the sort-based path is the exact alternative.
    """
    b = ctx.slots[p["slot"]]
    if ctx.P * b.capacity > 0x7FFFFFFF:
        raise ValueError(
            f"dense group_by: global capacity {ctx.P * b.capacity} exceeds "
            "the int32 count range; use the sort-based group_by path"
        )
    K = int(p["num_buckets"])
    per = max(1, -(-K // ctx.P))  # ceil
    Kp = per * ctx.P
    key = b.data[p["key"]]
    in_range = b.valid & (key >= 0) & (key < K)
    if p.get("guard"):
        # Int auto-dense rewrite: the [0, K) bound came from INGEST
        # statistics, so out-of-range keys mean post-ingest fabrication
        # — count them for the executor's deferred loud failure instead
        # of silently dropping (explicit dense=K keeps its documented
        # drop semantics).
        ctx.dict_miss = ctx.dict_miss + jnp.sum(
            (b.valid & ~in_range).astype(jnp.int32)
        )

    # Distinct value columns needed by sum/mean aggs.
    val_cols: List[str] = []
    for a in p["aggs"]:
        if a.op in ("sum", "mean") and a.col not in val_cols:
            val_cols.append(a.col)
    sums, cnt = _bucket_fold(
        key, [b.data[c] for c in val_cols], in_range, Kp
    )
    by_col = dict(zip(val_cols, sums))

    scat = lambda x: jax.lax.psum_scatter(
        x, ctx.axes, scatter_dimension=0, tiled=True
    )
    # Counts cross the mesh as int32: each per-partition partial is
    # exact (_bucket_fold), and integer reduce-scatter keeps the global
    # total exact past 2^24.
    cnt = scat(cnt)
    by_col = {c: scat(s) for c, s in by_col.items()}

    me = jax.lax.axis_index(ctx.axes)
    codes = me * per + jnp.arange(per, dtype=jnp.int32)
    decode = p.get("decode")
    if decode is None:
        out: Dict[str, jax.Array] = {p["key"]: codes.astype(key.dtype)}
    else:
        # auto-dense STRING key: gather this partition's code range from
        # the dictionary decode table to reconstruct the physical
        # (#h0, #h1, #r0, #r1) words (ops/stringcode.py); the table
        # arrives as a runtime operand when registered, else baked
        words = decode.slice_rows(
            me * per, per, operands=ctx.operand(decode)
        )  # (per, 4) uint32
        okey = p["out_key"]
        out = {
            f"{okey}#{w}": words[:, i]
            for i, w in enumerate(("h0", "h1", "r0", "r1"))
        }
    for a in p["aggs"]:
        if a.op == "count":
            out[a.out] = cnt
        elif a.op == "sum":
            s = by_col[a.col]
            dt = b.data[a.col].dtype
            out[a.out] = (
                jnp.round(s).astype(dt) if jnp.issubdtype(dt, jnp.integer)
                else s.astype(dt)
            )
        elif a.op == "mean":
            out[a.out] = by_col[a.col] / jnp.maximum(cnt, 1).astype(
                jnp.float32
            )
        else:  # guarded at the API layer
            raise ValueError(f"dense group_by cannot compute {a.op!r}")
    valid = (cnt > 0) & (codes < K)
    ctx.slots[p["slot"]] = ColumnBatch(out, valid)


def _k_string_code(ctx: StageContext, p) -> None:
    """Map a STRING column's Hash64 words to dense dictionary codes
    (``ops/stringcode.py``) — the bridge that lets a plain group_by
    over strings ride the MXU dense path.  Misses map to the padded
    code domain, which the dense kernel's range mask drops."""
    b = ctx.slots[p["slot"]]
    table = p["table"]
    rt = ctx.operand(table)  # runtime-operand arrays, or None = baked
    codes = table.lookup(b.data[p["h0"]], b.data[p["h1"]], operands=rt)
    # Out-of-dictionary rows (miss -> num_codes_padded) would be
    # silently dropped by the dense kernel's range mask; count them so
    # the executor can surface the loss instead (deferred readback, no
    # sync on the dense fast path).  The threshold is the TIER bound on
    # the operand path — num_codes itself would re-bake a per-widen
    # trace constant; nothing occupies [num_codes, padded), so the two
    # thresholds count identically.
    bound = table.num_codes_padded if rt is not None else table.num_codes
    miss = jnp.sum(
        (b.valid & (codes >= jnp.int32(bound))).astype(jnp.int32)
    )
    ctx.dict_miss = ctx.dict_miss + miss
    ctx.slots[p["slot"]] = ColumnBatch(
        {**b.data, p["out"]: codes}, b.valid
    )


def _k_distinct(ctx: StageContext, p) -> None:
    b = ctx.slots[p["slot"]]
    ctx.slots[p["slot"]] = SEG.distinct(b, p["keys"])


def _k_topk(ctx: StageContext, p) -> None:
    """Fused OrderBy+Take(n): per-partition local top-n, one
    ``all_gather`` of the P heads, final local sort — no full range
    exchange, no full-data shuffle (the SimpleRewriter-style plan
    rewrite, ``LinqToDryad/SimpleRewriter.cs``; classic distributed
    top-k).  Output is partition-major globally sorted with exactly n
    valid rows; per-partition capacity shrinks to the padded head size.
    Tie rows beyond position n are dropped in post-sort order (the
    engine's order_by+take makes the same unstable tie choice after a
    shuffle)."""
    b = ctx.slots[p["slot"]]
    operands = p["operands_fn"](b)
    sb = SORT.sort_batch_by_operands(b, operands)  # local sort; valid rows first
    n = int(p["n"])
    # head size never exceeds the partition capacity: slicing past the
    # array would clamp and the gather arithmetic below would duplicate
    # the tail partition's rows
    n_pad = min(b.capacity, max(8, _round8(n)))
    head = SH.cut(sb, n_pad)
    gb = _gather_all(head, ctx.axes)  # every partition: all P heads
    # identical globally-sorted array everywhere
    gsb = SORT.sort_batch_by_operands(gb, p["operands_fn"](gb))
    me = jax.lax.axis_index(ctx.axes)
    start = me * n_pad
    pos = start + jnp.arange(n_pad, dtype=jnp.int32)
    data = {
        c: jax.lax.dynamic_slice_in_dim(v, start, n_pad)
        for c, v in gsb.data.items()
    }
    valid = (
        jax.lax.dynamic_slice_in_dim(gsb.valid, start, n_pad)
        & (pos < jnp.int32(n))
    )
    ctx.slots[p["slot"]] = ColumnBatch(data, valid)


def _k_local_sort(ctx: StageContext, p) -> None:
    b = ctx.slots[p["slot"]]
    ctx.slots[p["slot"]] = SORT.sort_batch_by_operands(b, p["operands_fn"](b))


# -- multi-input -----------------------------------------------------------

def _gather_all(b: ColumnBatch, axes: Tuple[str, ...]) -> ColumnBatch:
    """Replicate a batch to every partition (the broadcast copy-tree of
    ``DrDynamicBroadcast.h:23`` as one ``all_gather`` over ICI)."""
    data = {
        n: jax.lax.all_gather(c, axes, tiled=True) for n, c in b.data.items()
    }
    return ColumnBatch(data, jax.lax.all_gather(b.valid, axes, tiled=True))


def _join_strategy(ctx: StageContext, p, right: ColumnBatch) -> bool:
    """True -> broadcast the right side; False -> co-hash-partition.

    The analog of the reference's dynamic broadcast decision
    (``DynamicManager.cs:51``, which reads actual data size): when the
    plan carries a static ROW-count bound for the right side
    (take(n) heads, aggregates, dense domains — lower.py's estimator),
    that bound decides; otherwise fall back to the capacity heuristic.
    Both are trace-time static, so the choice is baked per compiled
    shape and cached."""
    strategy = p.get("strategy", "shuffle")
    if strategy == "broadcast":
        return True
    if strategy == "auto":
        limit = p.get("broadcast_limit", 1 << 16)
        est = p.get("est_right")
        if est is not None:
            # global row bound: a mostly-empty right batch with large
            # CAPACITY still broadcasts when its rows are bounded small
            return est <= limit
        return right.capacity * ctx.P <= limit
    return False


@jax.named_scope("dryad.join.copartition")
def _co_partition_for_join(ctx: StageContext, p) -> None:
    """Hash-exchange whichever sides the plan says are not already
    partitioned on the join keys (deferred from lowering when the
    strategy decision is trace-time).  Under a scope of its own: the
    exchanges' ``exchange.layout``, ``exchange.collective`` and
    ``resize`` nest there, apart from the join proper."""
    for side in ("left", "right"):
        if p.get(f"need_{side}_exchange"):
            _do_exchange_hash(ctx, p[f"{side}_slot"], p[f"{side}_keys"])
            with jax.named_scope("dryad.resize"):  # as the stage op's is named
                _do_resize(
                    ctx, p[f"{side}_slot"], 1.0,
                    reader=_join_side(ctx.kind, p, p[f"{side}_slot"]),
                )


def _apply_join_strategy(ctx: StageContext, p) -> int:
    """Run the chosen placement (broadcast the right side, or the
    deferred co-partition exchanges) and return the capacity of the
    candidate-pair buffer: ``expansion`` x ``boost`` x the larger side's
    capacity.  That base uses PRE-broadcast sizes: replicating the
    right side multiplies its capacity by P but not the match count.
    What was decided goes on ``ctx.join_log``; :func:`_traced_join`
    adds what the join kernel then traced."""
    base = max(
        ctx.slots[p["left_slot"]].capacity, ctx.slots[p["right_slot"]].capacity
    )
    broadcast = False
    if "strategy" in p:
        broadcast = _join_strategy(ctx, p, ctx.slots[p["right_slot"]])
        if broadcast:
            right = ctx.slots[p["right_slot"]]
            est = p.get("est_right")
            if est is not None:
                # An est-bound broadcast must not gather the FULL
                # capacity (P x cap could dwarf broadcast_limit):
                # shrink each partition to the global row bound first —
                # per-partition valid <= global valid <= est, so this
                # cannot overflow.
                tight = _round8(min(right.capacity, max(8, int(est))))
                if tight < right.capacity:
                    right, ovf = SH.resize(right, tight)
                    ctx.overflow = ctx.overflow | ovf
                    ctx.slots[p["right_slot"]] = right
            ctx.slots[p["right_slot"]] = _gather_all(right, ctx.axes)
        else:
            _co_partition_for_join(ctx, p)
            base = max(
                ctx.slots[p["left_slot"]].capacity,
                ctx.slots[p["right_slot"]].capacity,
            )
    out_cap = _round8(base * p["expansion"] * ctx.boost)
    ctx.join_log.append(dict(
        strategy="broadcast" if broadcast else "shuffle",
        est_right=p.get("est_right"),
        broadcast_limit=p.get("broadcast_limit"),
        out_capacity=out_cap,
        # as the join kernel receives them, after the placement
        left_capacity=ctx.slots[p["left_slot"]].capacity,
        right_capacity=ctx.slots[p["right_slot"]].capacity,
    ))
    return out_cap


def _traced_join(ctx: StageContext, join, *args, **kwargs):
    """Trace one ``ops/join.py`` flavour and put what it gathered over
    its pair slots (``slot_gathers``, ``stacked_words``) on the record
    :func:`_apply_join_strategy` just opened for it.  The candidate
    pairs in this chip's pair buffer leave the program as the
    exchanges' counts do (:func:`_observe_exchange`): a column a chip,
    on the overflow flag's readback."""
    with J.slot_gather_log() as seen:
        out = join(*args, **kwargs)
        pairs = J.take_pairs()
    with jax.named_scope("dryad.join.observe"):
        ctx.seen.append(_a_column_a_chip(ctx, pairs[None]))
    ctx.seen_log.append(
        dict(kind="join", capacity=ctx.join_log[-1]["out_capacity"])
    )
    ctx.join_log[-1].update(seen)
    return out


def _k_join(ctx: StageContext, p) -> None:
    out_cap = _apply_join_strategy(ctx, p)
    left = ctx.slots[p["left_slot"]]
    right = ctx.slots[p["right_slot"]]
    if p.get("outer"):
        out, ovf = _traced_join(
            ctx, J.hash_join_outer,
            left, right, p["left_keys"], p["right_keys"], out_cap,
            p.get("right_defaults") or {}, p.get("suffix", "_r"),
        )
    else:
        out, ovf = _traced_join(
            ctx, J.hash_join,
            left, right, p["left_keys"], p["right_keys"], out_cap,
            p.get("suffix", "_r"),
        )
    ctx.slots[p["left_slot"]] = out
    ctx.overflow = ctx.overflow | ovf


def _k_semi(ctx: StageContext, p) -> None:
    cap = _apply_join_strategy(ctx, p)
    left = ctx.slots[p["left_slot"]]
    right = ctx.slots[p["right_slot"]]
    mask, ovf = _traced_join(
        ctx, J.exists_mask, left, right, p["left_keys"], p["right_keys"], cap
    )
    if p.get("negate"):
        mask = ~mask
    ctx.slots[p["left_slot"]] = left.filter(mask)
    ctx.overflow = ctx.overflow | ovf


def _k_concat(ctx: StageContext, p) -> None:
    batches = [ctx.slots[s] for s in p["slots"]]
    names = set(batches[0].columns)
    aligned = [b.select(sorted(names)) for b in batches]
    ctx.slots[p["out_slot"]] = ColumnBatch.concatenate(aligned)


def _k_group_join_count(ctx: StageContext, p) -> None:
    cap = _apply_join_strategy(ctx, p)
    left = ctx.slots[p["left_slot"]]
    right = ctx.slots[p["right_slot"]]
    counts, ovf = _traced_join(
        ctx, J.group_join_counts,
        left, right, p["left_keys"], p["right_keys"], cap,
    )
    ctx.slots[p["left_slot"]] = left.with_column(p["out"], counts)
    ctx.overflow = ctx.overflow | ovf


def _k_join_ranked(ctx: StageContext, p) -> None:
    """Inner join emitting a group-local match rank (full GroupJoin's
    enumerable group, reference ``DryadLinqQueryable.cs`` GroupJoin
    result-selector overloads)."""
    out_cap = _apply_join_strategy(ctx, p)
    left = ctx.slots[p["left_slot"]]
    right = ctx.slots[p["right_slot"]]
    operands_fn = p.get("operands_fn")
    operands = operands_fn(right) if operands_fn is not None else ()
    out, ovf = _traced_join(
        ctx, J.hash_join_ranked,
        left, right, p["left_keys"], p["right_keys"], out_cap,
        p.get("suffix", "_r"), p["rank_out"], operands,
        rank_limit=p.get("rank_limit"), boost=ctx.boost,
        # At the retry ladder's last rung the window clamp drops away,
        # so a hash-collision-into-a-hot-run row degrades to the
        # unclamped expansion instead of failing the job.
        final_attempt=ctx.boost >= p.get("rank_limit_max_boost", 1 << 30),
    )
    ctx.slots[p["left_slot"]] = out
    ctx.overflow = ctx.overflow | ovf


def _rank_column(b: ColumnBatch, P: int, axes: Tuple[str, ...]) -> Tuple[ColumnBatch, jax.Array]:
    """Compact and attach each valid row's global rank (partition-major)."""
    c = b.compact()
    # Ranks are uint32 with 0xFFFFFFFF as the invalid sentinel; the max
    # possible rank is the static global capacity, so guard at trace
    # time rather than silently wrapping past 4.29B rows.
    if P * c.capacity >= 0xFFFFFFFF:
        raise ValueError(
            f"rank-based operator: global capacity {P * c.capacity} "
            "exceeds the uint32 rank range (4.29e9 rows)"
        )
    local = jnp.sum(c.valid.astype(jnp.int32))
    counts = jax.lax.all_gather(local, axes)
    me = jax.lax.axis_index(axes)
    offset = jnp.sum(jnp.where(jnp.arange(P) < me, counts, 0))
    rank = (offset + jnp.arange(c.capacity, dtype=jnp.int32)).astype(jnp.uint32)
    rank = jnp.where(c.valid, rank, jnp.uint32(0xFFFFFFFF))
    total = jax.lax.psum(local, axes)
    return ColumnBatch(dict(c.data, **{"#rank": rank}), c.valid), total


def _exchange_by_rank(
    ctx: StageContext, b: ColumnBatch, per: int
) -> ColumnBatch:
    """Repartition rows so global rank r lands at partition r // per,
    locally sorted by rank (position i holds rank pid*per + i)."""
    rank = b.data["#rank"].astype(jnp.int32)
    dest = jnp.clip(rank // per, 0, ctx.P - 1)
    B = SH.bucket_capacity(b.capacity, ctx.P, ctx.slack * ctx.boost)
    out, ovf = _exchange(ctx, b, dest, ctx.P, B, ctx.axes)
    ctx.overflow = ctx.overflow | ovf
    # the sort on ``#rank`` puts valid rows first itself (``SH.resize``)
    out, ovf2 = SH.resize(out, per, reader_sorts=True)
    ctx.overflow = ctx.overflow | ovf2
    return SH.cut(SORT.sort_batch_by_operands(out, [out.data["#rank"]]), per)


def _k_zip(ctx: StageContext, p) -> None:
    """Pair rows by global position (LINQ Zip: truncate to shorter)."""
    left = ctx.slots[p["left_slot"]]
    right = ctx.slots[p["right_slot"]]
    per = _round8(max(ctx.base_cap(p["left_slot"]), ctx.base_cap(p["right_slot"])) * ctx.boost)
    lb, _lt = _rank_column(left, ctx.P, ctx.axes)
    rb, _rt = _rank_column(right, ctx.P, ctx.axes)
    la = _exchange_by_rank(ctx, lb, per)
    ra = _exchange_by_rank(ctx, rb, per)
    data: Dict[str, jax.Array] = {
        n: c for n, c in la.data.items() if n != "#rank"
    }
    for n, c in ra.data.items():
        if n == "#rank":
            continue
        data[J._suffixed(n, p["suffix"]) if n in data else n] = c
    valid = la.valid & ra.valid
    ctx.slots[p["left_slot"]] = ColumnBatch(data, valid)


def _k_sliding_window(ctx: StageContext, p) -> None:
    """Windows over the global row sequence with a cross-partition halo.

    Ring pass (the sequence-parallel halo-exchange pattern): each
    partition's (size-1)-row prefix rotates backward one step per hop
    for P-1 hops, so every partition observes the prefixes of ALL its
    successors — windows may span any number of (possibly empty)
    partitions.  Arrived rows are compacted valid-first in arrival
    order (= global row order) and the first size-1 fill the halo."""
    b = ctx.slots[p["slot"]].compact()
    w = int(p["size"])
    cap = b.capacity
    n_loc = jnp.sum(b.valid.astype(jnp.int32))

    need = w - 1
    halo_v = None
    halo_cols: Dict[str, jax.Array] = {}
    if need > 0 and ctx.P > 1:
        perm = [(i, i - 1) for i in range(1, ctx.P)]  # no wrap: sequence ends
        work_v = b.valid[:need]
        work_cols = {c: b.data[c][:need] for c in p["cols"]}
        arrived_v: List[jax.Array] = []
        arrived_cols: Dict[str, List[jax.Array]] = {c: [] for c in p["cols"]}
        for _hop in range(ctx.P - 1):
            work_v = jax.lax.ppermute(work_v, ctx.axes, perm)
            work_cols = {
                c: jax.lax.ppermute(col, ctx.axes, perm)
                for c, col in work_cols.items()
            }
            arrived_v.append(work_v)
            for c in p["cols"]:
                arrived_cols[c].append(work_cols[c])
        all_v = jnp.concatenate(arrived_v)
        # Stable sort by invalid flag keeps arrival (= global row) order
        # among valid rows; take the first `need` as the halo.
        operands = [all_v.astype(jnp.uint32) ^ jnp.uint32(1)] + [
            jnp.concatenate(arrived_cols[c]) for c in p["cols"]
        ] + [all_v]
        sorted_ops = jax.lax.sort(
            tuple(operands), num_keys=1, is_stable=True
        )
        halo_v = sorted_ops[-1][:need]
        for i, c in enumerate(p["cols"]):
            halo_cols[c] = sorted_ops[1 + i][:need]

    ext_len = cap + max(need, 0)
    out_cols: Dict[str, jax.Array] = {}
    ext_v = jnp.zeros((ext_len,), jnp.bool_)
    ext_v = jax.lax.dynamic_update_slice(ext_v, b.valid, (0,))
    if halo_v is not None:
        ext_v = jax.lax.dynamic_update_slice(ext_v, halo_v, (n_loc,))
    win_valid = jnp.ones((cap,), jnp.bool_)
    for j in range(w):
        win_valid = win_valid & ext_v[j : j + cap]

    for c in p["cols"]:
        col = b.data[c]
        ext = jnp.zeros((ext_len,), col.dtype)
        ext = jax.lax.dynamic_update_slice(ext, col, (0,))
        if halo_v is not None:
            ext = jax.lax.dynamic_update_slice(ext, halo_cols[c], (n_loc,))
        for j in range(w):
            out_cols[f"{c}_w{j}"] = ext[j : j + cap]

    ctx.slots[p["slot"]] = ColumnBatch(out_cols, win_valid)


# -- global ops ------------------------------------------------------------

def _strip_rank(b: ColumnBatch, keep: jax.Array) -> ColumnBatch:
    return ColumnBatch(
        {n: c for n, c in b.data.items() if n != "#rank"}, keep
    )


def _k_with_rank(ctx: StageContext, p) -> None:
    """Attach each row's global engine-order rank as an int32 column
    (the indexed-operator analog: reference LongSelect / indexed
    Select/Where overloads, ``DryadLinqQueryGen.cs`` LongSelect
    dispatch)."""
    b, _total = _rank_column(ctx.slots[p["slot"]], ctx.P, ctx.axes)
    rank = b.data["#rank"].astype(jnp.int32)
    out = {n: c for n, c in b.data.items() if n != "#rank"}
    out[p["out"]] = jnp.where(b.valid, rank, 0)
    ctx.slots[p["slot"]] = ColumnBatch(out, b.valid)


def _k_take(ctx: StageContext, p) -> None:
    b, _total = _rank_column(ctx.slots[p["slot"]], ctx.P, ctx.axes)
    rank = b.data["#rank"]
    keep = b.valid & (rank < jnp.uint32(p["n"]))
    ctx.slots[p["slot"]] = _strip_rank(b, keep)


def _k_skip(ctx: StageContext, p) -> None:
    """Drop the first n rows of global engine order (reference Skip)."""
    b, _total = _rank_column(ctx.slots[p["slot"]], ctx.P, ctx.axes)
    keep = b.valid & (b.data["#rank"] >= jnp.uint32(p["n"]))
    ctx.slots[p["slot"]] = _strip_rank(b, keep)


def _k_tail(ctx: StageContext, p) -> None:
    """Keep the last n rows of global engine order (Last/TakeLast shape,
    reference Last/LastOrDefault dispatch ``DryadLinqQueryGen.cs``)."""
    b, total = _rank_column(ctx.slots[p["slot"]], ctx.P, ctx.axes)
    cut = jnp.maximum(total - jnp.int32(p["n"]), 0).astype(jnp.uint32)
    keep = b.valid & (b.data["#rank"] >= cut)
    ctx.slots[p["slot"]] = _strip_rank(b, keep)


def _first_false_rank(
    b: ColumnBatch, pred: jax.Array, total: jax.Array, axes: Tuple[str, ...]
) -> jax.Array:
    """Global rank of the first valid row failing ``pred`` (= total if
    every row passes)."""
    rank = b.data["#rank"]
    failing = jnp.where(
        b.valid & jnp.logical_not(pred), rank, jnp.uint32(0xFFFFFFFF)
    )
    local_min = jnp.min(failing)
    global_min = jax.lax.pmin(local_min, axes)
    return jnp.minimum(global_min, total.astype(jnp.uint32))


def _k_take_while(ctx: StageContext, p) -> None:
    """Rows strictly before the first predicate failure (TakeWhile)."""
    b, total = _rank_column(ctx.slots[p["slot"]], ctx.P, ctx.axes)
    pred = p["fn"]({n: c for n, c in b.data.items() if n != "#rank"})
    cut = _first_false_rank(b, pred, total, ctx.axes)
    keep = b.valid & (b.data["#rank"] < cut)
    ctx.slots[p["slot"]] = _strip_rank(b, keep)


def _k_skip_while(ctx: StageContext, p) -> None:
    """Rows from the first predicate failure onward (SkipWhile)."""
    b, total = _rank_column(ctx.slots[p["slot"]], ctx.P, ctx.axes)
    pred = p["fn"]({n: c for n, c in b.data.items() if n != "#rank"})
    cut = _first_false_rank(b, pred, total, ctx.axes)
    keep = b.valid & (b.data["#rank"] >= cut)
    ctx.slots[p["slot"]] = _strip_rank(b, keep)


def _k_reverse(ctx: StageContext, p) -> None:
    """Globally reverse engine row order (reference Reverse,
    ``DryadLinqQueryGen.cs:2731``): invert each row's global rank and
    repartition by the inverted rank."""
    b, total = _rank_column(ctx.slots[p["slot"]], ctx.P, ctx.axes)
    inv = (total.astype(jnp.uint32) - jnp.uint32(1)) - b.data["#rank"]
    inv = jnp.where(b.valid, inv, jnp.uint32(0xFFFFFFFF))
    b = ColumnBatch(dict(b.data, **{"#rank": inv}), b.valid)
    per = _round8(ctx.base_cap(p["slot"]) * ctx.boost)
    out = _exchange_by_rank(ctx, b, per)
    ctx.slots[p["slot"]] = _strip_rank(out, out.valid)


def _k_default_if_empty(ctx: StageContext, p) -> None:
    """If the table is globally empty, emit one default row on partition
    0 (reference DefaultIfEmpty)."""
    b = ctx.slots[p["slot"]].compact()
    total = jax.lax.psum(jnp.sum(b.valid.astype(jnp.int32)), ctx.axes)
    me = jax.lax.axis_index(ctx.axes)
    emit = (total == 0) & (me == 0)
    data = {}
    for name, col in b.data.items():
        dflt = jnp.asarray(p["defaults"].get(name, 0), col.dtype)
        data[name] = jnp.where(
            emit, col.at[0].set(dflt), col
        )
    valid = jnp.where(emit, b.valid.at[0].set(True), b.valid)
    ctx.slots[p["slot"]] = ColumnBatch(data, valid)


def _global_pair_reduce(
    ctx: StageContext, op: str, b: ColumnBatch, lo_col: str, v: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Mesh-wide 64-bit word-pair reduce: per-partition pair reduce,
    all_gather the P partial pairs (psum can't carry 64 bits), reduce
    the gathered pairs the same way.  All-invalid partitions contribute
    the op identity (neutral), so the gathered reduce needs no
    validity."""
    plo, phi = SEG.pair_scalar_reduce(op, *SEG.pair_words(b.data, lo_col), v)
    glo = jax.lax.all_gather(plo[None], ctx.axes, tiled=True)
    ghi = jax.lax.all_gather(phi[None], ctx.axes, tiled=True)
    return SEG.pair_scalar_reduce(
        op, glo, ghi, jnp.ones(glo.shape, jnp.bool_)
    )


def _k_scalar_agg(ctx: StageContext, p) -> None:
    b = ctx.slots[p["slot"]]
    v = b.valid
    out: Dict[str, jax.Array] = {}
    for a in p["aggs"]:
        if a.op == "count":
            loc = jnp.sum(v.astype(jnp.int32))
            out[a.out] = jax.lax.psum(loc, ctx.axes)[None]
        elif a.op == "sum":
            col = b.data[a.col]
            loc = jnp.sum(jnp.where(v, col, jnp.zeros((), col.dtype)))
            out[a.out] = jax.lax.psum(loc, ctx.axes)[None]
        elif a.op == "min":
            col = b.data[a.col]
            big = _dtype_max(col.dtype)
            loc = jnp.min(jnp.where(v, col, big))
            out[a.out] = jax.lax.pmin(loc, ctx.axes)[None]
        elif a.op == "max":
            col = b.data[a.col]
            small = _dtype_min(col.dtype)
            loc = jnp.max(jnp.where(v, col, small))
            out[a.out] = jax.lax.pmax(loc, ctx.axes)[None]
        elif a.op == "mean":
            col = b.data[a.col].astype(jnp.float32)
            s = jax.lax.psum(jnp.sum(jnp.where(v, col, 0.0)), ctx.axes)
            c = jax.lax.psum(jnp.sum(v.astype(jnp.float32)), ctx.axes)
            out[a.out] = (s / jnp.maximum(c, 1.0))[None]
        elif a.op == "mean64":
            # Average over long: exact global sum64, f32 divide
            tlo, thi = _global_pair_reduce(ctx, "sum64", b, a.col, v)
            c = jax.lax.psum(jnp.sum(v.astype(jnp.int32)), ctx.axes)
            out[a.out] = SEG.pair_mean(tlo, thi, c, a.scale)[None]
        elif a.op in SEG.PAIR_OPS:
            # 64-bit scalar over a split column
            tlo, thi = _global_pair_reduce(ctx, a.op, b, a.col, v)
            out[f"{a.out}#h0"] = tlo[None]
            out[f"{a.out}#h1"] = thi[None]
        elif a.op == "any":
            col = b.data[a.col]
            loc = jnp.any(v & col).astype(jnp.int32)
            out[a.out] = (jax.lax.psum(loc, ctx.axes) > 0)[None]
        elif a.op == "all":
            col = b.data[a.col]
            loc = jnp.all(jnp.where(v, col, True)).astype(jnp.int32)
            out[a.out] = (jax.lax.psum(loc, ctx.axes) >= ctx.P)[None]
        else:
            raise ValueError(f"unknown scalar agg {a.op!r}")
    me = jax.lax.axis_index(ctx.axes)
    valid = (me == 0)[None]
    ctx.slots[p["slot"]] = ColumnBatch(out, valid)


def _k_fork(ctx: StageContext, p) -> None:
    b = ctx.slots[p["slot"]]
    outs = p["fn"](b)
    want = p["out_dtypes"]
    if len(outs) != len(want):
        raise ValueError(
            f"fork fn returned {len(outs)} outputs, expected {len(want)}"
        )
    for i, (slot, ob, dtypes) in enumerate(zip(p["out_slots"], outs, want)):
        if not isinstance(ob, ColumnBatch):
            raise TypeError("fork fn must return ColumnBatches")
        # against its schema here, where the output can be named: a
        # wrong column otherwise fails inside whatever consumes it
        got = tuple(sorted((n, a.dtype.name) for n, a in ob.data.items()))
        if got != dtypes:
            raise ValueError(
                f"fork output {i} has columns {dict(got)}; its schema "
                f"(out_schemas[{i}]) says {dict(dtypes)}"
            )
        if ob.capacity != b.capacity:
            raise ValueError(
                f"fork output {i} has capacity {ob.capacity}; a fork "
                f"keeps its input's ({b.capacity}): filter rows, do not "
                "cut them"
            )
        ctx.slots[slot] = ob


def _dtype_max(dt):
    if jnp.issubdtype(dt, jnp.floating):
        return jnp.array(jnp.inf, dt)
    return jnp.array(jnp.iinfo(dt).max, dt)


def _dtype_min(dt):
    if jnp.issubdtype(dt, jnp.floating):
        return jnp.array(-jnp.inf, dt)
    return jnp.array(jnp.iinfo(dt).min, dt)


_KERNELS = {
    "select": _k_select,
    "where": _k_where,
    "project": _k_project,
    "seed": _k_seed,
    "select_many": _k_select_many,
    "apply": _k_apply,
    "exchange_hash": _k_exchange_hash,
    "exchange_range": _k_exchange_range,
    "resize": _k_resize,
    "group_reduce": _k_group_reduce,
    "group_reduce_dense": _k_group_reduce_dense,
    "string_code": _k_string_code,
    "group_combine": _k_group_combine,
    "distinct": _k_distinct,
    "local_sort": _k_local_sort,
    "topk": _k_topk,
    "join": _k_join,
    "semi": _k_semi,
    "concat": _k_concat,
    "take": _k_take,
    "with_rank": _k_with_rank,
    "skip": _k_skip,
    "tail": _k_tail,
    "take_while": _k_take_while,
    "skip_while": _k_skip_while,
    "reverse": _k_reverse,
    "default_if_empty": _k_default_if_empty,
    "scalar_agg": _k_scalar_agg,
    "fork": _k_fork,
    "group_join_count": _k_group_join_count,
    "join_ranked": _k_join_ranked,
    "zip": _k_zip,
    "sliding_window": _k_sliding_window,
}


def build_fused_fn(fused, P: int, slack: float, boost: int,
                   axes: "Tuple[str, ...]" = (AXIS,),
                   axis_sizes: "Tuple[int, ...]" = (),
                   operand_objs: "Tuple[Any, ...]" = (),
                   window: int = 0,
                   xchg_cell: "List[Dict[str, int]]" = None,
                   join_cell: "List[Dict[str, Any]]" = None,
                   sort_cell: "List[int]" = None,
                   elided_cell: "List[int]" = None,
                   seen_cell: "List[Dict[str, Any]]" = None):
    """Compose a whole fused REGION (``plan.fuse.FusedStage``) into one
    per-partition function: the member stage fns chain device-resident
    — member i's output batches feed member j's slots directly in HBM,
    exchanges at the seams stay ``ops/shuffle`` collectives inside the
    one ``shard_map`` region, and the driver never touches the
    boundary.  This body must stay free of host-transfer APIs
    (``np.asarray`` / ``.item()`` / ``jax.device_get``) — enforced
    statically by ``tests/test_fuse_lint.py``.

    Overflow/miss contract: the region's overflow flag is the OR over
    every member's (already mesh-reduced) flag, the dict-miss count
    is the sum and what the exchanges and joins saw
    (``StageContext.seen``) is every member's, in member order — one
    seam overflowing retries the WHOLE region at the next palette
    boost, the same bounded-palette contract as the single-stage path.

    ``operand_objs``: the region's deduplicated OPERAND-registered
    param objects in ``stage_operand_objs(fused)`` order (the chained
    member enumeration); each member fn receives exactly its own
    objects' arrays, so one table shared by two members uploads once.
    """
    members = fused.members
    member_objs = [
        tuple(stage_operand_objs(m)) if operand_objs else ()
        for m in members
    ]
    # Per-member exchange-round accounting and join-plan cells; each
    # member fn rewrites its own cells idempotently at trace time, and
    # the region fn flattens them in member order into the caller's.
    member_cells = [[] for _ in members]
    member_joins = [[] for _ in members]
    member_sorts = [[] for _ in members]
    member_elided = [[] for _ in members]
    member_seen = [[] for _ in members]
    member_fns = [
        build_stage_fn(
            m, P, slack, boost, axes, axis_sizes,
            operand_objs=member_objs[i],
            window=window, xchg_cell=member_cells[i],
            join_cell=member_joins[i], sort_cell=member_sorts[i],
            elided_cell=member_elided[i], seen_cell=member_seen[i],
        )
        for i, m in enumerate(members)
    ]

    def fn(sharded_inputs, replicated):
        rep = tuple(replicated)
        rep_map = {}
        pos = 0
        for obj in operand_objs:
            n = obj.operand_arity
            rep_map[id(obj)] = rep[pos:pos + n]
            pos += n
        if pos != len(rep):
            raise ValueError(
                f"fused region {fused.name!r}: {len(rep)} replicated "
                f"operand arrays for {pos} registered operand slots"
            )
        ext = tuple(sharded_inputs)
        member_outs: List[Tuple] = []
        overflow = None
        miss = None
        seen: Tuple = ()
        for i, mfn in enumerate(member_fns):
            ins = tuple(
                ext[src[1]] if src[0] == "ext"
                else member_outs[src[1]][src[2]]
                for src in fused.wiring[i]
            )
            mrep = tuple(
                a for obj in member_objs[i] for a in rep_map[id(obj)]
            )
            outs, (m_ovf, m_miss, m_seen) = mfn(ins, mrep)
            member_outs.append(outs)
            overflow = m_ovf if overflow is None else (overflow | m_ovf)
            miss = m_miss if miss is None else (miss + m_miss)
            seen += m_seen
        region_outs = tuple(
            member_outs[mi][oi] for mi, oi in fused.exports
        )
        if xchg_cell is not None:
            xchg_cell[:] = [r for c in member_cells for r in c]
        if join_cell is not None:
            join_cell[:] = [r for c in member_joins for r in c]
        if sort_cell is not None:
            sort_cell[:] = [max((w for c in member_sorts for w in c), default=0)]
        if elided_cell is not None:
            elided_cell[:] = [sum(n for c in member_elided for n in c)]
        if seen_cell is not None:
            seen_cell[:] = [r for c in member_seen for r in c]
        return region_outs, (overflow, miss, seen)

    return fn


def build_stage_fn(stage, P: int, slack: float, boost: int,
                   axes: "Tuple[str, ...]" = (AXIS,),
                   axis_sizes: "Tuple[int, ...]" = (),
                   operand_objs: "Tuple[Any, ...]" = (),
                   window: int = 0,
                   xchg_cell: "List[Dict[str, int]]" = None,
                   join_cell: "List[Dict[str, Any]]" = None,
                   sort_cell: "List[int]" = None,
                   elided_cell: "List[int]" = None,
                   seen_cell: "List[Dict[str, Any]]" = None):
    """Compose the stage's ops into one per-partition function.

    ``operand_objs``: the stage's OPERAND-registered param objects (in
    ``stage_operand_objs`` order) whose arrays arrive flattened through
    the replicated input slot at call time instead of being baked as
    trace constants; empty = the legacy baked path (every caller that
    passes operands must feed the matching arrays on every call).

    The fn returns ``(outs, (overflow, dict_miss, seen))``: the output
    batches, sharded, and three replicated values — the overflow flag
    and the dictionary misses, reduced over the mesh, and one array an
    exchange or join kernel the trace kept (``StageContext.seen``;
    ``()`` for a stage with neither; an exchange on one partition is
    not traced), of which ``seen_cell`` gets ``StageContext.seen_log``'s
    record each."""

    def fn(sharded_inputs, replicated):
        ctx = StageContext(P, slack, boost, axes, axis_sizes, window)
        ctx.bind_inputs(tuple(sharded_inputs))
        rep = tuple(replicated)
        pos = 0
        for obj in operand_objs:
            n = obj.operand_arity
            ctx.operand_map[id(obj)] = rep[pos:pos + n]
            pos += n
        if pos != len(rep):
            raise ValueError(
                f"stage {stage.name!r}: {len(rep)} replicated operand "
                f"arrays for {pos} registered operand slots"
            )
        with SORT.widest_row() as row_words:
            for i, op in enumerate(stage.ops):
                if op.kind == "do_while":
                    raise RuntimeError(
                        "do_while stages are driver-evaluated"
                    )
                ctx.ahead = stage.ops[i + 1:]
                apply_op(ctx, op.kind, op.params)
        outs = tuple(ctx.slots[s] for s in stage.out_slots)
        # Overflow flags from resize/join are per-device; reduce across the
        # mesh so the replicated output is truly uniform (a silently
        # device-local flag loses rows without tripping the retry).
        overflow = jax.lax.psum(ctx.overflow.astype(jnp.int32), axes) > 0
        miss = jax.lax.psum(ctx.dict_miss, axes)
        if xchg_cell is not None:
            # Idempotent rewrite (not append): a retrace must not
            # double-count the static accounting.
            xchg_cell[:] = list(ctx.xchg_log)
        if join_cell is not None:
            join_cell[:] = list(ctx.join_log)
        if sort_cell is not None:
            # 4-byte words of the widest row a sort of the stage carried
            sort_cell[:] = row_words
        if elided_cell is not None:
            # exchanges this trace skipped on a mesh of one partition
            elided_cell[:] = [ctx.xchg_elided]
        if seen_cell is not None:
            seen_cell[:] = list(ctx.seen_log)
        return outs, (overflow, miss, tuple(ctx.seen))

    return fn
