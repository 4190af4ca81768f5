"""The shuffle exchange — repartitioning as an XLA collective.

This is the TPU-native replacement for the reference's cross-product
channel wiring + channel stack: where Dryad materializes N*M file/HTTP
channels between a partition stage and its consumers
(``GraphBuilder.cs:481`` ConnectCrossProduct;
``DryadVertex/VertexHost/system/channel/``), we exchange rows between
mesh devices with one ``all_to_all`` over ICI inside the compiled
program.

Static-shape strategy (XLA needs fixed shapes): each source device
lays its rows out in a ``(P, B)`` send buffer — ``B`` is the
per-destination bucket capacity, uniform expectation times a slack
factor — with a row-drop *overflow* flag when a bucket fills.  The
executor treats overflow as a retryable fault and re-runs the stage with
a larger ``B`` from a bounded shape palette (the adaptive analog of
``DrDynamicDistributor.h:26``'s data-size-driven fan-out).

How rows move: one stable ``lax.sort`` by destination puts each
bucket's rows side by side, and the columns go through that sort with
the key (``ops.sort.sort_carry``: on the TPU as extra sort operands,
elsewhere gathered by the sorted row index; the same permutation
either way).  Bucket ``p`` is then the run of sorted rows that starts
where ``p`` first stands among the sorted destinations (a binary
search for ``0..P``; the counts are the distances between the starts),
and row ``(p, j)`` of the send buffer is read by position: one
``dynamic_slice`` a bucket a column, masked past the bucket's count.
``resize`` counts, and sorts only where nothing that reads the slots
next would: it compacts the same way then, a stable sort on ``~valid``
alone with the columns carried, and not at all before a kernel whose
own sort puts valid rows first (a fifth of a four-chip group-by's
device time was that second sort of the same slots; ``PERF.md``
section 6, PR 48).  No column is gathered by a sorted ``iota``
and none is scattered into its slots: XLA's TPU ``gather`` runs at
28 ns an element (92.5% of the device time of a 2^25-row ``order_by``
and 73% of a four-chip ``group_by`` in that form; ``PERF_LEDGER.jsonl``,
PR 24, ``gather_dev_share``) and its ``scatter`` at 4.9 ns an update,
which with a ``scatter-add`` histogram beside it was a quarter to a
half of the four-chip cells' device time (``PERF.md`` section 6,
PR 43).

Under whole-DAG fusion (``plan/fuse.py``) these exchanges also serve as
the SEAMS between fused member stages: the whole multi-stage region
compiles as one ``shard_map`` program, so an inter-stage repartition is
just another ``exchange`` call inside the region — device-resident on
both sides, no driver boundary — and a seam overflow retries the whole
region on the same palette.  Placement within a destination partition
is (source, bucket-position) ordered independent of ``B``, which is
what keeps results byte-identical across overflow boosts and across
the fused/staged split.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from dryad_tpu.columnar.batch import ColumnBatch
from dryad_tpu.ops.sort import sort_carry


def bucket_capacity(capacity: int, num_partitions: int, slack: float) -> int:
    """Per-(src,dst) bucket rows: slack * uniform expectation, >= 8.

    Clamped to ``capacity``: one source holds at most ``capacity`` valid
    rows, so a bucket of ``capacity`` rows can never overflow — without
    the clamp the 8-row floor pads tiny chunks ~P x on wide meshes
    (send buffer ``P * 8`` rows for a source that only has, say, 4).
    Placement within a destination is independent of ``B``, so the
    clamp never changes exchanged bytes, only trims the padding.
    """
    import math

    want = max(8, int(math.ceil(capacity * slack / num_partitions)))
    return max(1, min(want, capacity))


def row_bytes(batch: ColumnBatch) -> int:
    """Static per-row byte footprint (columns + validity mask).

    Shape-only arithmetic — safe at trace time, used for the exchange
    planner's ``exchange_round`` byte accounting.
    """
    import math

    per = 1  # validity mask
    for col in batch.data.values():
        per += col.dtype.itemsize * int(math.prod(col.shape[1:]))
    return per


# Operator scopes (``jax.named_scope``) inside an exchange: the bucket
# layout (sort by destination with the columns carried, slice the runs
# into send buffers) and the collective apart, so a device trace splits
# one from the other.
LAYOUT_SCOPE = "dryad.exchange.layout"
COLLECTIVE_SCOPE = "dryad.exchange.collective"


def _bucket_layout(batch: ColumnBatch, dest: jax.Array, P: int, B: int):
    """Rows stably sorted by destination, so bucket ``p`` is the run
    that starts at ``offsets[p]``: ``(sorted columns, offsets, rows of
    each bucket that ship, overflow)``.  Invalid rows take the sentinel
    ``P`` (after every valid row, in their own order) and never ship; a
    bucket of more than ``B`` rows ships its first ``B`` and sets
    ``overflow``.  The columns ride the sort (``ops.sort.sort_carry``)
    and come back ``B`` slots longer than the batch: ``dynamic_slice``
    clamps its start to keep the slice inside its operand, which would
    hand the last buckets the rows before their own, so ``offsets[p] +
    B`` is made to fit."""
    names = batch.columns
    _, (dsorted,), carried = sort_carry(
        [jnp.where(batch.valid, dest, P)],
        batch.valid,
        [batch.data[n] for n in names],
    )
    offsets = jnp.searchsorted(
        dsorted.astype(jnp.int32), jnp.arange(P + 1, dtype=jnp.int32)
    )
    counts = jnp.diff(offsets)
    cols = {
        n: jnp.pad(c, ((0, B),) + ((0, 0),) * (c.ndim - 1))
        for n, c in zip(names, carried)
    }
    return cols, offsets, jnp.minimum(counts, B), jnp.any(counts > B)


def _bucket_block(cols, offsets, ships, p, B: int):
    """The ``(B, ...)`` block a column of bucket ``p`` (static or
    traced) and its ``valid``: the first ``B`` rows of the run, zeros
    behind them, which is slot for slot what a scatter to
    ``position in bucket`` over a zeroed block leaves."""
    live = jnp.arange(B, dtype=jnp.int32) < ships[p]
    blocks = {}
    for name, col in cols.items():
        blk = jax.lax.dynamic_slice_in_dim(col, offsets[p], B)
        keep = live.reshape((B,) + (1,) * (col.ndim - 1))
        blocks[name] = jnp.where(keep, blk, jnp.zeros((), col.dtype))
    return blocks, live


def exchange(
    batch: ColumnBatch,
    dest: jax.Array,
    num_partitions: int,
    bucket_cap: int,
    axis_name: str = "p",
) -> Tuple[ColumnBatch, jax.Array]:
    """All-to-all rows to their destination partitions.

    Must run inside ``shard_map`` over mesh axis ``axis_name`` with one
    partition per device.  ``dest[i]`` in [0, P) for valid rows; invalid
    rows never ship.  Returns the received batch (capacity ``P * B``)
    and a scalar bool overflow flag (psum'd across devices).
    """
    P, B = num_partitions, bucket_cap
    with jax.named_scope(LAYOUT_SCOPE):
        cols, offsets, ships, overflow = _bucket_layout(batch, dest, P, B)
        buckets = [
            _bucket_block(cols, offsets, ships, p, B) for p in range(P)
        ]
        send = {
            name: jnp.stack([blocks[name] for blocks, _ in buckets])
            for name in cols
        }
        send_valid = jnp.stack([live for _, live in buckets])

    with jax.named_scope(COLLECTIVE_SCOPE):
        recv = {
            name: jax.lax.all_to_all(
                buf, axis_name, split_axis=0, concat_axis=0, tiled=True
            ).reshape((P * B,) + buf.shape[2:])
            for name, buf in send.items()
        }
        recv_valid = jax.lax.all_to_all(
            send_valid, axis_name, split_axis=0, concat_axis=0, tiled=True
        ).reshape(P * B)

        overflow = jax.lax.psum(overflow.astype(jnp.int32), axis_name) > 0
    return ColumnBatch(recv, recv_valid), overflow


def exchange_staged(
    batch: ColumnBatch,
    dest: jax.Array,
    num_partitions: int,
    bucket_cap: int,
    axis_name,
    schedule,
) -> Tuple[ColumnBatch, jax.Array]:
    """Staged exchange: the flat all-to-all decomposed into ppermute hops.

    Same contract as :func:`exchange`, but instead of materializing the
    whole ``(P, B)`` send buffer, rows ship one destination bucket at a
    time along *schedule* (an :class:`~dryad_tpu.plan.xchgplan.ExchangeSchedule`):
    hop ``(sd, sp)`` builds a single ``(B, ...)`` block per column —
    the bucket destined for device ``((d+sd) % D, (p+sp) % ici)`` —
    ``ppermute``\\ s it, and writes the received block into the output at
    the sender's slot.  Peak extra HBM is one block per in-flight hop,
    ``O(window * B)`` per round, instead of the flat path's ``O(P * B)``.

    The output layout is the same ``(P * B)`` source-major placement as
    the flat path — (source, bucket-position) ordered, independent of
    the schedule — so staged and flat results are byte-identical and the
    choice is invisible to every consumer (including fused regions and
    overflow-palette retries).
    """
    P, B = num_partitions, bucket_cap
    D, ici = schedule.dcn_slices, schedule.ici_partitions
    assert P == schedule.num_partitions == D * ici

    with jax.named_scope(LAYOUT_SCOPE):
        cols, offsets, ships, overflow = _bucket_layout(batch, dest, P, B)

    me = jax.lax.axis_index(axis_name)  # flattened, slice-major
    md, mp = me // ici, me % ici

    out = {
        name: jnp.zeros((P * B,) + col.shape[1:], col.dtype)
        for name, col in cols.items()
    }
    out_valid = jnp.zeros((P * B,), jnp.bool_)

    def place(blocks, bv, src):
        start = (src * B).astype(jnp.int32)
        for name, blk in blocks.items():
            zeros = (0,) * (blk.ndim - 1)
            out[name] = jax.lax.dynamic_update_slice(
                out[name], blk, (start,) + zeros
            )
        return jax.lax.dynamic_update_slice(out_valid, bv, (start,))

    # Local bucket: zero network bytes, sliced straight into my slot.
    with jax.named_scope(LAYOUT_SCOPE):
        blocks, bv = _bucket_block(cols, offsets, ships, me, B)
        out_valid = place(blocks, bv, me)

    for rnd in schedule.rounds:
        for sd, sp in rnd.hops:
            perm = [
                (i, ((i // ici + sd) % D) * ici + (i % ici + sp) % ici)
                for i in range(P)
            ]
            tgt = ((md + sd) % D) * ici + (mp + sp) % ici
            src = ((md - sd) % D) * ici + (mp - sp) % ici
            with jax.named_scope(LAYOUT_SCOPE):
                blocks, bv = _bucket_block(cols, offsets, ships, tgt, B)
            with jax.named_scope(COLLECTIVE_SCOPE):
                blocks = {
                    name: jax.lax.ppermute(blk, axis_name, perm)
                    for name, blk in blocks.items()
                }
                bv = jax.lax.ppermute(bv, axis_name, perm)
            with jax.named_scope(LAYOUT_SCOPE):
                out_valid = place(blocks, bv, src)

    with jax.named_scope(COLLECTIVE_SCOPE):
        overflow = jax.lax.psum(overflow.astype(jnp.int32), axis_name) > 0
    return ColumnBatch(out, out_valid), overflow


def resize(
    batch: ColumnBatch, capacity: int, reader_sorts: bool = False
) -> Tuple[ColumnBatch, jax.Array]:
    """The slots an exchange hands over, brought to ``capacity``.

    Returns (batch, overflow).  ``overflow`` is a count and no sort:
    more valid rows than ``capacity`` (the executor retries with a
    larger shape), which no batch of at most ``capacity`` slots can
    hold, so there it is false as the program is traced.

    What moves the rows is the caller's to say.  ``reader_sorts``: the
    kernel that reads the batch next sorts valid rows first itself, and
    stably (a fold's ``_segment_layout``, a ``local_sort``, a join's
    probe of its right side; a join's left side counts as such, its
    rows are gathered where they lie), so nothing is compacted for it: the
    holes stay where the exchange left them, as after a ``where``, the
    valid rows reach the reader's sort in the order a compaction would
    have handed them over in, and its output is the same slot for slot.
    The batch grows to ``capacity`` (``pad_to``) and is never cut: where
    it has more slots the reader runs over all of them and the caller
    cuts the READER's output, whose valid rows are at the front
    (:func:`cut`) - without an overflow nothing valid lies past
    ``capacity`` there, with one the stage is run again.  Otherwise
    (anything else reads the batch, or it leaves the program) valid
    rows are compacted to the front (``ColumnBatch.compact``: a stable
    sort on ``~valid`` with the columns carried), which is what keeps a
    fetch's extent short, and the rows past ``capacity`` are dropped.
    """
    if capacity < batch.capacity:
        overflow = batch.count() > capacity
    else:
        overflow = jnp.zeros((), jnp.bool_)
    if reader_sorts:
        return batch.pad_to(max(capacity, batch.capacity)), overflow
    return cut(batch.compact(), capacity).pad_to(capacity), overflow


def cut(batch: ColumnBatch, capacity: int) -> ColumnBatch:
    """The first ``capacity`` slots of a batch that has more."""
    if capacity >= batch.capacity:
        return batch
    data = {k: v[:capacity] for k, v in batch.data.items()}
    return ColumnBatch(data, batch.valid[:capacity])
