"""Shuffle exchange tests on the virtual 8-device mesh."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dryad_tpu.columnar.batch import ColumnBatch
from dryad_tpu.columnar.schema import ColumnType, Schema
from dryad_tpu.exec.kernels import JOINS, build_stage_fn
from dryad_tpu.ops.hash import partition_ids
from dryad_tpu.ops.segmented import AggSpec, group_reduce
from dryad_tpu.ops import join as JOIN
from dryad_tpu.ops import segmented as SEG
from dryad_tpu.ops import sort as SORT
from dryad_tpu.ops.shuffle import (
    bucket_capacity,
    exchange,
    exchange_staged,
    resize,
)
from dryad_tpu.parallel.distribute import from_host_table, to_host_table
from dryad_tpu.parallel.mesh import AXIS, make_mesh
from dryad_tpu.parallel.stage import compile_stage
from dryad_tpu.plan.lower import Stage, StageOp
from dryad_tpu.plan.xchgplan import plan_exchange

from oracle import check

SCHEMA = Schema([("k", ColumnType.INT32), ("v", ColumnType.FLOAT32)])


def test_hash_exchange_preserves_rows(mesh8):
    P = 8
    n = 1000
    rng = np.random.default_rng(1)
    k = rng.integers(0, 100, n).astype(np.int32)
    v = rng.standard_normal(n).astype(np.float32)
    batch = from_host_table(SCHEMA, {"k": k, "v": v}, mesh8, partition_capacity=200)
    cap = batch.capacity // P
    B = bucket_capacity(cap, P, slack=2.0)

    def stage(sharded, _):
        (b,) = sharded
        dest = partition_ids([b["k"]], P)
        out, overflow = exchange(b, dest, P, B, AXIS)
        return (out,), (overflow,)

    fn = compile_stage(mesh8, stage)
    (out,), (overflow,) = fn((batch,), ())
    assert not bool(overflow)
    got = to_host_table(out, SCHEMA)
    check(got, {"k": k, "v": v})


def test_exchange_overflow_detected(mesh8):
    P = 8
    n = 800
    # All rows share one key -> all go to one partition; tiny buckets overflow.
    k = np.zeros(n, np.int32)
    v = np.arange(n, dtype=np.float32)
    batch = from_host_table(SCHEMA, {"k": k, "v": v}, mesh8, partition_capacity=100)

    def stage(sharded, _):
        (b,) = sharded
        dest = partition_ids([b["k"]], P)
        out, overflow = exchange(b, dest, P, 16, AXIS)
        return (out,), (overflow,)

    fn = compile_stage(mesh8, stage)
    _, (overflow,) = fn((batch,), ())
    assert bool(overflow)


def test_shuffled_group_reduce_end_to_end(mesh8):
    """Hash shuffle + segmented reduce == global groupby (the WordCount core)."""
    P = 8
    n = 2000
    rng = np.random.default_rng(2)
    k = rng.integers(0, 50, n).astype(np.int32)
    v = np.ones(n, np.float32)
    batch = from_host_table(SCHEMA, {"k": k, "v": v}, mesh8, partition_capacity=300)
    cap = batch.capacity // P
    B = bucket_capacity(cap, P, slack=4.0)

    def stage(sharded, _):
        (b,) = sharded
        dest = partition_ids([b["k"]], P)
        shuf, ovf1 = exchange(b, dest, P, B, AXIS)
        shuf, ovf2 = resize(shuf, cap * 2)
        red = group_reduce(shuf, ["k"], [AggSpec("sum", "v", "s"), AggSpec("count", None, "c")])
        return (red,), (ovf1 | ovf2,)

    fn = compile_stage(mesh8, stage)
    (out,), (overflow,) = fn((batch,), ())
    assert not bool(overflow)

    valid = np.asarray(out.valid)
    got_k = np.asarray(out["k"])[valid]
    got_s = np.asarray(out["s"])[valid]
    got_c = np.asarray(out["c"])[valid]
    # Oracle: numpy groupby
    uk, counts = np.unique(k, return_counts=True)
    want = {int(a): int(b) for a, b in zip(uk, counts)}
    got = {int(a): int(b) for a, b in zip(got_k, got_c)}
    assert got == want
    assert np.allclose(sorted(got_s), sorted(counts.astype(np.float32)))
    # keys must not be duplicated across partitions
    assert len(got_k) == len(set(got_k.tolist()))


def test_resize_shrink_and_overflow():
    schema = Schema([("n", ColumnType.INT32)])
    b = ColumnBatch.from_numpy(schema, {"n": np.arange(10, dtype=np.int32)}, capacity=16)
    small, ovf = resize(b, 4)
    assert bool(ovf)
    big, ovf2 = resize(b, 32)
    assert not bool(ovf2)
    assert big.capacity == 32 and int(big.count()) == 10


# -- a batch's rows ride the sort that permutes them (PR 25) ------------------
#
# The contract: whichever way the columns move (carried through
# ``lax.sort`` as on the TPU, or gathered by a sorted row index as on
# the CPU), every slot of the output, the invalid ones included, holds
# what ``batch.take(order)`` put there before PR 25, and the overflow
# flag is the same.  Since PR 43 the exchange reads its send buffers out
# of the sorted rows by position; the form that scattered them there
# (``_scatter_exchange``) is the oracle both exchange forms are held to.

_P, _CAP = 8, 64
_B = bucket_capacity(_CAP, _P, 1.5)  # 12: a bucket fills in the all-valid batch only


def _take_order_layout(batch, dest, P, B):
    """``_bucket_layout`` as it was before PR 25: sort ``(dest, iota)``,
    then one gather a column by the sorted ``iota``; the histogram and
    each row's position in its bucket as they were through PR 42."""
    cap = batch.capacity
    dest = jnp.where(batch.valid, dest, P)
    dsorted, order = jax.lax.sort(
        (dest, jnp.arange(cap, dtype=jnp.int32)), num_keys=1, is_stable=True
    )
    sb = batch.take(order)
    counts = jnp.bincount(dsorted, length=P + 1)[:P]
    offsets = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]]
    )
    within = jnp.arange(cap, dtype=jnp.int32) - jnp.where(
        dsorted < P, offsets[jnp.clip(dsorted, 0, P - 1)], 0
    ).astype(jnp.int32)
    in_range = (dsorted < P) & (within < B)
    overflow = jnp.any((dsorted < P) & (within >= B))
    return sb, dsorted, within, in_range, overflow


def _scatter_exchange(batch, dest, P, B, axis_name):
    """The oracle: ``exchange`` as it stood through PR 42, every row
    scattered to ``dest * B + position in bucket`` of a zeroed send
    buffer.  ``exchange_staged`` promises the same bytes."""
    sb, dsorted, within, in_range, overflow = _take_order_layout(
        batch, dest, P, B
    )
    flat_idx = jnp.where(in_range, dsorted * B + within, P * B)

    def ship(col, fill):
        buf = jnp.zeros((P * B,) + col.shape[1:], col.dtype)
        buf = buf.at[flat_idx].set(fill, mode="drop")
        return jax.lax.all_to_all(
            buf.reshape((P, B) + col.shape[1:]), axis_name,
            split_axis=0, concat_axis=0, tiled=True,
        ).reshape(buf.shape)

    recv = {name: ship(col, col) for name, col in sb.data.items()}
    recv_valid = ship(sb.valid, sb.valid & in_range)
    overflow = jax.lax.psum(overflow.astype(jnp.int32), axis_name) > 0
    return ColumnBatch(recv, recv_valid), overflow


def _take_order_compact(batch):
    """``ColumnBatch.compact`` as it was before PR 25."""
    return batch.take(jnp.argsort(jnp.logical_not(batch.valid), stable=True))


def _permuted_batch(kind):
    """``_P * _CAP`` rows with stale values under the invalid slots."""
    n = _P * _CAP
    rng = np.random.default_rng(25)
    data = {
        "k": jnp.asarray(rng.integers(-50, 50, n).astype(np.int32)),
        "v": jnp.asarray(rng.standard_normal(n).astype(np.float32)),
        "f": jnp.asarray(rng.random(n) < 0.5),
    }
    if kind == "col2d":
        data["m"] = jnp.asarray(
            rng.integers(0, 1 << 20, (n, 3)).astype(np.uint32)
        )
    valid = {
        "all_valid": np.ones(n, np.bool_),
        "half_valid": rng.random(n) < 0.5,
        "none_valid": np.zeros(n, np.bool_),
        "col2d": rng.random(n) < 0.7,
    }[kind]
    return ColumnBatch(data, jnp.asarray(valid))


def _staged_exchange(batch, dest, P, B, axis_name):
    return exchange_staged(
        batch, dest, P, B, (axis_name,), plan_exchange(P, 2, 1)
    )


_PATHS = {"exchange": exchange, "exchange_staged": _staged_exchange}


def _exchange_stage(fn, to=None, B=_B):
    """A stage that runs exchange form *fn*: every row to partition
    *to*, or by the hash of ``k``."""

    def stage(sharded, _):
        (b,) = sharded
        dest = partition_ids([b["k"]], _P)
        if to is not None:
            dest = jnp.full_like(dest, to)
        out, ovf = fn(b, dest, _P, B, AXIS)
        return (out,), (ovf,)

    return stage


def _run_exchange(mesh, batch, fn, to=None, B=_B):
    (out,), (ovf,) = compile_stage(mesh, _exchange_stage(fn, to, B))(
        (batch,), ()
    )
    return out, bool(ovf)


def _assert_same_slots(got, want):
    np.testing.assert_array_equal(np.asarray(got.valid), np.asarray(want.valid))
    assert got.columns == want.columns
    for name in want.columns:
        np.testing.assert_array_equal(
            np.asarray(got[name]), np.asarray(want[name]), err_msg=name
        )


_RESIZE_TO = {"resize_equal": _P * _CAP, "resize_shrink": 100,
              "resize_grow": _P * _CAP + 40}


# Exchange-only cases: kind -> (batch, where every row goes (None: by
# hash), B, whether rows drop).  A shard holds ``_CAP`` rows.
_EXCHANGE_CASES = {
    # every row to the LAST bucket, which overflows
    "last_dest_overflows": ("all_valid", _P - 1, _B, True),
    "col2d_last_dest": ("col2d", _P - 1, _B, True),
    # the valid rows all to one bucket in the middle
    "one_dest": ("half_valid", 3, _B, True),
    # B == capacity: ``offsets[p] + B`` passes the end of the sorted
    # rows for every bucket but the first, where an unpadded
    # ``dynamic_slice`` would clamp its start
    "bucket_is_capacity": ("all_valid", None, _CAP, False),
}
_KINDS = ["all_valid", "half_valid", "none_valid", "col2d"]


@pytest.mark.parametrize(
    "path,kind",
    [(p, k) for k in _KINDS
     for p in ["exchange", "exchange_staged", "compact", *_RESIZE_TO]]
    + [(p, k) for k in _EXCHANGE_CASES for p in _PATHS],
)
@pytest.mark.parametrize("carry", [True, False], ids=["carry", "gather"])
def test_permuted_batch_equals_take_order(mesh8, monkeypatch, carry, path, kind):
    kind, to, B, drops = _EXCHANGE_CASES.get(
        kind, (kind, None, _B, kind == "all_valid")  # rows drop at _B there
    )
    batch = _permuted_batch(kind)
    monkeypatch.setattr(SORT, "_carry_profitable", lambda: carry)
    if path in _PATHS:
        got, got_ovf = _run_exchange(mesh8, batch, _PATHS[path], to, B)
        want, want_ovf = _run_exchange(mesh8, batch, _scatter_exchange, to, B)
        assert want_ovf == drops
    elif path == "compact":
        got, got_ovf = batch.compact(), None
        want, want_ovf = _take_order_compact(batch), None
    else:
        got, got_ovf = resize(batch, _RESIZE_TO[path])
        monkeypatch.setattr(ColumnBatch, "compact", _take_order_compact)
        want, want_ovf = resize(batch, _RESIZE_TO[path])
        assert bool(want_ovf) == (
            int(np.count_nonzero(np.asarray(batch.valid))) > _RESIZE_TO[path]
        )
        got_ovf, want_ovf = bool(got_ovf), bool(want_ovf)
    assert got_ovf == want_ovf
    _assert_same_slots(got, want)


def _lowered(mesh, carry, monkeypatch, stage):
    """*stage* lowered over the 1-D columns of a half-valid batch."""
    monkeypatch.setattr(SORT, "_carry_profitable", lambda: carry)
    batch = _permuted_batch("half_valid")
    return compile_stage(mesh, stage).lower((batch,), ()).as_text()


def _wide_gathers(text):
    """Leading dimension of every ``stablehlo.gather`` result of more
    than ``_P + 1`` rows (the bucket offsets' binary search reads
    ``_P + 1`` of the sorted destinations a step)."""
    found = re.findall(
        r'stablehlo\.gather.*:\s*\(tensor<\d+[x>].*->\s*tensor<(\d+)[x>]', text
    )
    return [int(rows) for rows in found if int(rows) > _P + 1]


def test_carried_exchange_and_resize_lower_to_no_column_gather(
    mesh8, monkeypatch
):
    """With the carry forced, the program gathers no column."""

    def stage(sharded, _):
        (b,) = sharded
        out, o1 = exchange(b, partition_ids([b["k"]], _P), _P, _B, AXIS)
        out, o2 = resize(out, 2 * _CAP)
        return (out,), (o1 | o2,)

    capacities = {_CAP, _P * _B}
    gathered = _wide_gathers(_lowered(mesh8, False, monkeypatch, stage))
    assert capacities <= set(gathered), gathered  # the check can see them
    assert _wide_gathers(_lowered(mesh8, True, monkeypatch, stage)) == []


@pytest.mark.parametrize("path", list(_PATHS))
def test_exchange_lowers_to_slices_not_scatters(mesh8, monkeypatch, path):
    """A send buffer is sliced out of the destination-sorted rows (PR
    43): with the carry forced, neither exchange form scatters anything
    or gathers more than the ``_P + 1`` run starts; the oracle does
    both, so the check can see them."""
    oracle = _lowered(mesh8, True, monkeypatch, _exchange_stage(_scatter_exchange))
    assert "stablehlo.scatter" in oracle and _wide_gathers(oracle)
    text = _lowered(mesh8, True, monkeypatch, _exchange_stage(_PATHS[path]))
    assert "stablehlo.scatter" not in text
    assert "stablehlo.dynamic_slice" in text
    assert _wide_gathers(text) == []


# -- a resize counts; it sorts only where its slot's next reader would not (PR 48) --
#
# The stage programs are built as the executor builds them
# (``build_stage_fn`` over ``resize`` + the reader), the batch a chip is
# handed has holes as an exchange leaves them, and the reference is the
# form every ``resize`` had through PR 47, written out here: compact,
# cut or pad to the target, then the kernel.

_SLOTS = 64  # a chip's, as the exchange hands them over; slack 1 and boost 1:
_FACTORS = {"shrink": 0.5, "equal": 1.0, "grow": 1.5}  # the target is factor x _SLOTS
_FILLS = {"sparse": 20, "dense": 44}  # valid rows a chip: only 44 > 32 overflows


def _received(P, fill, seed=48):
    """``P`` x ``_SLOTS`` slots, ``fill`` valid rows a chip at drawn
    positions, few keys so that groups lie across the holes."""
    rng = np.random.default_rng([seed, P, fill])
    n = P * _SLOTS
    valid = np.zeros(n, np.bool_)
    for p in range(P):
        valid[p * _SLOTS + rng.choice(_SLOTS, fill, replace=False)] = True
    return ColumnBatch({
        "k": jnp.asarray(rng.integers(-6, 6, n).astype(np.int32)),
        "v": jnp.asarray(rng.standard_normal(n).astype(np.float32)),
        "n": jnp.asarray(np.ones(n, np.int32)),
    }, jnp.asarray(valid))


def _merge(a, b):
    return {"n": a["n"] + b["n"], "v": a["v"] + b["v"]}


def _operands(b):
    from dryad_tpu.ops.sortkeys import to_sortable_u32

    return [to_sortable_u32(b.data["k"])]


_AGGS = [AggSpec("count", None, "c"), AggSpec("sum", "v", "s"), AggSpec("first", "n", "n")]
# reader -> (the stage op's params beside its slots, the kernel over (left, received))
_READERS = {
    "group_reduce": (
        dict(keys=["k"], aggs=_AGGS),
        lambda _l, b: group_reduce(b, ["k"], _AGGS)),
    "group_combine": (
        dict(keys=["k"], state_cols=["n", "v"], merge=_merge),
        lambda _l, b: SEG.group_combine(b, ["k"], ["n", "v"], _merge)),
    "distinct": (dict(keys=["k"]), lambda _l, b: SEG.distinct(b, ["k"])),
    "local_sort": (
        dict(operands_fn=_operands),
        lambda _l, b: SORT.sort_batch_by_operands(b, _operands(b))),
    "join": (
        dict(left_keys=["k"], right_keys=["k"], expansion=8.0),
        lambda l, b: JOIN.hash_join(
            l, b, ["k"], ["k"], 8 * max(l.capacity, b.capacity), "_r")[0]),
    # the received rows are the join's LEFT side (its right: the other input)
    "join_left": (
        dict(left_keys=["k"], right_keys=["k"], expansion=8.0, left_slot=1, right_slot=0),
        lambda r, b: JOIN.hash_join(
            b, r, ["k"], ["k"], 8 * max(r.capacity, b.capacity), "_r")[0]),
}


def _stage_after_resize(reader, factor, **named):
    """``resize`` of slot 1 and then ``reader`` (of that slot, a join's
    right, unless ``named`` says other slots), a stage of two inputs
    whose output is the join's or the resized slot."""
    ops = [StageOp("resize", dict(slot=1, factor=factor))]
    out = 1
    if reader is not None:
        params = dict(_READERS.get(reader, ({},))[0])
        kind = reader.split("_left")[0]  # ``join_left`` is a ``join``
        if kind in JOINS:
            params = dict(dict(left_slot=0, right_slot=1), **params)
        elif "slots" not in named:
            params["slot"] = 1
        params.update(named)
        ops.append(StageOp(kind, params))
        out = params["left_slot"] if kind in JOINS else 1
    return Stage(0, "resized", [("plan_input", 0), ("plan_input", 1)], ops=ops,
                 out_slots=[out])


def _parents_form(reader, target):
    """compact, cut or pad, then the kernel: the stage through PR 47."""

    def stage(sharded, _):
        left, b = sharded
        c = b.compact()
        overflow = c.count() > target
        if target < c.capacity:
            c = ColumnBatch({k: v[:target] for k, v in c.data.items()}, c.valid[:target])
        else:
            c = c.pad_to(target)
        overflow = jax.lax.psum(overflow.astype(jnp.int32), AXIS) > 0
        return (_READERS[reader][1](left, c),), (overflow,)

    return stage


def _probe_side(P):
    """The join's left table: every slot a row, the received keys' range."""
    n = P * _SLOTS
    k = np.random.default_rng([48, P]).integers(-6, 6, n).astype(np.int32)
    return ColumnBatch({"k": jnp.asarray(k), "i": jnp.arange(n, dtype=jnp.int32)},
                       jnp.ones(n, jnp.bool_))


@pytest.mark.parametrize("P", [4, 8])
@pytest.mark.parametrize("fill", list(_FILLS))
@pytest.mark.parametrize("to", list(_FACTORS))
@pytest.mark.parametrize("reader", list(_READERS))
def test_a_resize_before_a_kernel_that_sorts_is_the_parents_form(reader, to, fill, P):
    """Rule 2: whichever of the readers follows (a join as the reader
    of either side) and whatever the target, the overflow flag is the parent's, and wherever it is false
    (where it is true the stage is run again and its output dropped) so
    is every row of the output, slot for slot and to the bit: the f32
    sums and the user's ``merge`` fold the rows in the parent's order."""
    mesh = make_mesh(P)
    target = int(_FACTORS[to] * _SLOTS)
    inputs = (_probe_side(P), _received(P, _FILLS[fill]))
    fn = build_stage_fn(_stage_after_resize(reader, _FACTORS[to]), P, 1.0, 1)
    (got,), (got_ovf, _, _) = compile_stage(mesh, fn)(inputs, ())
    (want,), (want_ovf,) = compile_stage(mesh, _parents_form(reader, target))(inputs, ())
    assert bool(got_ovf) == bool(want_ovf) == (_FILLS[fill] > target)
    if bool(want_ovf):
        return
    assert got.capacity == want.capacity and got.columns == want.columns
    valid = np.asarray(want.valid)
    np.testing.assert_array_equal(np.asarray(got.valid), valid)
    assert valid.any()
    for name in want.columns:
        np.testing.assert_array_equal(
            np.asarray(got[name])[valid], np.asarray(want[name])[valid], err_msg=name)


def _compacts(stage, P=4):
    """Whether the stage's lowered program holds the compaction's sort,
    and the sorts it holds."""
    inputs = (_probe_side(P), _received(P, _FILLS["sparse"]))
    lowered = compile_stage(make_mesh(P), build_stage_fn(stage, P, 1.0, 1)).lower(inputs, ())
    paths = re.findall(r'loc\("([^"]*dryad\.[^"]*)"', lowered.as_text(debug_info=True))
    sorts = len(re.findall(r"stablehlo\.sort", lowered.as_text()))
    return any("dryad.resize/dryad.sort.carry" in p for p in paths), sorts


_OTHER_READERS = {
    # nothing reads the slot: it leaves the stage (a ``hash_partition`` read back)
    "nothing": (None, {}),
    "select": ("select", dict(fn=lambda cols: cols)),
    "where": ("where", dict(fn=lambda cols: cols["k"] > 0)),
    "project": ("project", dict(cols=["k", "v"])),
    "apply": ("apply", dict(fn=lambda b: b)),
    "concat": ("concat", dict(slots=[1, 1], out_slot=1)),
    "another_exchange": ("exchange_hash", dict(keys=["k"])),
    # a kernel that sorts, but another slot: this one leaves the stage as it is
    "local_sort_of_another_slot": ("local_sort", dict(slot=0)),
    # a join that hands its left batch on, slot for slot: holes would reach the egress
    "semi_left": ("semi", dict(left_slot=1, right_slot=0, left_keys=["k"],
                               right_keys=["k"], expansion=8.0)),
    "outer_join_left": ("join", dict(left_slot=1, right_slot=0, outer=True,
                                     **_READERS["join"][0])),
}


@pytest.mark.parametrize("reader", list(_OTHER_READERS))
def test_a_resize_before_anything_else_still_compacts(reader):
    """Rule 3: the compaction's sort stays in the lowered program, under
    the ``resize``'s scope, whatever else reads the slot next."""
    kind, named = _OTHER_READERS[reader]
    compacts, _ = _compacts(_stage_after_resize(kind, 1.0, **named))
    assert compacts


@pytest.mark.parametrize("reader,to,compacts", [
    (r, to, False) for r in list(_READERS)[:4] for to in _FACTORS
] + [(j, to, to == "shrink") for j in ("join", "join_left") for to in _FACTORS])
def test_what_a_resize_before_a_sorting_kernel_lowers_to(reader, to, compacts):
    """Rule 2 in the program's text: no sort under ``dryad.resize`` and
    ONE sort in the whole stage where a fold or a ``local_sort`` reads
    the slot; before either side of a join one sort fewer than the
    stage that cuts, which keeps its compaction (the probe sorts the
    right side whatever was done to it and merges the left hashes into
    it)."""
    found, sorts = _compacts(_stage_after_resize(reader, _FACTORS[to]))
    assert found == compacts
    if reader.startswith("join"):
        _, cutting = _compacts(_stage_after_resize(reader, _FACTORS["shrink"]))
        assert sorts == cutting - (not compacts)
    else:
        assert sorts == 1


@pytest.mark.parametrize("query,sorts", [("group_by", 0), ("order_by", 0), ("hash_partition", 1)])
def test_a_job_says_how_many_resizes_sorted(query, sorts):
    """``resize_sorts`` on the ``drain`` span and the ``exchange_observed``
    event, a trace-time constant beside ``exchanges``: none where a fold
    or a ``local_sort`` reads the received rows, one for the rows of a
    ``hash_partition`` that go back to the user as they are."""
    from dryad_tpu import DryadContext

    rng = np.random.default_rng(48)
    ctx = DryadContext(num_partitions_=4)
    table = ctx.from_arrays({
        "k": (rng.integers(0, 50, 4096) - 1).astype(np.int32),  # a negative key: no dense route
        "v": rng.standard_normal(4096).astype(np.float32),
    })
    bound = {
        "group_by": lambda: table.group_by("k", {"c": ("count", None), "s": ("sum", "v")}),
        "order_by": lambda: table.order_by(["k"]),
        "hash_partition": lambda: table.hash_partition("k"),
    }[query]()
    assert len(bound.collect()["k"]) == (50 if query == "group_by" else 4096)
    events = ctx.events.events()
    seen, = [e for e in events if e["kind"] == "exchange_observed"]
    drain, = [e for e in events if e["kind"] == "span" and e["name"] == "drain"]
    assert seen["exchanges"] == drain["exchanges"] == 1
    assert seen["resize_sorts"] == drain["resize_sorts"] == sorts
    assert type(drain["resize_sorts"]) is int  # a number: it rides the profiler annotation
