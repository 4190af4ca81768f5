"""``ColumnBatch.fetch_host`` copies back the prefix of each shard that
its valid rows reach, not the capacity: the layout is measured a fetch
(valid rows and last valid row + 1, a shard), the columns are cut to
one tier of a bounded ladder on the device, and the answer is the
untrimmed fetch's whatever the layout.

CPU, P = 1 and a 4-device mesh; the batches here are small, so most
tests lower the gate (``TRIM_MIN_BYTES``) and three leave it alone.
"""

import math

import jax
import numpy as np
import pytest

import dryad_tpu.columnar.batch as batch_mod
from dryad_tpu import DryadContext
from dryad_tpu.columnar.batch import (
    ColumnBatch,
    ShardRows,
    decode_physical_table,
    encode_table,
    trim_tiers,
)
from dryad_tpu.columnar.schema import ColumnType, Schema, StringDictionary
from dryad_tpu.exec.events import EventLog
from dryad_tpu.obs.metrics import MetricsRegistry
from dryad_tpu.obs.span import Tracer
from dryad_tpu.parallel.mesh import make_mesh, partition_sharding

CAP = 4096  # slots a shard

SCHEMA = Schema([
    ("k", ColumnType.INT32), ("v", ColumnType.FLOAT32),
    ("big", ColumnType.INT64), ("x", ColumnType.FLOAT64),
    ("word", ColumnType.STRING),
])


@pytest.fixture
def low_gate(monkeypatch):
    monkeypatch.setattr(batch_mod, "TRIM_MIN_BYTES", 1 << 10)


def _masks(layout: str, P: int, rng) -> list:
    """The validity mask of each shard under one layout."""
    masks = [np.zeros(CAP, np.bool_) for _ in range(P)]
    for s, m in enumerate(masks):
        if layout == "packed":  # a compacted prefix, the same every shard
            m[:1000] = True
        elif layout == "unequal":  # compacted, another count a shard
            m[:300 + 700 * s] = True
        elif layout == "holes":  # a where() without a resize after it
            m[:1500] = rng.random(1500) < 0.6
            m[1499] = True
        elif layout == "last_slot":  # the reach is the capacity
            m[:200] = True
            m[CAP - 1] = s == P - 1
        elif layout == "empty_shard":  # at P = 1 the batch is empty
            m[:900] = s != 0
        elif layout == "full":
            m[:] = True
        else:
            assert layout == "all_empty"
    return masks


LAYOUTS = ("packed", "unequal", "holes", "last_slot", "empty_shard",
           "all_empty", "full")


def _build(layout: str, P: int, seed: int = 7):
    """A sharded batch of every column type under ``layout``, and the
    logical rows it holds, in slot order."""
    rng = np.random.default_rng(seed)
    masks = _masks(layout, P, rng)
    valid = np.concatenate(masks)
    n = int(valid.sum())
    table = {
        "k": rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32),
        "v": rng.standard_normal(n).astype(np.float32),
        "big": rng.integers(-(2**62), 2**62, n).astype(np.int64),
        "x": rng.standard_normal(n).astype(np.float64),
        "word": np.array([f"w{i % 97}" for i in range(n)], object),
    }
    dictionary = StringDictionary()
    phys, _ = encode_table(SCHEMA, table, dictionary)
    sharding = (partition_sharding(make_mesh(P)) if P > 1
                else jax.sharding.SingleDeviceSharding(jax.devices()[0]))
    data = {}
    for name, vals in phys.items():
        # stale values in the invalid slots, as a device batch has
        padded = rng.integers(0, 2**31, P * CAP).astype(vals.dtype)
        padded[valid] = vals
        data[name] = jax.device_put(padded, sharding)
    return (ColumnBatch(data, jax.device_put(valid, sharding)), table,
            dictionary, masks)


def _traced():
    events = EventLog()
    return Tracer(events), MetricsRegistry(), events


def _spans(events, name):
    return [e for e in events.events()
            if e["kind"] == "span" and e["name"] == name]


def _assert_tables_equal(got, want):
    assert sorted(got) == sorted(want)
    for name in want:
        assert got[name].dtype == want[name].dtype, name
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


@pytest.mark.parametrize("P", [1, 4])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_fetch_is_the_untrimmed_fetch(layout, P, low_gate):
    batch, table, dictionary, masks = _build(layout, P)
    tracer, metrics, events = _traced()
    valid, host, extras, rows = batch.fetch_host(tracer=tracer, metrics=metrics)

    # what was measured on the device is what NumPy counts on the mask
    assert isinstance(rows, ShardRows)
    assert rows.counts == tuple(int(m.sum()) for m in masks)
    assert rows.extents == tuple(
        int(np.flatnonzero(m)[-1]) + 1 if m.any() else 0 for m in masks)
    assert rows.packed == (layout not in ("holes", "last_slot"))
    # one tier for every shard, the smallest rung that holds the reach
    tiers = trim_tiers(CAP)
    assert rows.tier == min(t for t in tiers if t >= max(rows.extents))
    assert len(valid) == P * rows.tier
    assert all(len(a) == P * rows.tier for a in host.values())
    assert extras == []

    # shard s of the fetched arrays is the head of shard s of the batch
    whole = np.asarray(jax.device_get(batch.valid)).reshape(P, CAP)
    np.testing.assert_array_equal(
        valid.reshape(P, rows.tier), whole[:, :rows.tier])
    assert int(valid.sum()) == sum(rows.counts)

    # the rows, by the slices where there is no hole and by the mask
    _assert_tables_equal(
        decode_physical_table(SCHEMA, valid, host, dictionary), table)
    if rows.packed:
        _assert_tables_equal(
            decode_physical_table(SCHEMA, rows.slices(), host, dictionary),
            table)

    # the bytes: the span, the counter and the arrays agree
    copied = valid.nbytes + sum(a.nbytes for a in host.values())
    (copy,) = _spans(events, "fetch_copy")
    assert copy["bytes"] == copied == metrics.total("d2h_bytes")
    assert copy["capacity"] == P * CAP
    row_bytes = 1 + sum(a.dtype.itemsize for a in host.values())
    assert copied == row_bytes * P * rows.tier
    assert metrics.total("d2h_bytes_trimmed") == row_bytes * P * (CAP - rows.tier)
    (trim,) = _spans(events, "fetch_trim")
    assert trim["cat"] == "readback"
    assert (trim["capacity"], trim["shards"], trim["tier"]) == (P * CAP, P, rows.tier)
    assert trim["extent_max"] == max(rows.extents)
    assert trim["count"] == sum(rows.counts)
    full = layout in ("last_slot", "full")
    assert trim["trimmed"] == int(not full)
    assert (rows.tier == CAP) == full
    # the copy accounts for itself, the wait and the trim say seconds
    # alone (obs/span.py)
    assert {"user_s", "sys_s"} <= set(copy)
    for span in (trim, *_spans(events, "fetch_wait")):
        assert "user_s" not in span


@pytest.mark.parametrize("P", [1, 4])
def test_a_second_fetch_at_the_same_tier_compiles_nothing(P, low_gate):
    # 1000 and 1010 valid rows lie on one rung of the ladder (1024)
    first, *_ = _build("packed", P)
    other = ColumnBatch(first.data, first.valid.at[1000:1010].set(True))
    _, metrics, _ = _traced()
    first.fetch_host(metrics=metrics)
    compiled = metrics.total("xla_compiles")
    seconds = metrics.total("xla_compile_s")
    assert compiled <= 2  # the extent and the trim programs, if new here
    for again in (first, other, first):
        valid, _, _, rows = again.fetch_host(metrics=metrics)
        assert rows.tier == 1024 and len(valid) == P * 1024
    assert metrics.total("xla_compiles") == compiled
    assert metrics.total("xla_compile_s") == seconds


@pytest.mark.parametrize("P", [1, 4])
def test_a_new_tier_is_one_compile_of_the_trim_program(P, low_gate):
    batch, *_ = _build("packed", P, seed=11)
    _, metrics, _ = _traced()
    batch.fetch_host(metrics=metrics)
    before = metrics.total("xla_compiles")
    # a reach no other test of this file has: another rung, another program
    longer = ColumnBatch(batch.data, batch.valid.at[:2777].set(True))
    _, _, _, rows = longer.fetch_host(metrics=metrics)
    assert rows.tier == min(t for t in trim_tiers(CAP) if t >= 2777)
    assert metrics.total("xla_compiles") == before + 1


@pytest.mark.parametrize("P", [1, 4])
def test_under_the_gate_the_fetch_is_todays(P):
    """The gate as it ships: this batch's whole copy is P x 4096 slots
    of 41 B, far under 16 MiB, so nothing is asked and nothing is cut."""
    batch, table, dictionary, _ = _build("packed", P)
    whole = P * CAP * 41
    assert whole < batch_mod.TRIM_MIN_BYTES
    tracer, metrics, events = _traced()
    valid, host, extras, rows = batch.fetch_host(tracer=tracer, metrics=metrics)
    assert rows is None and len(valid) == P * CAP
    assert [e["name"] for e in events.events() if e["kind"] == "span"] == [
        "fetch_wait", "fetch_copy"]
    assert metrics.total("d2h_bytes") == whole
    assert metrics.total("d2h_bytes_trimmed") == 0
    assert metrics.total("xla_compiles") == 0
    _assert_tables_equal(
        decode_physical_table(SCHEMA, valid, host, dictionary), table)


def test_over_the_gate_as_it_ships_the_padding_stays():
    """No lowered gate: 2^21 slots of 9 B are over 16 MiB."""
    n, cap = 1000, 1 << 21
    valid = np.zeros(cap, np.bool_)
    valid[:n] = True
    k = np.arange(cap, dtype=np.int32)
    batch = ColumnBatch(
        {"k": jax.numpy.asarray(k), "v": jax.numpy.asarray(k.astype(np.float32))},
        jax.numpy.asarray(valid))
    assert cap * 9 >= batch_mod.TRIM_MIN_BYTES
    tracer, metrics, events = _traced()
    got_valid, host, _, rows = batch.fetch_host(tracer=tracer, metrics=metrics)
    assert rows == ShardRows(1024, (n,), (n,))  # 2^21 / 2^11 is a rung
    assert len(got_valid) == 1024 and int(got_valid.sum()) == n
    np.testing.assert_array_equal(host["k"], k[:1024])
    assert metrics.total("d2h_bytes") == 9 * 1024
    assert metrics.total("d2h_bytes_trimmed") == 9 * (cap - 1024)
    assert len(_spans(events, "fetch_trim")) == 1


def test_extras_ride_the_trimmed_copy(low_gate):
    batch, *_ = _build("packed", 4)
    miss = [jax.numpy.asarray([0, 3], jax.numpy.int32),
            jax.numpy.asarray(7, jax.numpy.int32)]
    valid, _, extras, rows = batch.fetch_host(extra=miss)
    assert len(valid) == 4 * rows.tier < 4 * CAP
    assert [np.asarray(e).tolist() for e in extras] == [[0, 3], 7]


def test_host_arrays_keep_todays_fetch():
    """A batch that is not on a device (NumPy arrays) is not asked."""
    valid = np.zeros(1 << 22, np.bool_)
    valid[:5] = True
    batch = ColumnBatch({"k": np.arange(1 << 22, dtype=np.int32)}, valid)
    got_valid, host, _, rows = batch.fetch_host()
    assert rows is None and len(got_valid) == 1 << 22 == len(host["k"])


@pytest.mark.parametrize(
    "capacity", [1, 7, 8, 9, 100, 1000, 4096, 2**24 + 1, 2**25, 2**26])
def test_the_tier_ladder_is_bounded_and_monotone(capacity):
    tiers = trim_tiers(capacity)
    assert tiers[-1] == capacity
    assert all(a < b for a, b in zip(tiers, tiers[1:]))
    assert len(tiers) <= 4 * math.log2(capacity) + 1
    assert all(t % 8 == 0 for t in tiers[:-1])
    assert tiers[0] <= 8
    # never more than a fifth over the reach (and the rounding to 8 rows)
    for low, high in zip(tiers, tiers[1:]):
        assert high <= (low + 1) * 2 ** 0.25 + 8
    # the halves of a power of two are rungs to the row (sort-1c: 2^25 of 2^26)
    if capacity & (capacity - 1) == 0:
        assert {capacity >> k for k in range(capacity.bit_length() - 3)} <= set(tiers)


# -- through the query surface -----------------------------------------------

def _queries(ctx, rng):
    n = 6000
    k = (rng.integers(0, 300, n) - 1).astype(np.int32)  # a negative key: sort path
    v = rng.standard_normal(n).astype(np.float32)
    big = rng.integers(-(2**40), 2**40, n).astype(np.int64)
    x = rng.standard_normal(n).astype(np.float64)
    words = np.array([f"w{i}" for i in rng.integers(0, 50, n)], object)
    base = {"k": k, "v": v}
    return {
        "group_by": ctx.from_arrays(base).group_by(
            "k", {"c": ("count", None), "s": ("sum", "v")}),
        "order_by": ctx.from_arrays(base).order_by(["k"]),
        "where": ctx.from_arrays(base).where(lambda c: c["k"] % 3 == 0),
        "group_where": ctx.from_arrays(base).group_by(
            "k", {"c": ("count", None)}).where(lambda c: c["c"] % 2 == 0),
        "wide_sort": ctx.from_arrays(
            {"big": big, "x": x, "k": k}).order_by(["big"]),
        "words": ctx.from_arrays({"word": words, "v": v}).group_by(
            "word", {"c": ("count", None)}),
        "apply_host": ctx.from_arrays(base).group_by(
            "k", {"c": ("count", None)}).apply_host(lambda t, _i: t),
    }


def _collect_all(P, monkeypatch, gate):
    monkeypatch.setattr(batch_mod, "TRIM_MIN_BYTES", gate)
    ctx = DryadContext(num_partitions_=P)
    answers = {name: q.collect()
               for name, q in _queries(ctx, np.random.default_rng(5)).items()}
    return ctx, answers


@pytest.fixture(scope="module", params=[1, 4])
def both_ways(request):
    """Every query of ``_queries`` collected twice at one P: with the
    gate lowered so every fetch asks, and with no fetch asking."""
    with pytest.MonkeyPatch.context() as mp:
        trimmed_ctx, trimmed = _collect_all(request.param, mp, 1 << 10)
        _, whole = _collect_all(request.param, mp, 1 << 60)
    return request.param, trimmed_ctx, trimmed, whole


@pytest.mark.parametrize("name", [
    "group_by", "order_by", "where", "group_where", "wide_sort", "words",
    "apply_host"])
def test_a_query_answers_as_with_the_whole_fetch(both_ways, name):
    _, _, trimmed, whole = both_ways
    # row for row, in the same order: both decode shard after shard
    _assert_tables_equal(trimmed[name], whole[name])
    assert len(next(iter(whole[name].values()))) > 0


def test_the_queries_fetches_were_trimmed(both_ways):
    P, ctx, _, _ = both_ways
    spans = [e for e in ctx.events.events() if e["kind"] == "span"]
    trims = [e for e in spans if e["name"] == "fetch_trim"]
    decodes = [e for e in spans if e["name"] == "decode"]
    assert len(trims) >= len(decodes) == 7
    # the exchanges' slack is cut off; where() fills its capacity, the
    # dense word count and apply_host's repacked answer nearly do
    assert sum(t["trimmed"] for t in trims) >= 4
    for d in decodes:
        assert d["fetched"] <= d["capacity"] and d["shards"] == P
        assert d["shard_rows_min"] <= d["shard_rows_max"]
    m = ctx.executor.metrics
    copies = [e for e in spans if e["name"] == "fetch_copy"]
    assert sum(c["bytes"] for c in copies) == m.total("d2h_bytes")
    # on a mesh an exchange's slack is most of what a fetch would copy;
    # on one partition no exchange runs, so there is no slack to cut
    if P == 1:
        assert 0 < m.total("d2h_bytes_trimmed") < m.total("d2h_bytes")
    else:
        assert m.total("d2h_bytes_trimmed") > m.total("d2h_bytes")


def test_a_full_sorted_answer_on_one_partition_is_copied_whole(monkeypatch):
    """An ``order_by`` on one partition keeps its input's capacity (no
    exchange, no slack), so a table that fills it reaches the last slot:
    the fetch asks (the extent program), finds nothing to cut, dispatches
    no trim program and copies the columns as they are."""
    monkeypatch.setattr(batch_mod, "TRIM_MIN_BYTES", 1 << 10)
    asked = []
    real = batch_mod._egress_program

    def spy(key, *args, **kwargs):
        asked.append(key[0])
        return real(key, *args, **kwargs)

    monkeypatch.setattr(batch_mod, "_egress_program", spy)
    rng = np.random.default_rng(33)
    table = {"k": rng.permutation(CAP).astype(np.int32) - 100,
             "v": rng.standard_normal(CAP).astype(np.float32)}
    ctx = DryadContext(num_partitions_=1)
    answer = ctx.from_arrays(table).order_by(["k"]).collect()
    order = np.argsort(table["k"], kind="stable")
    _assert_tables_equal(answer, {n: c[order] for n, c in table.items()})
    spans = [e for e in ctx.events.events() if e["kind"] == "span"]
    (trim,) = [e for e in spans if e["name"] == "fetch_trim"]
    assert (trim["trimmed"], trim["tier"], trim["capacity"]) == (0, CAP, CAP)
    assert (trim["extent_max"], trim["count"], trim["shards"]) == (CAP, CAP, 1)
    assert asked == ["dryad_egress_extent"]
    (decode,) = [e for e in spans if e["name"] == "decode"]
    assert decode["rows"] == decode["fetched"] == decode["capacity"] == CAP
    (copy,) = [e for e in spans if e["name"] == "fetch_copy"]
    assert copy["bytes"] == 9 * CAP  # key, payload and the validity byte
    assert ctx.executor.metrics.total("d2h_bytes_trimmed") == 0


@pytest.mark.parametrize("P", [1, 4])
def test_a_miss_still_raises_on_the_trimmed_fetch(P, low_gate):
    """The deferred miss counters ride the trimmed copy as they rode the
    whole one: keys moved out of the ingest-time range after the query
    was defined fail the job, they are not dropped."""
    from dryad_tpu.exec.executor import StageFailedError

    rng = np.random.default_rng(1)
    ctx = DryadContext(num_partitions_=P)
    arrays = {"k": rng.integers(0, 20, 4000).astype(np.int32)}
    q = ctx.from_arrays(arrays).group_by("k", {"c": ("count", None)})
    arrays["k"][:] = arrays["k"] + 100
    with pytest.raises(StageFailedError, match="ingest-time range"):
        q.collect()
    # and an honest table of the same shape is answered
    honest = {"k": rng.integers(0, 20, 4000).astype(np.int32)}
    out = DryadContext(num_partitions_=P).from_arrays(honest).group_by(
        "k", {"c": ("count", None)}).collect()
    assert dict(zip(out["k"].tolist(), out["c"].tolist())) == {
        int(k): int(c) for k, c in enumerate(np.bincount(honest["k"]))}


@pytest.mark.parametrize("P", [1, 4])
def test_to_store_writes_the_partitions_of_a_trimmed_fetch(P, low_gate, tmp_path):
    """``to_store`` cuts the fetched arrays into partitions by the slots
    that came back, not by the batch's capacity."""
    rng = np.random.default_rng(9)
    k = (rng.integers(0, 300, 6000) - 1).astype(np.int32)
    ctx = DryadContext(num_partitions_=P)
    q = ctx.from_arrays({"k": k}).group_by("k", {"c": ("count", None)})
    q.to_store(str(tmp_path / "counts"))
    trims = [e for e in ctx.events.events()
             if e["kind"] == "span" and e["name"] == "fetch_trim"]
    assert [t["trimmed"] for t in trims] == [1]
    back = DryadContext(num_partitions_=P).from_store(
        str(tmp_path / "counts")).collect()
    assert dict(zip(back["k"].tolist(), back["c"].tolist())) == {
        int(key) - 1: int(c) for key, c in enumerate(np.bincount(k + 1))}


def _releases(ctx) -> int:
    return sum(
        e["kind"] == "span" and e["name"] == "release"
        for e in ctx.events.events()
    )


@pytest.mark.parametrize("how", ["collect", "async"])
def test_the_job_that_ingested_lets_go_of_its_host_arrays(how, monkeypatch):
    """The host arrays an ingest handed to ``device_put`` are dropped
    (a generation-0 collection, which jax's own hook turns into the
    release) in the job that copied them, under a ``release`` span
    before the fetch (what a stage that was waited for has used) and
    another when the job is done (the rest, and the host copies of the
    answer's arrays); a requery, which copied nothing, has none."""
    collected = []
    monkeypatch.setattr(
        "dryad_tpu.exec.inputs.gc.collect", lambda gen: collected.append(gen)
    )
    ctx = DryadContext(num_partitions_=1)
    q = ctx.from_arrays({"k": np.arange(64, dtype=np.int32)}).order_by(["k"])

    def run():
        return q.collect() if how == "collect" else ctx.run_to_host_async(q)()

    np.testing.assert_array_equal(run()["k"], np.arange(64))
    assert (_releases(ctx), collected) == (2, [0, 0])
    spans = [e for e in ctx.events.events() if e["kind"] == "span"]
    names = [e["name"] for e in spans]
    first, last = [i for i, n in enumerate(names) if n == "release"]
    assert names[first + 1] == "fetch_wait"
    if how == "collect":
        # the job's last acts: the answer's device arrays dropped (and
        # the host copies of them with those), then what is left let go
        assert names[last - 3:] == [
            "decode", "drop", "drop", "release", "collect"]
        assert spans[last]["parent_id"] == spans[-1]["span_id"]
    else:  # the closure keeps the device arrays: the host copies alone
        assert names[last - 2:] == ["decode", "drop", "release"]
    before = len(ctx.events.events())
    np.testing.assert_array_equal(run()["k"], np.arange(64))
    assert (_releases(ctx), collected) == (2, [0, 0])
    again = [e["name"] for e in ctx.events.events()[before:]
             if e["kind"] == "span"]
    # a requery drops too: the host copies, and with ``collect`` the arrays
    assert again.count("drop") == (2 if how == "collect" else 1)
