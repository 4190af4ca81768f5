"""The ingest edge since PR 36: a table takes its ``P * capacity``
layout in ONE pass, written into staging buffers the context keeps
(``parallel.distribute.lay_out`` / ``StagingPool``).

What is held to here: the staged columns are the parent's
``encode_table`` + pad byte for byte; a buffer is used again only when
its copy is done and never when the backend made the device array OF
it (the CPU client aliases a 64-byte aligned source: the first table's
device batch must keep its own rows whatever a later ingest writes); a
buffer serves any column that fits; nothing of the context pins a
device array once its copy is done; one ``encode`` span a table with ``pack`` inside.
"""

import gc
import threading
import weakref

import jax
import numpy as np
import pytest

from dryad_tpu import DryadConfig, DryadContext
from dryad_tpu.columnar.batch import encode_table
from dryad_tpu.columnar.schema import (
    BYTES,
    ColumnType,
    Schema,
    StringDictionary,
    bytes_to_words,
)
from dryad_tpu.exec.events import EventLog
from dryad_tpu.obs.metrics import MetricsRegistry
from dryad_tpu.obs.span import Tracer
from dryad_tpu.parallel import distribute as D
from dryad_tpu.parallel.mesh import make_mesh

ROWS = 1003  # no multiple of 4: the last partition is short, the others padded


def plain(rng, rows=ROWS):
    return {"k": rng.integers(-(2**31), 2**31 - 1, rows).astype(np.int32),
            "v": rng.standard_normal(rows).astype(np.float32)}


def casts(rng, rows=ROWS):
    # int64 -> INT32, float64 -> FLOAT32, int -> BOOL, int64 -> UINT32; and the
    # split types, which keep ``encode_physical``
    return {"k": rng.integers(-1000, 1000, rows),
            "v": rng.standard_normal(rows),
            "flag": rng.integers(0, 3, rows),
            "u": rng.integers(0, 2**32 - 1, rows),
            "wide": rng.integers(-(2**62), 2**62, rows),
            "d": rng.standard_normal(rows),
            "s": np.array([f"w{i % 41}" for i in range(rows)], object)}


CASTS_SCHEMA = Schema([
    ("k", ColumnType.INT32), ("v", ColumnType.FLOAT32), ("flag", ColumnType.BOOL),
    ("u", ColumnType.UINT32), ("wide", ColumnType.INT64),
    ("d", ColumnType.FLOAT64), ("s", ColumnType.STRING)])


def records(rng, rows=ROWS):
    return {"key": rng.integers(0, 256, (rows, 10), dtype=np.uint8),
            "payload": rng.integers(0, 256, (rows, 7), dtype=np.uint8),
            "n": np.arange(rows, dtype=np.int32)}


TABLES = {
    "int32_f32": (plain, Schema([("k", ColumnType.INT32), ("v", ColumnType.FLOAT32)])),
    "casts": (casts, CASTS_SCHEMA),
    "bytes": (records, Schema([("key", BYTES(10)), ("payload", BYTES(7)),
                               ("n", ColumnType.INT32)])),
}


def parents_layout(schema, table, P, cap=None):
    """What the parent of PR 36 put on the device: ``encode_table`` at n
    rows, then ``np.zeros(P * cap)`` + a slice a partition."""
    phys, n = encode_table(schema, table, StringDictionary())
    per = -(-n // P) if n else 1
    cap = cap if cap is not None else per
    data = {}
    for c, a in phys.items():
        pad = np.zeros(P * cap, a.dtype)
        for p in range(P):
            lo, hi = min(p * per, n), min((p + 1) * per, n)
            pad[p * cap : p * cap + hi - lo] = a[lo:hi]
        data[c] = pad
    valid = np.zeros(P * cap, np.bool_)
    for p in range(P):
        valid[p * cap : p * cap + min((p + 1) * per, n) - min(p * per, n)] = True
    return data, valid


def spans(ctx, *names):
    return [e for e in ctx.events.events()
            if e["kind"] == "span" and e["name"] in names]


@pytest.mark.parametrize("P", [1, 4])
@pytest.mark.parametrize("kind", sorted(TABLES))
@pytest.mark.parametrize("cap", [None, 300])
def test_the_staged_columns_are_the_parents_byte_for_byte(P, kind, cap):
    make, schema = TABLES[kind]
    table = make(np.random.default_rng(36))
    if cap is not None and cap * P < ROWS:
        cap = ROWS  # a capacity with room behind every partition's rows
    pool = D.StagingPool()
    # dirty buffers first: only the layout's own zeros may reach the device
    for _ in range(2):
        batch = D.from_host_table(schema, table, make_mesh(P), cap,
                                  StringDictionary(), pool=pool)
        for arena in pool._idle:
            arena.mem[:] = 0xAB if arena.landed() else arena.mem
    data, valid = parents_layout(schema, table, P, cap)
    assert sorted(batch.data) == sorted(data)
    np.testing.assert_array_equal(np.asarray(batch.valid), valid)
    for c, want in data.items():
        got = np.asarray(batch.data[c])
        assert got.dtype == want.dtype
        assert got.tobytes() == want.tobytes(), c


@pytest.mark.parametrize("P", [1, 4])
def test_a_physical_table_and_a_table_without_a_pool_lay_out_the_same(P):
    schema = TABLES["casts"][1]
    table = casts(np.random.default_rng(3))
    phys, _ = encode_table(schema, table, StringDictionary())
    mesh = make_mesh(P)
    want, valid = parents_layout(schema, table, P)
    for batch in (D.from_physical_table(phys, mesh, pool=D.StagingPool()),
                  D.from_physical_table(phys, mesh),
                  D.from_host_table(schema, table, mesh,
                                    dictionary=StringDictionary())):
        np.testing.assert_array_equal(np.asarray(batch.valid), valid)
        for c, a in want.items():
            assert np.asarray(batch.data[c]).tobytes() == a.tobytes(), c
    with pytest.raises(ValueError, match="partition_capacity"):
        D.from_physical_table(phys, mesh, partition_capacity=1)
    with pytest.raises(ValueError, match="ragged"):
        D.from_host_table(TABLES["int32_f32"][1],
                          {"k": np.arange(4), "v": np.arange(5.0)}, mesh)
    empty = D.from_host_table(TABLES["bytes"][1], records(np.random.default_rng(0), 0),
                              mesh)
    assert empty.capacity == P and not np.asarray(empty.valid).any()
    with pytest.raises(ValueError, match=r"BYTES\(10\)"):
        D.from_host_table(TABLES["bytes"][1], {
            "key": np.zeros((0, 9), np.uint8), "payload": np.zeros((0, 7), np.uint8),
            "n": np.zeros(0, np.int32)}, mesh)


@pytest.mark.parametrize("P", [1, 4])
@pytest.mark.parametrize("kind", sorted(TABLES))
def test_a_second_table_is_warm_or_the_buffers_were_given_up(P, kind):
    """Whichever the backend did with a buffer (copied it: it is used
    again; aliased it: the pool has forgotten it), the FIRST table's
    device batch requeries to its own answer after the second ingest."""
    make, schema = TABLES[kind]
    rng = np.random.default_rng(P)
    first, second = make(rng), make(rng)
    key = "key" if kind == "bytes" else "k"
    ctx = DryadContext(num_partitions_=P)
    q1 = ctx.from_arrays(first, schema=schema).order_by([key])
    a1 = q1.collect()
    held = ctx.inputs.staging.held_bytes()
    a2 = ctx.from_arrays(second, schema=schema).order_by([key]).collect()
    (e1, e2) = spans(ctx, "encode")
    assert e1["warm_bytes"] == 0 and e1["bytes_out"] == e2["bytes_out"]
    assert e1["rows"] == ROWS and e1["capacity"] == P * -(-ROWS // P)
    assert 0 <= e2["warm_bytes"] <= held
    assert e2["warm_bytes"] > 0 or held < e2["bytes_out"]
    metrics = ctx.executor.metrics
    assert metrics.total("ingest_staged_bytes") == 2 * e1["bytes_out"]
    assert metrics.total("ingest_warm_bytes") == e2["warm_bytes"]
    # the first table's cached device batch still holds the first table
    before = len(spans(ctx, "encode"))
    again = q1.collect()
    assert len(spans(ctx, "encode")) == before  # from the device cache
    for c in a1:
        np.testing.assert_array_equal(again[c], a1[c])
    order = (np.lexsort(first["key"].T[::-1]) if kind == "bytes"
             else np.argsort(np.asarray(first["k"]), kind="stable"))
    np.testing.assert_array_equal(again[key], np.asarray(first[key])[order])
    assert not np.array_equal(a1[key], a2[key])


def aligned(nbytes):
    raw = np.empty(nbytes + 64, np.uint8)
    at = -raw.ctypes.data % 64
    return raw[at : at + nbytes]


def test_a_buffer_the_device_array_aliases_never_returns_to_the_pool():
    """The trap itself: jax 0.9.0's CPU client makes a device array OF
    a 64-byte aligned host array.  Such an arena is forgotten, so the
    next table is written elsewhere and the device array keeps its rows."""
    pool = D.StagingPool()
    for nbytes in (4 * 4096, 4 * 4096, 4096):  # k, v, valid at P = 1
        arena = D._Arena(nbytes)
        arena.mem = aligned(nbytes)
        pool._idle.append(arena)
    schema = TABLES["int32_f32"][1]
    first, second = (plain(np.random.default_rng(s), 4096) for s in (1, 2))
    mesh = make_mesh(1)
    b1 = D.from_host_table(schema, first, mesh, pool=pool)
    aliased = b1.data["k"].unsafe_buffer_pointer() % 64 == 0 and not pool._idle
    b2 = D.from_host_table(schema, second, mesh, pool=pool)
    for c in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(b1.data[c]), first[c])
        np.testing.assert_array_equal(np.asarray(b2.data[c]), second[c])
    if aliased:  # every buffer was given up; the second table allocated
        # (and the pool holds those of ITS buffers that the backend copied)
        assert pool.held_bytes() <= 9 * 4096
        assert all(a.mem.ctypes.data % 64 for a in pool._idle)
    # an arena whose array is not ready is not offered; once it is, the
    # arena is, and the pool has let go of the array
    arena = D._Arena(64)

    class Flying:
        ready = False

        def is_deleted(self):
            return False

        def is_ready(self):
            return self.ready

    arena.sent_to = Flying()
    assert not arena.landed()
    pool.clear()
    pool._idle.append(arena)
    assert pool.take(64)[1] is False and pool.held_bytes() == 64
    arena.sent_to.ready = True
    assert pool.take(64) == (arena, True) and pool.held_bytes() == 0
    assert arena.sent_to is None
    # nobody can ask a deleted array (``is_ready`` of one would crash the
    # process): its arena is not offered again, and two trims drop it
    d = jax.device_put(np.arange(8))
    arena.sent_to = d
    d.delete()
    pool._idle.append(arena)
    assert not arena.landed() and pool.take(64)[1] is False
    pool.trim()
    pool.trim()
    assert pool.held_bytes() == 0


def test_an_array_dropped_while_its_copy_is_in_flight_keeps_its_buffer():
    """Gone is not landed: ``device_put`` returns before the source is
    read (the CPU client copies a 64 MiB source in another thread), so
    a holder that drops the array at once, with a program that reads it
    dispatched, must not free the buffer for the next table: the rows
    written there would be the rows the program sums."""
    pool, n = D.StagingPool(), 1 << 24
    total = jax.jit(lambda x: x.sum())
    total(jax.numpy.zeros(n, np.int32)).block_until_ready()
    for _ in range(3):
        arena, _ = pool.take(4 * n)
        column = arena.mem[: 4 * n].view(np.int32)
        column[:] = 1
        d = jax.device_put(column)
        pool.sent(arena, d)
        ones = total(d)
        del d
        again, warm = pool.take(4 * n)  # the next table's buffer
        again.mem[:] = 0xFF
        assert int(ones) == n
        assert warm == (again is arena)
        # the program has run, so the copy is done: the buffer is idle,
        # and the pool keeps nothing of the array
        if not warm and pool._idle:
            assert pool.take(4 * n) == (arena, True) and arena.sent_to is None
        pool.clear()


@pytest.mark.parametrize("P", [1, 4])
@pytest.mark.parametrize("cache_bytes", [None, 0])
def test_two_tables_in_flight_both_answer_right(P, cache_bytes):
    """``run_many_to_host_async`` ingests the second table before
    anything waited for the first: a buffer still in flight is not
    offered, and a buffer is never handed to two tables at once.  With
    the device cache off nothing but the pool holds the first table's
    device arrays once its job is dispatched."""
    rng = np.random.default_rng(7)
    config = None if cache_bytes is None else DryadConfig(device_cache_bytes=cache_bytes)
    ctx = DryadContext(num_partitions_=P, config=config)
    for _ in range(3):  # the pool fills on the first pass, serves the others
        t1, t2 = plain(rng), plain(rng)
        fetch1, fetch2 = ctx.run_many_to_host_async([
            ctx.from_arrays(t1).order_by(["k"]), ctx.from_arrays(t2).order_by(["k"])])
        t3 = plain(rng)
        fetch3 = ctx.run_to_host_async(ctx.from_arrays(t3).order_by(["k"]))
        for table, answer in ((t2, fetch2()), (t3, fetch3()), (t1, fetch1())):
            order = np.argsort(table["k"], kind="stable")
            np.testing.assert_array_equal(answer["k"], table["k"][order])
            np.testing.assert_array_equal(answer["v"], table["v"][order])
    encodes = spans(ctx, "encode")
    assert len(encodes) == 9 and encodes[0]["warm_bytes"] == 0
    # what one job staged, and no more, outlives it
    assert ctx.inputs.staging.held_bytes() <= 3 * encodes[0]["bytes_out"]
    # every program has run, so every copy is done and its array let go of
    assert all(a.landed() and a.sent_to is None for a in ctx.inputs.staging._idle)


def test_threads_share_the_pool_and_no_buffer_is_handed_out_twice():
    import sys

    pool, mesh = D.StagingPool(), make_mesh(1)
    schema = TABLES["int32_f32"][1]
    wrong, done = [], []

    def ingest(seed):
        rng = np.random.default_rng(seed)
        for _ in range(20):
            table = plain(rng, 257)
            batch = D.from_host_table(schema, table, mesh, pool=pool)
            for c in ("k", "v"):
                if not np.array_equal(np.asarray(batch.data[c]), table[c]):
                    wrong.append((seed, c))
        done.append(seed)

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=ingest, args=(s,)) for s in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(before)
    assert not any(t.is_alive() for t in threads)
    assert sorted(done) == list(range(8)) and not wrong


def test_a_smaller_table_after_a_larger_one_is_served_from_the_larger_buffer():
    pool, mesh = D.StagingPool(), make_mesh(4)
    schema = TABLES["int32_f32"][1]
    log = EventLog(None)
    tracer, metrics = Tracer(log), MetricsRegistry()
    rng = np.random.default_rng(5)
    large, small = plain(rng, 4001), plain(rng, 1003)
    D.from_host_table(schema, large, mesh, tracer=tracer, metrics=metrics, pool=pool)
    gc.collect()
    # buffers the backend kept for itself aside, the pool holds the large table's
    held = sorted(a.mem.nbytes for a in pool._idle)
    batch = D.from_host_table(schema, small, mesh, tracer=tracer, metrics=metrics,
                              pool=pool)
    e1, e2 = [e for e in log.events() if e["name"] == "encode"]
    assert e1["bytes_out"] == 9 * 4004 and e2["bytes_out"] == 9 * 1004
    # every column of the small table that found an idle buffer took it: the
    # smallest that fits, each viewed at the column's own dtype and length,
    # in the order ``lay_out`` asks: the columns, then ``valid`` (sorted,
    # this model was off whenever the backend had kept the smallest buffer)
    need = [4 * 1004, 4 * 1004, 1004]
    served = 0
    for nbytes in need:
        fit = [h for h in held if h >= nbytes]
        if fit:
            held.remove(min(fit))
            served += nbytes
    assert e2["warm_bytes"] == served
    assert metrics.total("ingest_warm_bytes") == served
    np.testing.assert_array_equal(np.asarray(batch.data["k"]).reshape(4, 251)[0],
                                  small["k"][:251])
    # the trim keeps what was used since the trim before, and only that
    pool.trim()
    kept = pool.held_bytes()
    pool.trim()
    assert pool.held_bytes() == 0 or kept == 0


def test_close_and_rebuild_mesh_leave_the_pool_empty_and_nothing_pins_a_device_array():
    ctx = DryadContext(num_partitions_=4)
    table = plain(np.random.default_rng(11), 4096)
    q = ctx.from_arrays(table).order_by(["k"])
    answer = q.collect()
    assert ctx.inputs.holds(q.node.inputs[0].id)[2]
    ref = weakref.ref(ctx.inputs.device_batch(q.node.inputs[0]).data["k"])
    assert ref() is not None
    # the pool held the device arrays until their copies were done, the
    # job's end at the latest: with the device cache's entry gone nothing
    # of the context keeps the array
    ctx.inputs.evict()
    gc.collect()
    assert ref() is None
    ctx.close()
    assert ctx.inputs.staging.held_bytes() == 0 and not ctx.inputs.staging._idle
    np.testing.assert_array_equal(q.collect()["k"], answer["k"])  # still usable
    ctx.rebuild_mesh([jax.devices()[3].id])
    assert ctx.inputs.staging.held_bytes() == 0
    np.testing.assert_array_equal(
        ctx.from_arrays(table).order_by(["k"]).collect()["k"], answer["k"])


@pytest.mark.parametrize("rows", [0, 1, 5, (1 << 14) * 17 + 3])
@pytest.mark.parametrize("width", [1, 4, 10, 90])
def test_bytes_to_words_out_equals_bytes_to_words(rows, width):
    a = np.random.default_rng(width).integers(0, 256, (rows, width), dtype=np.uint8)
    want = bytes_to_words(a, width)
    words = -(-width // 4)
    # rows of a larger buffer, as the layout hands them in
    buffers = [np.full(rows + 9, 0xDEADBEEF, np.uint32) for _ in range(words)]
    got = bytes_to_words(a, width, out=[b[4 : 4 + rows] for b in buffers])
    assert len(got) == len(want) == words
    for b, g, w in zip(buffers, got, want):
        np.testing.assert_array_equal(g, w)
        np.testing.assert_array_equal(b[4 : 4 + rows], w)
        assert (b[:4] == 0xDEADBEEF).all() and (b[4 + rows :] == 0xDEADBEEF).all()
    with pytest.raises(ValueError, match="uint32 arrays"):
        bytes_to_words(a, width, out=[np.zeros(rows + 1, np.uint32)] * words)
    with pytest.raises(ValueError, match="uint32 arrays"):
        bytes_to_words(a, width, out=[np.zeros(rows, np.int32)] * words)


@pytest.mark.parametrize("P", [1, 4])
def test_a_job_has_one_encode_span_a_table_and_pack_lies_inside_it(P, tmp_path):
    rng = np.random.default_rng(2)
    ctx = DryadContext(num_partitions_=P)
    ctx.from_arrays(records(rng)).order_by(["key"]).collect()
    ctx.from_arrays(plain(rng)).join(
        ctx.from_arrays({"k": np.arange(64, dtype=np.int32)}), "k").collect()
    text = tmp_path / "words.txt"
    text.write_text(" ".join(f"w{i % 13}" for i in range(900)))
    ctx.from_text(str(text)).group_by("word", {"n": ("count", None)}).collect()
    events = [e for e in ctx.events.events() if e["kind"] == "span"]
    by_id = {e["span_id"]: e for e in events}
    encodes = [e for e in events if e["name"] == "encode"]
    # one a table: the records, the join's two inputs, the text
    assert [e["rows"] for e in encodes] == [ROWS, ROWS, 64, 900]
    for e in encodes:
        assert {"capacity", "bytes_out", "warm_bytes", "user_s", "sys_s"} <= set(e)
        assert by_id[e["parent_id"]]["name"] == "bind"
        assert e["capacity"] % P == 0 and e["capacity"] >= e["rows"]
    packs = [e for e in events if e["name"] == "pack"]
    assert [(e["bytes"], e["rows"], e["bytes_out"]) for e in packs] == [
        (10 * ROWS, ROWS, 12 * ROWS), (7 * ROWS, ROWS, 8 * ROWS)]
    assert all(by_id[e["parent_id"]] is encodes[0] for e in packs)
    # the records' layout: 3 + 2 words, ``n`` and ``valid``, every slot
    assert encodes[0]["bytes_out"] == (4 * (3 + 2 + 1) + 1) * encodes[0]["capacity"]
    # a job that ingested still closes with two ``release`` spans
    assert len([e for e in events if e["name"] == "release"]) == 6


def test_the_store_binding_lays_out_through_the_same_place(tmp_path):
    ctx = DryadContext(num_partitions_=4)
    table = plain(np.random.default_rng(9), 2001)
    path = str(tmp_path / "t")
    ctx.to_store(ctx.from_arrays(table), path)
    first = ctx.from_store(path).order_by(["k"]).collect()
    second = ctx.from_store(path).order_by(["k"]).collect()
    order = np.argsort(table["k"], kind="stable")
    for answer in (first, second):
        np.testing.assert_array_equal(answer["k"], table["k"][order])
        np.testing.assert_array_equal(answer["v"], table["v"][order])
    encodes = spans(ctx, "encode")
    assert [e["rows"] for e in encodes] == [2001, 2001, 2001]
    # the table as handed in, then the store's parts twice (capacity in eights)
    assert all("warm_bytes" in e for e in encodes)
    assert [e["capacity"] % 8 for e in encodes[1:]] == [0, 0]
    assert encodes[1]["bytes_out"] == encodes[2]["bytes_out"] == 9 * encodes[1]["capacity"]
