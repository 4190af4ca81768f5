"""The fixed-width BYTES column (``columnar.schema.BYTES``): host form
``[rows, width]`` uint8 in and out, device form big-endian uint32
words; exact as an ``order_by`` / ``range_partition`` key over every
byte, carried as a payload by everything that carries columns, through
the engine at P = 1 and on the 4-device CPU mesh, each case against
``local_debug`` and against a NumPy ``lexsort`` written here."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dryad_tpu import BYTES, ColumnType, DryadContext, Schema
from dryad_tpu.columnar.schema import (
    bytes_to_words,
    parse_ctype,
    words_to_bytes,
)
from dryad_tpu.ops import sort as SORT
from dryad_tpu.utils.config import DryadConfig

ROWS = 1 << 11
WIDTHS = (1, 3, 4, 10, 90, 128)


def edge_bytes(width, rows=ROWS, seed=0):
    """Random bytes with 0x00 and 0xFF in every position of some row,
    rows of all NULs and all 0xFF, and trailing NULs."""
    a = np.random.default_rng([seed, width]).integers(
        0, 256, (rows, width), dtype=np.uint8)
    a[0], a[1] = 0, 255
    for j in range(width):
        a[2 + 2 * j, j] = 0
        a[3 + 2 * j, j] = 255
        a[300 + j, j:] = 0  # trailing NULs from position j on
    return a


def memcmp_order(key, descending=False, then=None):
    """The stable permutation into ``memcmp`` order of a ``[rows,
    width]`` uint8 key (then by an int column), by ``np.lexsort`` over
    the bytes themselves, last byte least significant."""
    cols = [key[:, j].astype(np.int64) for j in range(key.shape[1])]
    if descending:
        cols = [-c for c in cols]
    if then is not None:
        cols.append(then.astype(np.int64))
    return np.lexsort(cols[::-1])


def key_payload(key, width=90):
    """A payload that is a function of its key and of the position."""
    mix = (key.astype(np.uint32) * np.arange(1, key.shape[1] + 1, dtype=np.uint32)
           ).sum(axis=1, dtype=np.uint32)
    pos = np.arange(width, dtype=np.uint32)
    return ((mix[:, None] * np.uint32(2654435761) + pos * np.uint32(40503))
            >> np.uint32(13)).astype(np.uint8)


def same_bytes(got, want):
    assert got.dtype == np.uint8 and got.shape == want.shape
    assert got.tobytes() == np.ascontiguousarray(want).tobytes()


# -- the type and the two host passes -------------------------------------------

@pytest.mark.parametrize("width", WIDTHS)
def test_pack_and_unpack_round_trip(width):
    a = edge_bytes(width)
    words = bytes_to_words(a, width)
    assert len(words) == BYTES(width).words == -(-width // 4)
    assert all(w.dtype == np.uint32 and w.shape == (ROWS,) for w in words)
    # big-endian, zero-padded on the right
    want0 = int.from_bytes(bytes(a[7, :4]).ljust(4, b"\0"), "big")
    assert int(words[0][7]) == want0
    if width % 4:
        assert not np.any(words[-1] & np.uint32((1 << (8 * (4 - width % 4))) - 1))
    same_bytes(words_to_bytes(words, width), a)
    # more rows than one block of the blocked passes (enough blocks for
    # two threads to share them), and none
    big = edge_bytes(width, rows=(9 << 14) + 37, seed=1)
    same_bytes(words_to_bytes(bytes_to_words(big, width), width), big)
    none = np.zeros((0, width), np.uint8)
    assert words_to_bytes(bytes_to_words(none, width), width).shape == (0, width)


@pytest.mark.parametrize("width", WIDTHS)
def test_the_words_order_is_memcmp_order(width):
    rng = np.random.default_rng(width)
    a = rng.integers(0, 256, (4096, width), dtype=np.uint8)
    b = a.copy()
    # pairs that differ from some byte on, so ties on a prefix are common
    cut = rng.integers(0, width + 1, 4096)
    fresh = rng.integers(0, 256, (4096, width), dtype=np.uint8)
    for j in range(width):
        b[:, j] = np.where(j >= cut, fresh[:, j], a[:, j])
    wa, wb = bytes_to_words(a, width), bytes_to_words(b, width)
    for i in range(0, 4096, 7):
        by_bytes = (bytes(a[i]) > bytes(b[i])) - (bytes(a[i]) < bytes(b[i]))
        ta, tb = tuple(int(w[i]) for w in wa), tuple(int(w[i]) for w in wb)
        assert (ta > tb) - (ta < tb) == by_bytes


def test_the_type_carries_its_width():
    s = Schema([("key", BYTES(10)), ("payload", BYTES(90)), ("n", ColumnType.INT32)])
    assert s.field("key").ctype == BYTES(10) != BYTES(12)
    assert s.field("key").ctype.is_split and s.field("key").ctype.is_bytes
    assert not ColumnType.INT64.is_bytes
    assert s.field("key").device_names == ["key#b0", "key#b1", "key#b2"]
    assert len(s.field("payload").device_names) == 23
    assert s.field("key").identity_names == s.field("key").device_names
    assert len(s.device_names()) == 27
    assert repr(s) == "Schema(key:bytes[10], payload:bytes[90], n:int32)"
    assert parse_ctype("bytes[10]") == BYTES(10)
    assert parse_ctype("int64") is ColumnType.INT64
    assert BYTES(90).numpy_dtype == np.uint8
    for bad in (0, -1, 2.5):
        with pytest.raises(ValueError):
            BYTES(bad)
    with pytest.raises(ValueError, match=r"\[rows, 10\] uint8"):
        bytes_to_words(np.zeros((4, 9), np.uint8), 10)


def test_infer_schema_maps_a_2d_uint8_column():
    ctx = DryadContext(num_partitions_=1)
    q = ctx.from_arrays({"k": np.zeros((8, 10), np.uint8),
                         "s": np.array([b"ab"] * 8), "n": np.arange(8, dtype=np.int32)})
    assert q.schema.field("k").ctype == BYTES(10)
    assert q.schema.field("s").ctype is ColumnType.STRING  # S<n> is still text
    with pytest.raises(TypeError):
        ctx.from_arrays({"k": np.zeros(8, np.uint8)})


@pytest.mark.parametrize("P", [1, 4])
@pytest.mark.parametrize("width", WIDTHS)
def test_round_trip_through_the_engine(width, P):
    a = edge_bytes(width)
    n = np.arange(ROWS, dtype=np.int32)
    out = DryadContext(num_partitions_=P).from_arrays({"b": a, "n": n}).collect()
    same_bytes(out["b"], a)  # trailing NULs kept, byte for byte
    assert np.array_equal(out["n"], n)
    dbg = DryadContext(local_debug=True).from_arrays({"b": a, "n": n}).collect()
    same_bytes(dbg["b"], a)


# -- as a key ---------------------------------------------------------------------

def uniform(rng):
    return rng.integers(0, 256, (ROWS, 10), dtype=np.uint8)


def equal_in(prefix):
    def keys(rng):
        key = uniform(rng)
        key[:, :prefix] = key[0, :prefix]
        return key
    return keys


def differ_in_byte_9(rng):
    key = np.tile(uniform(rng)[:1], (ROWS, 1))
    key[:, 9] = rng.integers(0, 256, ROWS, dtype=np.uint8)
    return key


def duplicates(rng):
    return uniform(rng)[rng.integers(0, 64, ROWS)]


KEYS = {
    "uniform": uniform,
    "equal_in_bytes_0_3": equal_in(4),
    "equal_in_bytes_0_7": equal_in(8),  # the third word decides
    "differ_only_in_byte_9": differ_in_byte_9,  # the padding never decides
    "exact_duplicates": duplicates,
    "edges": lambda rng: edge_bytes(10),
}


@pytest.mark.parametrize("descending", [False, True], ids=["asc", "desc"])
@pytest.mark.parametrize("P", [1, 4])
@pytest.mark.parametrize("shape", sorted(KEYS))
def test_order_by_is_exact_over_every_byte(shape, P, descending):
    key = KEYS[shape](np.random.default_rng([32, len(shape)]))
    table = {"key": key, "payload": key_payload(key)}
    order = memcmp_order(key, descending)
    keys = [("key", descending)]
    out = DryadContext(num_partitions_=P).from_arrays(table).order_by(keys).collect()
    same_bytes(out["key"], key[order])
    # the payload is a function of the key, so ties cannot hide a swap
    same_bytes(out["payload"], key_payload(key[order]))
    dbg = DryadContext(local_debug=True).from_arrays(table).order_by(keys).collect()
    same_bytes(dbg["key"], key[order])
    same_bytes(dbg["payload"], key_payload(key[order]))


@pytest.mark.parametrize("P", [1, 4])
@pytest.mark.parametrize("bytes_first", [True, False], ids=["bytes_then_int", "int_then_bytes"])
def test_order_by_with_a_second_key(bytes_first, P):
    rng = np.random.default_rng(5)
    key = duplicates(rng)
    n = rng.integers(-50, 50, ROWS).astype(np.int32)
    table = {"key": key, "n": n, "payload": key_payload(key)}
    if bytes_first:
        keys, order = ["key", "n"], memcmp_order(key, then=n)
    else:
        order = np.lexsort([key[:, j] for j in range(9, -1, -1)] + [n])
        keys = ["n", "key"]
    out = DryadContext(num_partitions_=P).from_arrays(table).order_by(keys).collect()
    same_bytes(out["key"], key[order])
    assert np.array_equal(out["n"], n[order])
    dbg = DryadContext(local_debug=True).from_arrays(table).order_by(keys).collect()
    same_bytes(dbg["key"], key[order])
    assert np.array_equal(dbg["n"], n[order])


@pytest.mark.parametrize("P", [1, 4])
def test_range_partition_colocates_and_orders_the_ranges(P):
    key = duplicates(np.random.default_rng(6))
    table = {"key": key, "payload": key_payload(key)}
    out = DryadContext(num_partitions_=P).from_arrays(table).range_partition(
        ["key"]).collect()
    # every row is there with its payload, in some order
    same_bytes(out["payload"], key_payload(out["key"]))
    assert sorted(map(bytes, out["key"])) == sorted(map(bytes, key))
    dbg = DryadContext(local_debug=True).from_arrays(table).range_partition(
        ["key"]).collect()
    assert sorted(map(bytes, dbg["key"])) == sorted(map(bytes, key))


# -- as a payload -----------------------------------------------------------------

# the boosts of the fresh job's dispatches, then the requery's: a
# sorted table sends every shard to one chip whole, so the job doubles
# its room once (tests/test_sort_4c_config.py::BOOSTS)
BOOSTS = {"uniform": [1, 1], "sorted": [1, 2, 1, 2]}


@pytest.mark.parametrize("shape", sorted(BOOSTS))
def test_the_payload_through_the_range_exchange_and_its_retry(shape):
    key = uniform(np.random.default_rng(7))
    if shape == "sorted":
        key = key[memcmp_order(key)]
    table = {"key": key, "payload": key_payload(key)}
    ctx = DryadContext(num_partitions_=4)
    query = ctx.from_arrays(table).order_by(["key"])
    order = memcmp_order(key)
    for answer in (query.collect(), query.collect()):
        same_bytes(answer["key"], key[order])
        same_bytes(answer["payload"], key_payload(key[order]))
    dispatched = [e for e in ctx.events.events()
                  if e["kind"] == "span" and e.get("cat") == "execute"]
    assert [e["boost"] for e in dispatched] == BOOSTS[shape]
    # 3 key words + 23 payload words ride every sort of the stage
    assert {e["row_words"] for e in dispatched} == {26}
    assert all(e["xchg_ici_bytes"] > 0 for e in dispatched)


@pytest.mark.parametrize("P", [1, 4])
def test_every_operator_that_carries_columns_carries_the_payload(P):
    rng = np.random.default_rng(8)
    key = uniform(rng)
    n = np.arange(ROWS, dtype=np.int32)
    table = {"key": key, "n": n, "payload": key_payload(key)}
    for ctx in (DryadContext(num_partitions_=P), DryadContext(local_debug=True)):
        q = ctx.from_arrays(table)
        kept = q.where(lambda c: c["n"] % 3 == 0).collect()
        same_bytes(kept["key"], key[::3])
        same_bytes(kept["payload"], key_payload(key[::3]))
        head = q.order_by(["key"]).take(100).collect()
        same_bytes(head["key"], key[memcmp_order(key)][:100])
        same_bytes(head["payload"], key_payload(head["key"]))
        both = q.concat(q).collect()
        assert both["payload"].shape == (2 * ROWS, 90)
        same_bytes(both["payload"], key_payload(both["key"]))
        picked = q.project(["payload", "n"]).collect()
        same_bytes(picked["payload"], table["payload"])
        renamed = q.select(lambda c: {
            **{k.replace("payload#", "p2#"): v for k, v in c.items()
               if k.startswith("payload#")}, "n": c["n"]}).collect()
        same_bytes(renamed["p2"][:, :90], table["payload"])


def test_a_store_round_trip_keeps_the_width(tmp_path):
    key = edge_bytes(10)
    ctx = DryadContext(num_partitions_=2)
    ctx.from_arrays({"key": key, "payload": key_payload(key)}).to_store(
        str(tmp_path / "t"))
    back = DryadContext(num_partitions_=2).from_store(str(tmp_path / "t"))
    assert back.schema.field("key").ctype == BYTES(10)
    out = back.order_by(["key"]).collect()
    same_bytes(out["key"], key[memcmp_order(key)])
    same_bytes(out["payload"], key_payload(out["key"]))


# -- as a group_by / join / distinct key: right, never silently wrong --------------

def sorted_rows(table, *names):
    rows = sorted(zip(*[map(bytes, table[n]) if table[n].ndim == 2
                        else table[n].tolist() for n in names]))
    return rows


@pytest.mark.parametrize("P", [1, 4])
def test_group_by_a_bytes_key(P):
    rng = np.random.default_rng(9)
    key = duplicates(rng)
    table = {"key": key, "v": rng.integers(0, 100, ROWS).astype(np.float32)}
    aggs = {"c": ("count", None), "s": ("sum", "v")}
    out = DryadContext(num_partitions_=P).from_arrays(table).group_by("key", aggs).collect()
    dbg = DryadContext(local_debug=True).from_arrays(table).group_by("key", aggs).collect()
    want = {}
    for k, v in zip(map(bytes, key), table["v"]):
        c, s = want.get(k, (0, 0.0))
        want[k] = (c + 1, s + float(v))
    for got in (out, dbg):
        assert got["key"].dtype == np.uint8 and got["key"].shape == (len(want), 10)
        assert {bytes(k): (int(c), float(s)) for k, c, s in
                zip(got["key"], got["c"], got["s"])} == want


@pytest.mark.parametrize("P", [1, 4])
def test_distinct_and_join_on_a_bytes_key(P):
    rng = np.random.default_rng(10)
    key = duplicates(rng)
    table = {"key": key, "n": np.arange(ROWS, dtype=np.int32)}
    dim_key = np.unique(key, axis=0)
    dim = {"dkey": dim_key, "w": np.arange(len(dim_key), dtype=np.int32)}
    weight = {bytes(k): int(w) for k, w in zip(dim_key, dim["w"])}
    for ctx in (DryadContext(num_partitions_=P), DryadContext(local_debug=True)):
        d = ctx.from_arrays({"key": key}).distinct().collect()
        assert sorted(map(bytes, d["key"])) == sorted(map(bytes, dim_key))
        j = ctx.from_arrays(table).join(ctx.from_arrays(dim), "key", "dkey").collect()
        assert len(j["n"]) == ROWS
        assert all(weight[bytes(k)] == w for k, w in zip(j["key"], j["w"]))
        assert sorted(j["n"].tolist()) == list(range(ROWS))


@pytest.mark.parametrize("op", ["sum", "mean", "min", "max"])
def test_arithmetic_over_bytes_is_refused(op):
    key = duplicates(np.random.default_rng(11))
    table = {"g": np.arange(ROWS, dtype=np.int32) % 7, "key": key}
    for ctx in (DryadContext(num_partitions_=1), DryadContext(local_debug=True)):
        with pytest.raises(ValueError, match=rf"{op}.*bytes\[10\].*'key'"):
            ctx.from_arrays(table).group_by("g", {"x": (op, "key")}).collect()
    # "first" carries every word
    out = DryadContext(num_partitions_=1).from_arrays(table).group_by(
        "g", {"x": ("first", "key")}).collect()
    assert out["x"].shape == (7, 10) and out["x"].dtype == np.uint8


# -- the forms of a carried sort ----------------------------------------------------

def wide_columns(n, words=26, seed=12):
    rng = np.random.default_rng(seed)
    cols = [jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32))
            for _ in range(words)]
    cols[0] = cols[0] % jnp.uint32(8)  # ties, so stability shows
    return cols, jnp.asarray(rng.random(n) < 0.9)


def test_the_forced_carry_and_the_row_index_form_give_the_same_bytes():
    n = 1 << 12
    words, valid = wide_columns(n)
    # what a stacked gather cannot take rides along as it always did
    extra = [jnp.asarray(np.arange(n) % 3 == 0),
             jnp.asarray(np.arange(3 * n, dtype=np.int32).reshape(n, 3)),
             jnp.asarray(np.arange(n, dtype=np.float32))]
    got = {}
    for form in (SORT.RIDE, SORT.INDEX):
        v, ops, carried = jax.jit(
            lambda w, v, form=form: SORT.sort_carry(w[:1], v, w, form=form)
        )(words + extra, valid)
        got[form] = [np.asarray(c) for c in carried] + [np.asarray(v)]
    for ride, index in zip(got[SORT.RIDE], got[SORT.INDEX]):
        assert ride.dtype == index.dtype and ride.tobytes() == index.tobytes()
    order = np.lexsort((np.asarray(words[0]), ~np.asarray(valid)))
    assert np.array_equal(got[SORT.RIDE][3], np.asarray(words[3])[order])
    assert np.array_equal(got[SORT.INDEX][27], np.asarray(extra[1])[order])


@pytest.mark.parametrize("words,gathers", [(2, 2), (8, 8), (9, 1), (26, 1)])
def test_a_wide_rows_payload_moves_in_one_gather_under_its_own_scope(words, gathers):
    cols, valid = wide_columns(64, words)

    def lowered(form):
        return jax.jit(lambda w, v: SORT.sort_carry(w[:1], v, w, form=form)).lower(
            cols, valid).as_text(debug_info=True)

    index, ride = lowered(SORT.INDEX), lowered(SORT.RIDE)
    assert len(re.findall(r'"stablehlo\.gather"\(', index)) == gathers
    assert re.search(r"/dryad\.sort\.carry/dryad\.sort\.payload/[^\"]*gather", index)
    assert "stablehlo.gather" not in ride and "dryad.sort.payload/" not in ride


def test_the_code_chooses_the_form_from_the_platform_and_the_rows_words(monkeypatch):
    # off the TPU a gather is the cheaper way at any width
    assert SORT.carry_form(2) == SORT.carry_form(26) == SORT.INDEX
    # on it a narrow row rides, as every row of the older cells does (at
    # most 5 words), and the sort benchmark's 26 words go by the index
    monkeypatch.setattr(SORT, "_carry_profitable", lambda: True)
    assert SORT.carry_form(2) == SORT.carry_form(5) == SORT.carry_form(8) == SORT.RIDE
    assert SORT.carry_form(9) == SORT.carry_form(26) == SORT.INDEX
    # from what the trace sees, not from an option
    assert not any("carry" in f or "wide" in f for f in vars(DryadConfig()))


def test_the_widest_row_a_stage_sorts_is_on_its_dispatch_span():
    n = np.arange(ROWS, dtype=np.int32)
    ctx = DryadContext(num_partitions_=1)
    ctx.from_arrays({"n": n, "x": n.astype(np.float32)}).order_by(["n"]).collect()
    ctx.from_arrays({"n": n}).where(lambda c: c["n"] > 5).collect()
    said = [e["row_words"] for e in ctx.events.events()
            if e["kind"] == "span" and e.get("cat") == "execute"]
    assert said == [2, 0]  # two 4-byte columns ride; a stage with no sort


def test_the_spans_say_what_was_packed_and_unpacked():
    key = uniform(np.random.default_rng(13))
    ctx = DryadContext(num_partitions_=1)
    ctx.from_arrays({"key": key, "payload": key_payload(key)}).order_by(
        ["key"]).collect()
    spans = [e for e in ctx.events.events() if e["kind"] == "span"]
    packed = [(e["bytes"], e["rows"]) for e in spans if e["name"] == "pack"]
    unpacked = [(e["bytes"], e["rows"]) for e in spans if e["name"] == "unpack"]
    assert packed == unpacked == [(10 * ROWS, ROWS), (90 * ROWS, ROWS)]
    by_id = {e["span_id"]: e for e in spans}
    for e in spans:
        if e["name"] == "pack":
            assert by_id[e["parent_id"]]["name"] == "encode"
        if e["name"] == "unpack":
            assert by_id[e["parent_id"]]["name"] == "decode"
    # a table with no BYTES column has neither span
    ctx.from_arrays({"n": np.arange(8, dtype=np.int32)}).collect()
    assert len([e for e in ctx.events.events()
                if e["kind"] == "span" and e["name"] in ("pack", "unpack")]) == 4
