"""Native runtime tests: hash/tokenizer parity, prefetch channel,
from_text ingest, compressed store round-trip."""

import os

import numpy as np
import pytest

from dryad_tpu import ColumnType, DryadConfig, DryadContext, Schema
from dryad_tpu.columnar.schema import hash64_str, string_prefix_rank
from dryad_tpu.runtime import bindings as B


def test_hash64_native_matches_python():
    for s in ["", "a", "hello world", "ünïcödé-строка-字符串"]:
        assert B.hash64(s.encode()) == hash64_str(s)


def test_tokenizer_native_matches_python():
    text = "  the quick\t brown\nfox  jumps over\r\nthe lazy dog "
    toks = B.tokenize(text.encode())
    words = text.split()
    hashes = (toks.h1.astype(np.uint64) << np.uint64(32)) | toks.h0.astype(np.uint64)
    assert [hash64_str(w) for w in words] == hashes.tolist()
    assert np.array_equal(toks.r0, string_prefix_rank(np.array(words, object)))
    assert np.array_equal(
        toks.r1, string_prefix_rank(np.array(words, object), offset=4)
    )
    # the distinct words, each where it first occurs ("the" once)
    distinct = [
        text.encode()[int(s) : int(s) + int(n)].decode()
        for s, n in zip(toks.starts, toks.lens)
    ]
    assert distinct == words[:6] + words[7:]
    assert [words[int(i)] for i in toks.first] == distinct
    assert toks.hashes.tolist() == [hash64_str(w) for w in distinct]
    assert toks.runs == 1
    for a, b in zip(toks, B._tokenize_py(text.encode())):
        assert np.array_equal(a, b)


def test_prefetch_channel_order(tmp_path):
    paths = []
    for i in range(10):
        p = tmp_path / f"f{i}.bin"
        p.write_bytes(bytes([i]) * (100 + i))
        paths.append(str(p))
    with B.PrefetchChannel(paths, depth=3, threads=4) as ch:
        blocks = list(ch)
    assert [b[0] for b in blocks] == list(range(10))
    assert [len(b) for b in blocks] == [100 + i for i in range(10)]


def test_from_text_wordcount(mesh8):
    ctx = DryadContext(num_partitions_=8)
    text = "to be or not to be that is the question " * 20
    wc = (
        ctx.from_text(text)
        .group_by("word", {"n": ("count", None)})
        .collect()
    )
    got = dict(zip(wc["word"], wc["n"].tolist()))
    py = {}
    for w in text.split():
        py[w] = py.get(w, 0) + 1
    assert got == py

    # localdebug path agrees
    dbg = DryadContext(local_debug=True)
    wc2 = dbg.from_text(text).group_by("word", {"n": ("count", None)}).collect()
    assert dict(zip(wc2["word"], wc2["n"].tolist())) == py


def test_from_text_file_and_strings_egress(tmp_path, mesh8):
    p = tmp_path / "input.txt"
    p.write_text("alpha beta alpha gamma")
    ctx = DryadContext(num_partitions_=8)
    out = ctx.from_text(str(p)).collect()
    assert sorted(out["word"]) == ["alpha", "alpha", "beta", "gamma"]


def test_compressed_store_roundtrip(tmp_path, mesh8):
    ctx = DryadContext(
        num_partitions_=8, config=DryadConfig(intermediate_compression="zlib")
    )
    tbl = {
        "w": np.array(["x", "y", "z", "x"] * 25, object),
        "v": np.arange(100, dtype=np.float32),
    }
    path = str(tmp_path / "store_z")
    ctx.from_arrays(tbl).to_store(path)
    back = DryadContext(num_partitions_=8).from_store(path).collect()
    assert sorted(back["w"]) == sorted(tbl["w"])
    assert sorted(back["v"].tolist()) == sorted(tbl["v"].tolist())


def test_native_write_partition_matches_python(tmp_path):
    from dryad_tpu.columnar import io as cio

    cols = {
        "a": np.arange(1000, dtype=np.int32),
        "b": np.linspace(0, 1, 1000).astype(np.float32),
    }
    for comp in (None, "zlib"):
        p_native = str(tmp_path / f"n_{comp}.dpf")
        p_python = str(tmp_path / f"p_{comp}.dpf")
        B.write_partition(p_native, cols, comp)
        cio.write_partition_file(p_python, cols, comp)
        got_n = cio.read_partition_file(p_native)
        got_p = cio.read_partition_file(p_python)
        for k in cols:
            np.testing.assert_array_equal(got_n[k], cols[k])
            np.testing.assert_array_equal(got_p[k], got_n[k])


def test_fifo_pipelined_producer_consumer():
    import threading

    f = B.Fifo(depth=2)
    blocks = [bytes([i]) * (i + 1) for i in range(50)]

    def produce():
        for b in blocks:
            f.push(b)
        f.close()

    t = threading.Thread(target=produce)
    t.start()
    got = []
    while True:
        b = f.pop()
        if b is None:
            break
        got.append(b)
    t.join()
    f.destroy()
    assert got == blocks


def test_tlv_roundtrip_and_malformed():
    entries = [(1, b"hello"), (42, b""), (65535, bytes(range(256)))]
    buf = B.tlv_encode(entries)
    assert B.tlv_decode(buf) == entries
    assert B.tlv_decode(b"") == []
    with pytest.raises(ValueError):
        B.tlv_decode(buf[:-1])
    with pytest.raises(ValueError):
        B.tlv_decode(b"\x01\x00")


def test_write_partition_escapes_column_names(tmp_path):
    from dryad_tpu.columnar import io as cio

    cols = {'a"b\\c': np.arange(10, dtype=np.int32)}
    p = str(tmp_path / "esc.dpf")
    B.write_partition(p, cols, "zlib")
    got = cio.read_partition_file(p)
    np.testing.assert_array_equal(got['a"b\\c'], cols['a"b\\c'])


def test_fifo_closed_semantics():
    f = B.Fifo(depth=2)
    f.push(b"x")
    f.close()
    assert f.push(b"y") is False
    assert f.pop() == b"x"
    assert f.pop() is None
    assert f.pop() is None  # repeatable end-of-stream
    f.destroy()


def test_tlv_tag_range_checked():
    with pytest.raises(ValueError):
        B.tlv_encode([(0x10000, b"x")])
    with pytest.raises(ValueError):
        B.tlv_encode([(-1, b"x")])


def test_from_text_multiple_files(tmp_path, mesh8):
    from dryad_tpu import DryadContext

    paths = []
    for i, content in enumerate(["alpha beta", "beta gamma", "alpha alpha"]):
        p = tmp_path / f"f{i}.txt"
        p.write_text(content)
        paths.append(str(p))
    ctx = DryadContext(num_partitions_=8)
    wc = (
        ctx.from_text(paths)
        .group_by("word", {"n": ("count", None)})
        .collect()
    )
    assert dict(zip(wc["word"], wc["n"].tolist())) == {
        "alpha": 3, "beta": 2, "gamma": 1
    }


def test_native_batch_decompress_roundtrip(tmp_path, rng):
    """Threaded native inflate of compressed partition columns (the
    channelbuffernativereader read-half analog), differential against
    the Python zlib fallback."""
    import zlib

    from dryad_tpu.columnar.io import (
        parse_partition_bytes, write_partition_file,
    )
    from dryad_tpu.runtime import bindings as RB

    cols = {
        "a": rng.integers(-(2 ** 31), 2 ** 31 - 1, 10_000).astype(np.int32),
        "b": rng.standard_normal(10_000).astype(np.float32),
        "c": rng.integers(0, 2, 10_000).astype(np.bool_),
        "d": rng.integers(0, 2 ** 32, 10_000, dtype=np.uint64).astype(np.uint32),
    }
    p = str(tmp_path / "part.dpf")
    write_partition_file(p, cols, compression="zlib")
    with open(p, "rb") as fh:
        buf = fh.read()
    got = parse_partition_bytes(buf)
    for n, v in cols.items():
        np.testing.assert_array_equal(got[n], v)

    if RB.native_available():
        # corrupt payload must raise, not return garbage
        src = zlib.compress(cols["a"].tobytes())
        bad = src[:-4] + b"\x00\x00\x00\x00"
        dst = np.empty(10_000, np.int32)
        import pytest as _pytest

        with _pytest.raises(ValueError):
            RB.decompress_batch([bad], [dst])
