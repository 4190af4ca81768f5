"""``from_text``'s one pass: the tokenizer's four columns and the
distinct words it finds as it hashes, in threads over cuts of the
buffer, against a plain reference kept here (per-token hashes, then
``np.unique`` over them: what the ingest did with two sorts); and the
lifetime of the columns the context makes for a text table."""

import functools
import gc

import numpy as np
import pytest

from dryad_tpu import DryadContext
from dryad_tpu.columnar.schema import hash64_bytes
from dryad_tpu.runtime import bindings as RB

SPACES = b" \t\n\r\f\v"
FNV_OFFSET, FNV_PRIME = 0xCBF29CE484222325, 0x100000001B3
MASK32 = np.uint64(0xFFFFFFFF)


def reference(buf: bytes) -> dict:
    """Every token's hash and prefix ranks, byte position by byte
    position over all tokens at once; the distinct words by sorting the
    hashes."""
    b = np.frombuffer(buf, np.uint8)
    word = ~np.isin(b, np.frombuffer(SPACES, np.uint8))
    edge = np.diff(np.concatenate([[0], word.astype(np.int8), [0]]))
    starts, ends = np.flatnonzero(edge == 1), np.flatnonzero(edge == -1)
    lens = ends - starts
    h = np.full(len(starts), FNV_OFFSET, np.uint64)
    rank = np.zeros(len(starts), np.uint64)
    for j in range(int(lens.max(initial=0))):
        live = np.flatnonzero(lens > j)
        c = b[starts[live] + j].astype(np.uint64)
        h[live] = (h[live] ^ c) * np.uint64(FNV_PRIME)
        if j < 8:
            rank[live] |= c << np.uint64(8 * (7 - j))
    uniq, first = np.unique(h, return_index=True)
    return {
        "cols": [(h & MASK32).astype(np.uint32), (h >> np.uint64(32)).astype(np.uint32),
                 (rank >> np.uint64(32)).astype(np.uint32), (rank & MASK32).astype(np.uint32)],
        "vocab": np.unique(h),
        "first": dict(zip(uniq.tolist(), first.tolist())),
        "words": {
            int(k): buf[starts[i] : ends[i]].decode("utf-8", "replace")
            for k, i in zip(uniq, first)
        },
    }


@functools.lru_cache(maxsize=None)
def corpus(name: str) -> bytes:
    rng = np.random.default_rng(40)
    if name == "distinct":  # 2^18 words, no two alike: the table grows
        return b" ".join(b"x%06d" % i for i in rng.permutation(1 << 18))
    if name == "zipf":  # 2^20 words of 2^12, 1 - 8 bytes, 1 - 3 spaces
        ids = np.minimum(rng.zipf(1.3, 1 << 20), 1 << 12) - 1
        words = [b"%x" % (i * 2654435761 % (1 << (4 * (1 + i % 8)))) for i in range(1 << 12)]
        gaps = [b" ", b"  ", b" \n\t"]
        return b"".join(words[i] + gaps[i % 3] for i in ids.tolist())
    return SMALL[name]


SMALL = {
    "empty": b"",
    "all_whitespace": b" \t\n\r\f\v  \n",
    "one_token": b"word",
    "no_trailing_whitespace": b"a bb ccc a",
    "leading_whitespace": b"\n\n  a bb a",
    "every_separator": b"a b\tc\nd\re\ff\vg a\x1cb a\x00b a\xa0b \x85",
    "token_lengths": b" ".join(
        [b"a", b"abcd", b"abcde", b"abcdefgh", b"abcdefghi", b"q" * 300,
         b"abcdefghZ", b"abcd", b"q" * 299 + b"r"]),
    "not_utf8": b"\xff\xfe ab\x80cd \xc3\x28 caf\xc3\xa9 \xf0\x9f\x98 \xff\xfe caf\xc3",
}


@functools.lru_cache(maxsize=None)
def expected(name: str) -> dict:
    return reference(corpus(name))


def zipf_cut(where: str) -> int:
    """A byte offset near the middle of the Zipf corpus: on a token's
    first, middle or last byte, or inside a run of spaces."""
    buf = corpus("zipf")
    at = len(buf) // 2
    if where == "spaces":
        return buf.index(b" \n\t", at) + 1
    while not (buf[at - 1] in SPACES and len(buf[at : at + 9].split()[0]) >= 3
               and buf[at] not in SPACES):
        at += 1
    length = len(buf[at : at + 9].split()[0])
    return {"first": at, "middle": at + length // 2, "last": at + length - 1}[where]


# (corpus, the cuts forced on the native pass: None = as its length says)
CASES = [(name, None) for name in SMALL] + [
    ("distinct", None),
    ("zipf", None),
    ("zipf", "first"), ("zipf", "middle"), ("zipf", "last"), ("zipf", "spaces"),
    ("zipf", "many"),
    ("token_lengths", "inside_every_token"),
]


def forced_cuts(name: str, cuts):
    if cuts is None:
        return None
    if cuts == "many":  # more cuts than threads would ever be taken
        return list(range(1 << 16, len(corpus(name)), 1 << 16))
    if cuts == "inside_every_token":  # several cuts in one token, some past the end
        return [1, 3, 3, 8, 20, 40, 41, 100, 350, 10_000]
    # three runs, the middle cut where the case wants it
    return [1 << 20, zipf_cut(cuts), zipf_cut(cuts) + (1 << 20)]


def ingest(monkeypatch, buf: bytes, native: bool, cuts=None):
    """``from_text`` over ``buf`` in a new context; returns the context,
    the query and the ``tokenize`` span."""
    if not native:
        monkeypatch.setattr(RB, "_load", lambda: None)
    elif cuts is not None:
        whole = RB.tokenize
        monkeypatch.setattr(RB, "tokenize", lambda text: whole(text, cuts))
    ctx = DryadContext(num_partitions_=8)
    query = ctx.from_text(buf)
    spans = {e["name"]: e for e in ctx.events.events() if e["kind"] == "span"}
    return ctx, query, spans["tokenize"]


def check(ctx, query, span, want: dict) -> None:
    binding = ctx.inputs.get(query.node.id)
    assert binding.kind == "host_physical"
    phys = binding.arrays
    for name, col in zip(("word#h0", "word#h1", "word#r0", "word#r1"), want["cols"]):
        assert phys[name].dtype == np.uint32
        assert phys[name].tobytes() == col.tobytes(), name
    vocab = query.node.params["str_vocab"]["word"]
    assert vocab.dtype == np.uint64
    np.testing.assert_array_equal(vocab, want["vocab"])
    assert ctx.dictionary._map == want["words"]
    assert span["rows"] == len(want["cols"][0])
    assert span["distinct"] == len(want["vocab"])
    assert span["bytes_out"] == 16 * len(want["cols"][0])


# the Python pass is one run: it takes every corpus, and no cuts
PASSES = [(n, c, True) for n, c in CASES] + [
    (n, c, False) for n, c in CASES if c is None]


@pytest.mark.parametrize(
    "name,cuts,native", PASSES,
    ids=[f"{n}-{c or 'own'}-{'native' if v else 'python'}" for n, c, v in PASSES])
def test_one_pass_gives_the_columns_the_vocabulary_and_the_dictionary(
        mesh8, monkeypatch, name, cuts, native):
    if native and not RB.native_available():
        pytest.skip("no toolchain")
    ctx, query, span = ingest(
        monkeypatch, corpus(name), native, forced_cuts(name, cuts))
    check(ctx, query, span, expected(name))
    if not native:
        assert span["runs"] == 1
    elif cuts is not None:
        assert span["runs"] == len(forced_cuts(name, cuts)) + 1
    else:  # a thread a MiB of text, at most eight
        assert span["runs"] == max(1, min(8, len(corpus(name)) >> 20))


@pytest.mark.parametrize("name,cuts", CASES,
                         ids=[f"{n}-{c or 'own'}" for n, c in CASES])
def test_first_occurrences_are_the_lowest_indexes(name, cuts):
    """``tokenize`` itself: a distinct word's ``first`` is the index of
    its first token and ``starts`` / ``lens`` are that token's bytes,
    in order of first occurrence, however the buffer was cut."""
    buf, want = corpus(name), expected(name)
    toks = RB.tokenize(buf, forced_cuts(name, cuts))
    assert toks.hashes.dtype == toks.first.dtype == toks.starts.dtype == np.uint64
    assert toks.lens.dtype == np.uint32
    assert dict(zip(toks.hashes.tolist(), toks.first.tolist())) == want["first"]
    assert np.all(np.diff(toks.first.astype(np.int64)) > 0)
    assert len(toks.hashes) == len(want["vocab"])
    for h, s, n in list(zip(toks.hashes.tolist(), toks.starts.tolist(), toks.lens.tolist()))[:4096]:
        assert hash64_bytes(buf[s : s + n]) == h
        assert not any(c in SPACES for c in buf[s : s + n])


@pytest.mark.parametrize("native", [True, False], ids=["native", "python"])
def test_a_hash_known_under_another_word_raises(mesh8, monkeypatch, native):
    if not native:
        monkeypatch.setattr(RB, "_load", lambda: None)
    ctx = DryadContext(num_partitions_=8)
    ctx.dictionary._map[hash64_bytes(b"bb")] = "not bb"
    with pytest.raises(ValueError, match="hash64 collision: 'not bb' vs 'bb'"):
        ctx.from_text(b"a bb ccc")
    # the same word under its hash is no collision
    ctx.dictionary._map[hash64_bytes(b"bb")] = "bb"
    assert len(ctx.from_text(b"a bb ccc").collect()["word"]) == 3


def test_several_files_are_their_concatenation(mesh8, tmp_path):
    texts = [b"a bb a ccc", b"", b"  dd a\n", b"ccc eee" * 3, b"\xff bb"]
    paths = []
    for i, text in enumerate(texts):
        paths.append(str(tmp_path / f"part{i}.txt"))
        with open(paths[-1], "wb") as fh:
            fh.write(text)
    ctx = DryadContext(num_partitions_=8)
    query = ctx.from_text(paths)
    spans = [e for e in ctx.events.events()
             if e["kind"] == "span" and e["name"] != "resource_sample"]
    assert [e["name"] for e in spans] == ["tokenize", "vocab"]
    want = reference(b" ".join(texts))
    # a word's first occurrence is looked for a file, not over the files
    span = dict(spans[0], distinct=len(want["vocab"]))
    assert spans[0]["distinct"] == sum(len(reference(t)["vocab"]) for t in texts)
    assert spans[0]["bytes"] == sum(len(t) for t in texts)
    check(ctx, query, span, want)
    assert ctx.from_text([]).collect()["word"].tolist() == []


def test_text_stream_chunks_carry_their_vocabulary(mesh8, tmp_path):
    text = b" ".join(b"w%d" % (i * i % 97) for i in range(4000))
    path = tmp_path / "stream.txt"
    path.write_bytes(text)
    ctx = DryadContext(num_partitions_=8)
    query = ctx.text_stream(str(path), chunk_bytes=1 << 12)
    binding = ctx.inputs.get(query.node.id)
    assert binding.kind == "stream"
    chunks = list(binding.source.chunks)
    assert len(chunks) > 3
    whole = reference(text)
    for i, col in enumerate(("word#h0", "word#h1", "word#r0", "word#r1")):
        got = np.concatenate([c[col] for c in chunks])
        assert got.tobytes() == whole["cols"][i].tobytes()
    for chunk in chunks:
        h = (chunk["word#h1"].astype(np.uint64) << np.uint64(32)) | chunk["word#h0"]
        np.testing.assert_array_equal(chunk["#vocab"]["word"], np.unique(h))
        assert chunk["#vocab"]["word"].dtype == np.uint64
    assert ctx.dictionary._map == whole["words"]
    # and the streamed count is the table's
    counts = ctx.text_stream(str(path), chunk_bytes=1 << 12).group_by(
        "word", {"n": ("count", None)}).collect()
    table = ctx.from_text(text).group_by("word", {"n": ("count", None)}).collect()
    assert dict(zip(counts["word"], counts["n"].tolist())) == dict(
        zip(table["word"], table["n"].tolist()))


# -- lifetime: what from_text made for a table dies with its queries ----

TEXT = "to be or not to be that is the question " * 50


def _held(ctx, node_id):
    return ctx.inputs.holds(node_id)[:2]


def test_a_dead_text_query_leaves_no_binding_and_no_fingerprint(mesh8):
    ctx = DryadContext(num_partitions_=8)
    q = ctx.from_text(TEXT)
    node_id = q.node.id
    assert len(q.collect()["word"]) == 500
    ctx.inputs.fingerprint(q.node.id)
    assert _held(ctx, node_id) == (True, True)
    del q
    gc.collect()
    assert _held(ctx, node_id) == (False, False)
    # nothing but the device cache's own bounded entry is left of it
    assert ctx.inputs.snapshot() == {}


def test_a_dead_query_that_never_ran_leaves_nothing(mesh8):
    ctx = DryadContext(num_partitions_=8)
    node_id = ctx.from_text(TEXT).group_by("word", {"n": ("count", None)}).node.inputs[0].id
    gc.collect()
    assert _held(ctx, node_id) == (False, False)


def test_a_live_derived_query_keeps_its_table(mesh8):
    ctx = DryadContext(num_partitions_=8)
    q = ctx.from_text(TEXT)
    node_id = q.node.id
    counts = q.group_by("word", {"n": ("count", None)}).order_by([("word", False)])
    first = counts.collect()
    ctx.inputs.fingerprint(q.node.id)
    del q
    gc.collect()
    assert _held(ctx, node_id) == (True, True)
    # re-ingested from the host columns, not from the device cache
    ctx.inputs.evict()
    again = counts.collect()
    assert first["word"].tolist() == again["word"].tolist()
    assert first["n"].tolist() == again["n"].tolist() == [100, 50, 50, 50, 50, 50, 50, 100]
    del counts
    gc.collect()
    assert _held(ctx, node_id) == (False, False)


def test_one_of_two_text_tables_dies_alone(mesh8):
    ctx = DryadContext(num_partitions_=8)
    a, b = ctx.from_text("x y x"), ctx.from_text("p q p p")
    ids = a.node.id, b.node.id
    del a
    gc.collect()
    assert _held(ctx, ids[0])[0] is False and _held(ctx, ids[1])[0] is True
    assert sorted(b.group_by("word", {"n": ("count", None)}).collect()["n"].tolist()) == [1, 3]


def test_the_users_arrays_stay_bound_as_they_did(mesh8):
    ctx = DryadContext(num_partitions_=8)
    table = {"k": np.arange(64, dtype=np.int32)}
    q = ctx.from_arrays(table)
    node_id = q.node.id
    q.collect()
    ctx.inputs.fingerprint(q.node.id)
    del q
    gc.collect()
    assert _held(ctx, node_id) == (True, True)
    assert ctx.inputs.get(node_id).arrays["k"] is table["k"]


def test_a_context_that_died_first_is_no_error(mesh8):
    ctx = DryadContext(num_partitions_=8)
    q = ctx.from_text(TEXT)
    node = q.node
    del q, ctx
    gc.collect()
    del node  # the finalizer finds no context
    gc.collect()
