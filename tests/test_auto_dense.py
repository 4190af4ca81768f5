"""Auto-dense STRING group_by: a plain group_by over one string key
rides the MXU bucket path keyed on dense dictionary codes — no shuffle
(``ops/stringcode.py``; the reference pays a full hash repartition for
the same query, ``DryadLinqQueryNode.cs:3581``)."""

import numpy as np
import pytest

from dryad_tpu import DryadContext
from dryad_tpu.plan.lower import lower
from dryad_tpu.utils.config import DryadConfig


def _vocab_table(rng, n=4000, vocab=97):
    words = np.array([f"tok{i:04d}" for i in range(vocab)], object)
    w = words[rng.integers(0, vocab, n)]
    v = rng.standard_normal(n).astype(np.float32)
    return {"word": w, "v": v}


def _ops(graph):
    return [op.kind for st in graph.stages for op in st.ops]


def test_wordcount_auto_dense_no_shuffle(rng):
    ctx = DryadContext(num_partitions_=8)
    tbl = _vocab_table(rng)
    q = ctx.from_arrays(tbl).group_by(
        "word", {"c": ("count", None), "s": ("sum", "v"), "m": ("mean", "v")}
    )
    kinds = _ops(lower([q.node], ctx.config, ctx.dictionary))
    assert "string_code" in kinds and "group_reduce_dense" in kinds
    assert "exchange_hash" not in kinds

    out = q.collect()
    words = tbl["word"]
    uniq, counts = np.unique(words.astype(str), return_counts=True)
    got = dict(zip([str(w) for w in out["word"]], out["c"].tolist()))
    assert got == dict(zip(uniq.tolist(), counts.tolist()))
    sums = {u: float(tbl["v"][words.astype(str) == u].sum()) for u in uniq}
    for w, s, m, c in zip(out["word"], out["s"], out["m"], out["c"]):
        assert abs(s - sums[str(w)]) < 1e-2 * max(1.0, abs(sums[str(w)]))
        assert abs(m - s / c) < 1e-4 * max(1.0, abs(m))


def test_auto_dense_matches_sort_path(rng):
    """Differential: auto-dense result == the sort-path result."""
    tbl = _vocab_table(rng, n=3000, vocab=53)
    on = DryadContext(num_partitions_=8)
    off = DryadContext(
        num_partitions_=8, config=DryadConfig(auto_dense_strings=False)
    )
    build = lambda c: c.from_arrays(tbl).group_by(  # noqa: E731
        "word", {"c": ("count", None), "s": ("sum", "v")}
    ).collect()
    a, b = build(on), build(off)
    ka = sorted(zip([str(w) for w in a["word"]], a["c"].tolist()))
    kb = sorted(zip([str(w) for w in b["word"]], b["c"].tolist()))
    assert ka == kb
    kinds = _ops(lower(
        [off.from_arrays(tbl).group_by("word", {"c": ("count", None)}).node],
        off.config, off.dictionary,
    ))
    assert "string_code" not in kinds and "exchange_hash" in kinds


def test_auto_dense_downstream_ops(rng):
    """order_by / join after an auto-dense group_by stay correct (the
    decoded key columns are real string physical words)."""
    ctx = DryadContext(num_partitions_=8)
    tbl = _vocab_table(rng, n=2000, vocab=31)
    top = (
        ctx.from_arrays(tbl)
        .group_by("word", {"c": ("count", None)})
        .order_by([("c", True), ("word", False)])
        .collect()
    )
    counts = list(top["c"])
    assert counts == sorted(counts, reverse=True)
    uniq, ref = np.unique(tbl["word"].astype(str), return_counts=True)
    assert sorted(str(w) for w in top["word"]) == sorted(uniq.tolist())
    assert int(np.sum(top["c"])) == len(tbl["word"])

    # join the aggregate back against a string table
    right = ctx.from_arrays({"word": uniq[:10].astype(object)})
    j = (
        ctx.from_arrays(tbl)
        .group_by("word", {"c": ("count", None)})
        .join(right, "word")
        .collect()
    )
    assert sorted(str(w) for w in j["word"]) == sorted(uniq[:10].tolist())


def test_auto_dense_gates(rng):
    """Non-dense aggs, multi-key, salt, and over-limit vocabularies all
    fall back to the sort path."""
    tbl = _vocab_table(rng, n=500, vocab=11)
    tbl["k2"] = rng.integers(0, 3, 500).astype(np.int32)

    def kinds_for(ctx, q):
        return _ops(lower([q.node], ctx.config, ctx.dictionary))

    ctx = DryadContext(num_partitions_=8)
    t = ctx.from_arrays(tbl)
    assert "string_code" not in kinds_for(
        ctx, t.group_by("word", {"m": ("min", "v")})
    )
    assert "string_code" not in kinds_for(
        ctx, t.group_by(["word", "k2"], {"c": ("count", None)})
    )
    assert "string_code" not in kinds_for(
        ctx, t.group_by("word", {"s": ("sum", "v")}, salt=4)
    )
    small = DryadContext(
        num_partitions_=8, config=DryadConfig(auto_dense_limit=4)
    )
    ts = small.from_arrays(tbl)
    assert "string_code" not in kinds_for(
        small, ts.group_by("word", {"c": ("count", None)})
    )
    # int keys are untouched by the auto path (explicit dense= exists)
    assert "string_code" not in kinds_for(
        ctx, t.group_by("k2", {"c": ("count", None)})
    )


def test_code_table_lookup_roundtrip(rng):
    """CodeTable maps every dictionary entry to its insertion rank;
    unknown hashes map to the padded code domain (past every real code
    — the sentinel is tier-static so the traced lookup is identical
    for every table of a palette tier)."""
    from dryad_tpu.columnar.schema import StringDictionary
    from dryad_tpu.ops.stringcode import build_tables

    import jax.numpy as jnp

    d = StringDictionary()
    words = [f"w{i}" for i in range(300)]
    for w in words:
        d.add(w)
    code_t, dec_t = build_tables(d)
    assert code_t.num_codes == 300
    assert code_t.num_codes_padded >= 300
    h0 = jnp.asarray(dec_t.words[:, 0])
    h1 = jnp.asarray(dec_t.words[:, 1])
    codes = np.asarray(code_t.lookup(h0, h1))
    assert codes.tolist() == list(range(300))
    miss = np.asarray(
        code_t.lookup(jnp.full((4,), 0xDEAD, jnp.uint32),
                      jnp.full((4,), 0xBEEF, jnp.uint32))
    )
    assert miss.tolist() == [code_t.num_codes_padded] * 4


def test_from_text_wordcount_auto_dense(rng, tmp_path):
    """The flagship from_text wordcount shape takes the auto-dense path
    end-to-end (tokens register in the context dictionary at ingest)."""
    ids = rng.integers(0, 200, 3000)
    path = tmp_path / "t.txt"
    path.write_text(" ".join(f"w{int(i):03d}" for i in ids))
    ctx = DryadContext(num_partitions_=8)
    q = ctx.from_text(str(path), column="word")
    g = q.group_by("word", {"c": ("count", None)})
    kinds = _ops(lower([g.node], ctx.config, ctx.dictionary))
    assert "string_code" in kinds and "exchange_hash" not in kinds
    out = g.order_by([("c", True)]).collect()
    assert int(np.sum(out["c"])) == 3000
    uniq, counts = np.unique([f"w{int(i):03d}" for i in ids], return_counts=True)
    got = dict(zip([str(w) for w in out["word"]], out["c"].tolist()))
    assert got == dict(zip(uniq.tolist(), counts.tolist()))


def test_auto_dense_then_shuffle_join_correct(rng):
    """SHUFFLE-strategy join after an auto-dense group_by: the output
    is code-range partitioned, so the node must NOT claim hash
    partitioning — a stale claim would elide the left exchange and
    silently drop matches (code-review regression)."""
    ctx = DryadContext(num_partitions_=8)
    tbl = _vocab_table(rng, n=2000, vocab=41)
    g = ctx.from_arrays(tbl).group_by("word", {"c": ("count", None)})
    assert g.node.partition.scheme not in ("hash", "range")
    uniq = np.unique(tbl["word"].astype(str))
    right = ctx.from_arrays(
        {"word": uniq.astype(object),
         "tag": np.arange(len(uniq), dtype=np.int32)}
    )
    j = g.join(right, "word", strategy="shuffle").collect()
    assert sorted(str(w) for w in j["word"]) == sorted(uniq.tolist())
    counts = {str(w): int(c) for w, c in zip(j["word"], j["c"])}
    ref = {
        str(u): int((tbl["word"].astype(str) == u).sum()) for u in uniq
    }
    assert counts == ref


def test_auto_dense_table_cache_reused(rng):
    """build_tables memoizes on the dictionary until it grows."""
    from dryad_tpu.ops.stringcode import build_tables

    ctx = DryadContext(num_partitions_=8)
    ctx.from_arrays(_vocab_table(rng, n=100, vocab=7))
    a = build_tables(ctx.dictionary)
    b = build_tables(ctx.dictionary)
    assert a[0] is b[0] and a[1] is b[1]
    ctx.dictionary.add("brand-new-token")
    c = build_tables(ctx.dictionary)
    assert c[0] is not a[0]
    assert c[0].num_codes == a[0].num_codes + 1


def test_distinct_auto_dense_vocabulary(rng):
    """distinct() over a single STRING column is the vocabulary query:
    shuffle-free bucket count>0 + decode."""
    ctx = DryadContext(num_partitions_=8)
    tbl = _vocab_table(rng, n=3000, vocab=67)
    q = ctx.from_arrays({"word": tbl["word"]}).distinct()
    kinds = _ops(lower([q.node], ctx.config, ctx.dictionary))
    assert "string_code" in kinds and "exchange_hash" not in kinds
    out = q.collect()
    uniq = np.unique(tbl["word"].astype(str))
    assert sorted(str(w) for w in out["word"]) == sorted(uniq.tolist())

    # multi-column table: dense distinct does NOT apply (schema != keys)
    q2 = ctx.from_arrays(tbl).distinct(["word"])
    kinds2 = _ops(lower([q2.node], ctx.config, ctx.dictionary))
    assert "string_code" not in kinds2
    out2 = q2.collect()
    assert sorted(str(w) for w in out2["word"]) == sorted(uniq.tolist())


def test_auto_dense_checkpoint_resume(rng, tmp_path):
    """A fresh context with the same checkpoint dir restores the
    auto-dense stage without recompute — table reprs are
    content-addressed, not object-address-based (regression: id-based
    repr made every context's fingerprint unique)."""
    words = np.array([f"w{i%50:02d}" for i in range(4000)], object)
    cfg = DryadConfig(checkpoint_dir=str(tmp_path))
    build = lambda: (  # noqa: E731
        DryadContext(num_partitions_=8, config=cfg)
        .from_arrays({"w": words})
        .group_by("w", {"c": ("count", None)})
        .order_by(["w"])
    )
    r1 = build().collect()
    q2 = build()
    r2 = q2.collect()
    assert [str(x) for x in r1["w"]] == [str(x) for x in r2["w"]]
    assert r1["c"].tolist() == r2["c"].tolist()
    kinds = [e["kind"] for e in q2.ctx.executor.events.events()]
    assert "stage_checkpoint_hit" in kinds


def test_dict_miss_surfaced_not_dropped(rng):
    """Rows whose STRING hash words miss the context dictionary (e.g.
    fabricated by apply_host after ingest) fail loudly instead of being
    silently dropped by the dense kernel's range mask."""
    from dryad_tpu.exec.executor import StageFailedError

    ctx = DryadContext(num_partitions_=8)
    tbl = _vocab_table(rng, n=400, vocab=13)
    q = ctx.from_arrays(tbl)

    def poison(table, _pi):
        t = {k: np.asarray(v).copy() for k, v in table.items()}
        # fabricate hash words no dictionary entry ever produced
        t["word#h0"] = t["word#h0"] ^ np.uint32(0xDEADBEEF)
        return t

    bad = q.apply_host(poison).group_by("word", {"c": ("count", None)})
    with pytest.raises(StageFailedError, match="dictionary"):
        bad.collect()


# -- int auto-dense: the integer twin of the STRING rewrite ---------------

def test_int_group_by_auto_dense_no_shuffle(rng):
    """A plain group_by over an ingest-bounded INT32 key rides the MXU
    bucket path: no exchange, no sort (every non-dense GroupBy used to
    pay the slower sort path)."""
    ctx = DryadContext(num_partitions_=8)
    tbl = {
        "k": rng.integers(0, 50, 3000).astype(np.int32),
        "v": rng.standard_normal(3000).astype(np.float32),
    }
    q = ctx.from_arrays(tbl).group_by(
        "k", {"c": ("count", None), "s": ("sum", "v")}
    )
    kinds = _ops(lower([q.node], ctx.config, ctx.dictionary))
    assert "group_reduce_dense" in kinds
    assert "exchange_hash" not in kinds and "group_reduce" not in kinds

    out = q.collect()
    ref = np.bincount(tbl["k"], minlength=50)
    got = dict(zip(out["k"].tolist(), out["c"].tolist()))
    assert got == {int(k): int(c) for k, c in enumerate(ref) if c}
    sums = np.bincount(tbl["k"], weights=tbl["v"], minlength=50)
    for k, s in zip(out["k"], out["s"]):
        assert abs(s - sums[int(k)]) < 1e-2 * max(1.0, abs(sums[int(k)]))


def test_int_auto_dense_gates(rng):
    ctx = DryadContext(num_partitions_=8)
    k = rng.integers(0, 50, 500).astype(np.int32)
    v = rng.standard_normal(500).astype(np.float32)

    def kinds_for(q):
        return _ops(lower([q.node], ctx.config, ctx.dictionary))

    base = ctx.from_arrays({"k": k, "v": v})
    # value-preserving chain keeps the bound
    q1 = base.where(lambda c: c["v"] > 0).group_by("k", {"c": ("count", None)})
    assert "group_reduce_dense" in kinds_for(q1)
    # select may fabricate values -> falls back to the sort path
    q2 = base.select(
        lambda c: {"k": c["k"] * 2, "v": c["v"]}
    ).group_by("k", {"c": ("count", None)})
    assert "group_reduce_dense" not in kinds_for(q2)
    # min/max aggs -> sort path
    q3 = base.group_by("k", {"m": ("min", "v")})
    assert "group_reduce_dense" not in kinds_for(q3)
    # negative ingest range -> sort path
    neg = ctx.from_arrays({"k": (k - 10).astype(np.int32), "v": v})
    q4 = neg.group_by("k", {"c": ("count", None)})
    assert "group_reduce_dense" not in kinds_for(q4)
    # huge domain -> sort path
    wide = ctx.from_arrays(
        {"k": rng.integers(0, 1 << 20, 500).astype(np.int32)}
    )
    q5 = wide.group_by("k", {"c": ("count", None)})
    assert "group_reduce_dense" not in kinds_for(q5)
    # disabled by config
    from dryad_tpu.utils.config import DryadConfig

    off = DryadContext(
        num_partitions_=8, config=DryadConfig(auto_dense_ints=False)
    )
    q6 = off.from_arrays({"k": k}).group_by("k", {"c": ("count", None)})
    assert "group_reduce_dense" not in _ops(
        lower([q6.node], off.config, off.dictionary)
    )


def test_int_auto_dense_matches_sort_path(rng):
    tbl = {
        "k": rng.integers(0, 100, 4000).astype(np.int32),
        "v": rng.standard_normal(4000).astype(np.float32),
    }
    from dryad_tpu.utils.config import DryadConfig

    fast = DryadContext(num_partitions_=8)
    slow = DryadContext(
        num_partitions_=8, config=DryadConfig(auto_dense_ints=False)
    )

    def q(c):
        return c.from_arrays(tbl).group_by(
            "k", {"c": ("count", None), "s": ("sum", "v"), "m": ("mean", "v")}
        ).collect()

    a, b = q(fast), q(slow)
    oa, ob = np.argsort(a["k"]), np.argsort(b["k"])
    np.testing.assert_array_equal(a["k"][oa], b["k"][ob])
    np.testing.assert_array_equal(a["c"][oa], b["c"][ob])
    np.testing.assert_allclose(a["s"][oa], b["s"][ob], rtol=1e-3, atol=1e-3)


def test_int_auto_dense_range_miss_guarded(rng):
    """Keys fabricated past the ingest range after definition must fail
    loudly, not silently drop (unlike explicit dense=K)."""
    from dryad_tpu.exec.executor import StageFailedError

    ctx = DryadContext(num_partitions_=8)
    k = rng.integers(0, 20, 400).astype(np.int32)
    q = ctx.from_arrays({"k": k})

    def poison(table, _pi):
        t = {kk: np.asarray(vv).copy() for kk, vv in table.items()}
        t["k"] = t["k"] + 100  # outside the ingest-observed [0, 20)
        return t

    # apply_host breaks the provenance chain, so the rewrite must NOT
    # fire after it — group_by below the poison takes the sort path and
    # stays correct
    safe = q.apply_host(poison).group_by("k", {"c": ("count", None)})
    out = safe.collect()
    assert int(np.sum(out["c"])) == 400

    # but mutating the BOUND arrays after definition (same ingest node)
    # hits the guard
    ctx2 = DryadContext(num_partitions_=8)
    arrays = {"k": rng.integers(0, 20, 400).astype(np.int32)}
    q2 = ctx2.from_arrays(arrays).group_by("k", {"c": ("count", None)})
    arrays["k"][:] = arrays["k"] + 100  # post-definition mutation
    with pytest.raises(StageFailedError, match="ingest-time range"):
        q2.collect()


def test_scatter_strategy_matches_matmul(rng):
    from dryad_tpu.ops.pallas_bucket import bucket_sum_count

    n, K = 4000, 300
    k = rng.integers(0, K, n).astype(np.int32)
    v = rng.standard_normal(n).astype(np.float32)
    valid = rng.random(n) > 0.2
    s1, c1 = bucket_sum_count(k, [v], valid, K, strategy="scatter")
    s2, c2 = bucket_sum_count(k, [v], valid, K, strategy="matmul")
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2))
    np.testing.assert_allclose(
        np.asarray(s1[0]), np.asarray(s2[0]), atol=1e-3
    )
    ref = np.bincount(k[valid], weights=v[valid], minlength=K)
    np.testing.assert_allclose(np.asarray(s1[0]), ref, atol=1e-4)


def test_int_auto_dense_project_and_default_if_empty(rng):
    """project() (name-only) keeps the ingest bound; default_if_empty
    can fabricate a key, so it must break the bound (code-review r4)."""
    ctx = DryadContext(num_partitions_=8)
    k = rng.integers(0, 30, 400).astype(np.int32)
    v = rng.standard_normal(400).astype(np.float32)
    base = ctx.from_arrays({"k": k, "v": v, "x": v})

    q1 = base.project(["k", "v"]).group_by("k", {"c": ("count", None)})
    assert "group_reduce_dense" in _ops(
        lower([q1.node], ctx.config, ctx.dictionary)
    )

    q2 = (
        base.where(lambda c: c["v"] > 1e9)  # empty
        .default_if_empty({"k": 99})
        .group_by("k", {"c": ("count", None)})
    )
    assert "group_reduce_dense" not in _ops(
        lower([q2.node], ctx.config, ctx.dictionary)
    )
    out = q2.collect()  # sort path: the fabricated key 99 must survive
    assert out["k"].tolist() == [99] and out["c"].tolist() == [1]


def test_range_miss_never_persists_a_poisoned_checkpoint(rng, tmp_path):
    """A guarded dense stage whose miss counter fires must not have
    saved a checkpoint: re-running the identical (still-poisoned) query
    raises AGAIN instead of silently loading dropped-row aggregates
    (code-review r4)."""
    from dryad_tpu.exec.executor import StageFailedError

    ctx = DryadContext(
        num_partitions_=8,
        config=DryadConfig(checkpoint_dir=str(tmp_path / "ck")),
    )
    arrays = {"k": rng.integers(0, 20, 400).astype(np.int32)}
    q = ctx.from_arrays(arrays).group_by("k", {"c": ("count", None)})
    arrays["k"][:] = arrays["k"] + 100  # fabricate past the ingest range
    with pytest.raises(StageFailedError, match="ingest-time range"):
        q.collect()
    with pytest.raises(StageFailedError, match="ingest-time range"):
        q.collect()  # would silently succeed if the checkpoint leaked
    # a CLEAN guarded stage still checkpoints (after the drain)
    ctx2 = DryadContext(
        num_partitions_=8,
        config=DryadConfig(checkpoint_dir=str(tmp_path / "ck2")),
    )
    out = ctx2.from_arrays(
        {"k": rng.integers(0, 20, 400).astype(np.int32)}
    ).group_by("k", {"c": ("count", None)}).collect()
    assert int(np.sum(out["c"])) == 400
    saved = [
        e for e in ctx2.events.events()
        if e["kind"] == "stage_checkpoint_saved"
    ]
    assert saved, "clean guarded stage should checkpoint after the drain"


def test_per_ingest_vocab_gate_survives_big_ingest(rng):
    """A context that ingested a HUGE unrelated vocabulary no longer
    loses the dense path for later small-vocab queries: the gate and
    the coding tables key on the KEY COLUMN's own per-ingest
    vocabulary (round-3 weak item 7)."""
    small_limit = DryadConfig(auto_dense_limit=64)
    ctx = DryadContext(num_partitions_=8, config=small_limit)

    # blow past the limit with an unrelated ingest
    big_words = np.array([f"huge{i:05d}" for i in range(500)], object)
    ctx.from_arrays({"w": big_words})
    assert len(ctx.dictionary) > 64

    # a small-vocab table still rides the dense path...
    small = np.array(
        [f"s{i}" for i in rng.integers(0, 20, 800)], object
    )
    q = ctx.from_arrays({"w": small}).group_by("w", {"c": ("count", None)})
    kinds = _ops(lower([q.node], ctx.config, ctx.dictionary))
    assert "string_code" in kinds and "exchange_hash" not in kinds
    # ...with coding tables shrunk to ITS vocabulary, not the context's
    st = [
        op for s in lower([q.node], ctx.config, ctx.dictionary).stages
        for op in s.ops if op.kind == "string_code"
    ][0]
    assert st.params["table"].num_codes == len(np.unique(small))

    out = q.collect()
    uniq, counts = np.unique(small.astype(str), return_counts=True)
    got = dict(zip([str(w) for w in out["w"]], out["c"].tolist()))
    assert got == dict(zip(uniq.tolist(), counts.tolist()))

    # the big-vocab table itself falls back to the sort path, correctly
    qb = ctx.from_arrays({"w": big_words}).group_by(
        "w", {"c": ("count", None)}
    )
    assert "string_code" not in _ops(lower([qb.node], ctx.config, ctx.dictionary))
    ob = qb.collect()
    assert len(ob["w"]) == 500 and set(ob["c"].tolist()) == {1}


def test_subset_tables_with_where_chain(rng):
    """The vocab bound propagates through value-preserving operators
    (where/project), and select breaks it."""
    ctx = DryadContext(num_partitions_=8)
    words = np.array([f"t{i}" for i in rng.integers(0, 15, 600)], object)
    v = rng.standard_normal(600).astype(np.float32)
    base = ctx.from_arrays({"w": words, "v": v})
    q = base.where(lambda c: c["v"] > 0).project(["w"]).group_by(
        "w", {"c": ("count", None)}
    )
    st = [
        op for s in lower([q.node], ctx.config, ctx.dictionary).stages
        for op in s.ops if op.kind == "string_code"
    ]
    assert st and st[0].params["table"].num_codes == len(np.unique(words))
    out = q.collect()
    mask = v > 0
    uniq, counts = np.unique(words[mask].astype(str), return_counts=True)
    got = dict(zip([str(w) for w in out["w"]], out["c"].tolist()))
    assert got == dict(zip(uniq.tolist(), counts.tolist()))
