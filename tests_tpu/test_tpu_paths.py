"""TPU-only code paths on real hardware."""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def jaxmod():
    import jax

    assert jax.devices()[0].platform == "tpu"
    return jax


def test_pallas_bucket_kernel_on_chip(jaxmod, ):
    """The Pallas MXU kernel (not the XLA fallback) computes correct
    bucket sums/counts on the chip."""
    import jax.numpy as jnp

    from dryad_tpu.ops.pallas_bucket import bucket_sum_count

    rng = np.random.default_rng(0)
    n, K = 1 << 16, 512
    k = rng.integers(0, K, n).astype(np.int32)
    v = rng.standard_normal(n).astype(np.float32)
    sums, cnt = bucket_sum_count(
        jnp.asarray(k), [jnp.asarray(v)], jnp.ones((n,), jnp.bool_), K,
        interpret=None,  # Pallas path on TPU
    )
    ref_cnt = np.bincount(k, minlength=K)
    ref_sum = np.bincount(k, weights=v, minlength=K)
    np.testing.assert_array_equal(np.asarray(cnt), ref_cnt)
    # Split-bf16 error contract: ~2^-16 per
    # ELEMENT, so the bound scales with the per-bucket sum of |v|
    # (cancellation makes a pure rtol vs the result meaningless).
    ref_abs = np.bincount(k, weights=np.abs(v), minlength=K)
    tol = 2.0**-16 * ref_abs + 1e-6
    err = np.abs(np.asarray(sums[0]) - ref_sum)
    worst = int(np.argmax(err - tol))
    assert np.all(err <= tol), (
        f"bucket {worst}: err {err[worst]:.3e} exceeds split-bf16 "
        f"bound {tol[worst]:.3e}"
    )


def test_group_reduce_on_chip(jaxmod):
    import jax.numpy as jnp

    from dryad_tpu.columnar.batch import ColumnBatch
    from dryad_tpu.ops.segmented import AggSpec, group_reduce

    rng = np.random.default_rng(1)
    n = 1 << 14
    k = rng.integers(0, 64, n).astype(np.int32)
    v = rng.standard_normal(n).astype(np.float32)
    b = ColumnBatch(
        {"k": jnp.asarray(k), "v": jnp.asarray(v)},
        jnp.ones((n,), jnp.bool_),
    )
    out = group_reduce(b, ["k"], [AggSpec("sum", "v", "s"),
                                  AggSpec("count", None, "c")])
    valid = np.asarray(out.valid)
    got = dict(zip(np.asarray(out.data["k"])[valid].tolist(),
                   np.asarray(out.data["c"])[valid].tolist()))
    ref = {int(key): int((k == key).sum()) for key in np.unique(k)}
    assert got == ref


def test_wordcount_end_to_end_on_chip(jaxmod):
    from dryad_tpu import DryadContext

    rng = np.random.default_rng(2)
    words = np.array([f"w{i:03d}" for i in rng.integers(0, 100, 5000)], object)
    ctx = DryadContext()
    out = (
        ctx.from_arrays({"w": words})
        .group_by("w", {"c": ("count", None)})
        .order_by([("c", True)])
        .collect()
    )
    assert int(np.sum(out["c"])) == 5000


def test_auto_dense_wordcount_on_chip(jaxmod):
    """The auto-dense STRING group_by (string_code + Pallas bucket +
    decode) lowers and computes correctly on the chip, and the plan is
    shuffle-free."""
    from dryad_tpu import DryadContext
    from dryad_tpu.plan.lower import lower

    rng = np.random.default_rng(3)
    words = np.array(
        [f"tok{i:04d}" for i in rng.integers(0, 300, 8000)], object
    )
    ctx = DryadContext()
    q = ctx.from_arrays({"w": words}).group_by("w", {"c": ("count", None)})
    kinds = [
        op.kind
        for st in lower([q.node], ctx.config, ctx.dictionary).stages
        for op in st.ops
    ]
    assert "string_code" in kinds and "exchange_hash" not in kinds
    out = q.collect()
    uniq, counts = np.unique(words.astype(str), return_counts=True)
    got = dict(zip([str(w) for w in out["w"]], out["c"].tolist()))
    assert got == dict(zip(uniq.tolist(), counts.tolist()))


def test_split_bf16_sums_on_chip(jaxmod):
    """Round-4 kernel: split-bf16 value accumulation at the MXU's
    native rate — integer values exact to 2^24 (3 terms), float values
    ~2^-16 (2 terms) — on the real chip."""
    import jax.numpy as jnp

    from dryad_tpu.ops.pallas_bucket import bucket_sum_count

    rng = np.random.default_rng(4)
    n, K = 1 << 16, 1024
    k = rng.integers(0, K, n).astype(np.int32)
    iv = rng.integers(0, (1 << 24) - 1, n).astype(np.int32)
    fv = np.abs(rng.standard_normal(n)).astype(np.float32)
    sums, cnt = bucket_sum_count(
        jnp.asarray(k), [jnp.asarray(iv), jnp.asarray(fv)],
        jnp.ones((n,), jnp.bool_), K, strategy="matmul",
    )
    ref_i = np.bincount(k, weights=iv.astype(np.float64), minlength=K)
    ref_f = np.bincount(k, weights=fv.astype(np.float64), minlength=K)
    np.testing.assert_allclose(np.asarray(sums[0]), ref_i, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sums[1]), ref_f, rtol=3e-5)
    np.testing.assert_array_equal(
        np.asarray(cnt), np.bincount(k, minlength=K)
    )


def test_scatter_strategy_on_chip(jaxmod):
    """The scatter-add bucket strategy (probe decision seam) computes
    correctly on the chip."""
    import jax.numpy as jnp

    from dryad_tpu.ops.pallas_bucket import bucket_sum_count

    rng = np.random.default_rng(5)
    n, K = 1 << 15, 700
    k = rng.integers(0, K, n).astype(np.int32)
    v = rng.standard_normal(n).astype(np.float32)
    valid = rng.random(n) > 0.1
    sums, cnt = bucket_sum_count(
        jnp.asarray(k), [jnp.asarray(v)], jnp.asarray(valid), K,
        strategy="scatter",
    )
    np.testing.assert_array_equal(
        np.asarray(cnt), np.bincount(k[valid], minlength=K)
    )
    np.testing.assert_allclose(
        np.asarray(sums[0]),
        np.bincount(k[valid], weights=v[valid], minlength=K),
        atol=1e-3,
    )


def test_int_auto_dense_on_chip(jaxmod):
    """A plain group_by over an ingest-bounded INT32 key rides the
    Pallas bucket path on the chip (shuffle-free plan, correct
    counts)."""
    from dryad_tpu import DryadContext
    from dryad_tpu.plan.lower import lower

    rng = np.random.default_rng(6)
    ctx = DryadContext()
    tbl = {
        "k": rng.integers(0, 200, 20000).astype(np.int32),
        "v": rng.standard_normal(20000).astype(np.float32),
    }
    q = ctx.from_arrays(tbl).group_by(
        "k", {"c": ("count", None), "s": ("sum", "v")}
    )
    kinds = [
        op.kind
        for st in lower([q.node], ctx.config, ctx.dictionary).stages
        for op in st.ops
    ]
    assert "group_reduce_dense" in kinds and "exchange_hash" not in kinds
    out = q.collect()
    ref = np.bincount(tbl["k"], minlength=200)
    got = dict(zip(out["k"].tolist(), out["c"].tolist()))
    assert got == {int(i): int(c) for i, c in enumerate(ref) if c}


def test_deferred_overflow_window_on_chip(jaxmod):
    """The speculative dispatch window (one batched overflow readback
    per k shuffle stages) executes correctly on the chip."""
    from dryad_tpu import DryadContext
    from dryad_tpu.exec.events import EventLog

    rng = np.random.default_rng(7)
    ctx = DryadContext()
    ev = EventLog(None)
    ctx.executor.events = ev
    kk = (rng.integers(0, 50, 6000) - 1).astype(np.int32)  # sort path
    a = ctx.from_arrays(
        {"k": kk, "v": np.ones(6000, np.float32)}
    ).group_by("k", {"s": ("sum", "v")})
    b = ctx.from_arrays({"k": kk}).group_by("k", {"n": ("count", None)})
    j = a.join(b, "k", strategy="shuffle").collect()
    assert len(j["k"]) == len(np.unique(kk))
    kinds = [e["kind"] for e in ev.events()]
    assert "overflow_drain" in kinds


def test_sort_carry_on_chip(jaxmod):
    """The operand-carrying sort (round-4 rewrite of every
    take(sort_order(...)) site) matches the permutation form on the
    real chip, where the two lower very differently (one variadic
    sort vs sort + per-column gathers)."""
    import jax.numpy as jnp

    from dryad_tpu.ops.sort import (
        sort_carry,
        sort_order_by_operands,
    )
    from dryad_tpu.ops.sortkeys import to_sortable_u32

    rng = np.random.default_rng(9)
    n = 1 << 14
    keys = jnp.asarray(rng.integers(-5000, 5000, n).astype(np.int32))
    valid = jnp.asarray(rng.random(n) < 0.85)
    pf = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    ops = [to_sortable_u32(keys)]

    order = np.asarray(sort_order_by_operands(ops, valid))
    v, (sk,), (spf,) = sort_carry(ops, valid, [pf])
    np.testing.assert_array_equal(np.asarray(v), np.asarray(valid)[order])
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(ops[0])[order])
    np.testing.assert_array_equal(np.asarray(spf), np.asarray(pf)[order])


def test_exchange_carry_on_chip(jaxmod):
    """``order_by`` and a sort-path ``group_by`` through DryadContext
    on the chip, where the exchange layout and ``resize`` carry the
    columns through ``lax.sort`` (the default here, and only here),
    against the LocalDebug interpreter (``exec/localdebug.py``).  On
    one chip the plan's exchanges trace nothing (the ``dispatch`` span
    counts them, ``xchg_elided``) and the two queries are their
    ``local_sort`` and fold alone, so there the layout and ``resize``
    are driven directly, against NumPy."""
    import jax
    import jax.numpy as jnp

    from dryad_tpu import DryadContext
    from dryad_tpu.columnar.batch import ColumnBatch
    from dryad_tpu.ops import shuffle as SH
    from dryad_tpu.ops.sort import _carry_profitable
    from dryad_tpu.plan.lower import lower

    assert _carry_profitable()
    rng = np.random.default_rng(25)
    n = 1 << 16
    tbl = {
        # one negative key keeps the dense rewrite off: the hash exchange
        "k": (rng.integers(0, 3000, n) - 1).astype(np.int32),
        "v": rng.standard_normal(n).astype(np.float32),
    }
    ctx, ref = DryadContext(), DryadContext(local_debug=True)

    def queries(c):
        t = c.from_arrays(tbl)
        return (
            t.order_by(["k", "v"]),
            t.group_by("k", {"c": ("count", None), "s": ("sum", "v")}),
        )

    kinds = [
        {op.kind for st in lower([q.node], ctx.config, ctx.dictionary).stages
         for op in st.ops}
        for q in queries(ctx)
    ]
    assert {"exchange_range", "resize"} <= kinds[0], kinds[0]
    assert {"exchange_hash", "resize"} <= kinds[1], kinds[1]

    (sorted_, grouped), (want_sorted, want_grouped) = (
        [q.collect() for q in queries(c)] for c in (ctx, ref)
    )
    for name in ("k", "v"):  # same rows, same order, payload on its key
        np.testing.assert_array_equal(sorted_[name], want_sorted[name])
    at, want_at = np.argsort(grouped["k"]), np.argsort(want_grouped["k"])
    np.testing.assert_array_equal(grouped["k"][at], want_grouped["k"][want_at])
    np.testing.assert_array_equal(grouped["c"][at], want_grouped["c"][want_at])
    np.testing.assert_allclose(
        grouped["s"][at], want_grouped["s"][want_at], rtol=1e-4, atol=1e-4
    )
    one_chip = len(jaxmod.devices()) == 1
    dispatched = [e for e in ctx.events.events()
                  if e["kind"] == "span" and e.get("cat") == "execute"]
    assert [e["xchg_elided"] for e in dispatched] == [int(one_chip)] * 2
    rounds = [e for e in ctx.events.events() if e["kind"] == "exchange_round"]
    assert len(rounds) == (0 if one_chip else 2)

    # the bucket layout over three destinations and the compaction, jitted
    # on one chip: rows side by side by destination, in their own order
    valid = rng.random(n) < 0.8
    dest = rng.integers(0, 3, n).astype(np.int32)
    batch = ColumnBatch(
        {c: jnp.asarray(a) for c, a in tbl.items()}, jnp.asarray(valid))
    cols, offsets, counts, overflow = jax.jit(
        lambda b, d: SH._bucket_layout(b, d, 3, n))(batch, jnp.asarray(dest))
    order = np.argsort(np.where(valid, dest, 3), kind="stable")
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(dest[valid], minlength=3))
    np.testing.assert_array_equal(
        np.asarray(offsets), np.concatenate([[0], np.cumsum(np.asarray(counts))]))
    for name in ("k", "v"):
        assert cols[name].shape == (2 * n,)  # B more slots than the batch
        np.testing.assert_array_equal(
            np.asarray(cols[name])[: valid.sum()], tbl[name][order][: valid.sum()])
    assert not bool(overflow)
    packed, dropped = jax.jit(lambda b: SH.resize(b, n // 2))(batch)
    assert bool(dropped) and packed.capacity == n // 2
    np.testing.assert_array_equal(
        np.asarray(packed.data["v"]), tbl["v"][valid][: n // 2])


def _split_bf16_bound(k, v, K):
    """Per-bucket error bound of 2-term split-bf16 float sums: ~2^-16
    per ELEMENT, so it scales with the bucket's sum of |v|."""
    return 2.0**-16 * np.bincount(k, weights=np.abs(v), minlength=K) + 1e-6


def test_pallas_bucket_stacked_boundary_on_chip(jaxmod):
    """K = 2^14: ``a_pad`` = 128, the last shape that takes the STACKED
    formulation (count + one float = 3 planes x 128 sublanes in one
    dot) — the shape WordCount at the bench vocabulary compiles."""
    import jax.numpy as jnp

    from dryad_tpu.ops.pallas_bucket import (
        _hi_width,
        _stacking_enabled,
        bucket_sum_count,
    )

    K = 1 << 14
    assert _hi_width(K) == 128 and _stacking_enabled(_hi_width(K))
    rng = np.random.default_rng(10)
    n = 1 << 20
    k = rng.integers(0, K, n).astype(np.int32)
    v = rng.standard_normal(n).astype(np.float32)
    valid = rng.random(n) > 0.05
    sums, cnt = bucket_sum_count(
        jnp.asarray(k), [jnp.asarray(v)], jnp.asarray(valid), K,
        strategy="matmul",
    )
    np.testing.assert_array_equal(
        np.asarray(cnt), np.bincount(k[valid], minlength=K)
    )
    ref = np.bincount(k[valid], weights=v[valid], minlength=K)
    err = np.abs(np.asarray(sums[0]) - ref)
    assert np.all(err <= _split_bf16_bound(k[valid], v[valid], K))


def test_pallas_bucket_per_term_on_chip(jaxmod):
    """K = 2^17 (the auto-dense limit): ``a_pad`` = 1024, the PER-TERM
    formulation, with count + one int + one float column."""
    import jax.numpy as jnp

    from dryad_tpu.ops.pallas_bucket import (
        _hi_width,
        _stacking_enabled,
        bucket_sum_count,
    )

    K = 1 << 17
    assert _hi_width(K) == 1024 and not _stacking_enabled(_hi_width(K))
    rng = np.random.default_rng(11)
    n = 1 << 20
    k = rng.integers(0, K, n).astype(np.int32)
    iv = rng.integers(0, 1 << 20, n).astype(np.int32)
    fv = rng.standard_normal(n).astype(np.float32)
    sums, cnt = bucket_sum_count(
        jnp.asarray(k), [jnp.asarray(iv), jnp.asarray(fv)],
        jnp.ones((n,), jnp.bool_), K, strategy="matmul",
    )
    np.testing.assert_array_equal(
        np.asarray(cnt), np.bincount(k, minlength=K)
    )
    # integers: 3 split terms, exact while bucket totals stay < 2^24
    ref_i = np.bincount(k, weights=iv.astype(np.float64), minlength=K)
    assert ref_i.max() < 2**24
    np.testing.assert_array_equal(np.asarray(sums[0]), ref_i)
    ref_f = np.bincount(k, weights=fv, minlength=K)
    err = np.abs(np.asarray(sums[1]) - ref_f)
    assert np.all(err <= _split_bf16_bound(k, fv, K))


def test_fetch_trim_across_the_chips(jaxmod):
    """The copy back of a group-by's answer over every chip of the
    machine (the four-chip host: a ``shard_map`` slice of each shard's
    padded columns): the trimmed answer is NumPy's, and what was copied
    is a fraction of the capacity's bytes."""
    from dryad_tpu import DryadContext

    P = len(jaxmod.devices())
    rng = np.random.default_rng(31)
    # 2^21 rows: on one chip the answer keeps its input's capacity (no
    # exchange, no slack), and 2^21 slots of 13 B are over the 16 MiB
    # under which a fetch does not ask
    n, groups = 1 << 21, 1 << 16
    # one negative key keeps the dense rewrite off: the hash exchange
    # and the segmented fold, whose answer is a prefix of each shard
    k = (rng.integers(0, groups, n) - 1).astype(np.int32)
    v = rng.standard_normal(n).astype(np.float32)
    ctx = DryadContext(num_partitions_=P)
    out = ctx.from_arrays({"k": k, "v": v}).group_by(
        "k", {"c": ("count", None), "s": ("sum", "v")}).collect()

    counts = np.bincount(k + 1, minlength=groups)
    sums = np.bincount(k + 1, weights=v, minlength=groups)
    present = np.flatnonzero(counts)
    order = np.argsort(out["k"])
    np.testing.assert_array_equal(out["k"][order], present - 1)
    np.testing.assert_array_equal(out["c"][order], counts[present])
    abs_sums = np.bincount(k + 1, weights=np.abs(v), minlength=groups)
    tol = (counts[present] + 8) * 2.0**-23 * abs_sums[present] + 1e-6
    assert np.all(np.abs(out["s"][order] - sums[present]) <= tol)

    spans = {e["name"]: e for e in ctx.events.events() if e["kind"] == "span"}
    trim, copy, decode = (spans[n] for n in ("fetch_trim", "fetch_copy", "decode"))
    assert trim["trimmed"] == 1 and trim["shards"] == P == decode["shards"]
    assert trim["count"] == len(present) == decode["rows"]
    # mask + key + count + sum: 13 B a slot, of the slots fetched
    assert copy["bytes"] == 13 * decode["fetched"] < 13 * decode["capacity"] // 4
    metrics = ctx.executor.metrics
    assert metrics.total("d2h_bytes") == copy["bytes"]
    assert (metrics.total("d2h_bytes_trimmed")
            == 13 * (decode["capacity"] - decode["fetched"]))


def test_the_third_key_word_decides_at_the_cells_rows(jaxmod):
    """``sort-100b-1c``'s query at the cell's rows on a table whose
    keys all share bytes 0 - 7, so that bytes 8 and 9, the half-empty
    third word, decide every comparison (uniform keys never tie on
    eight bytes at 2^23 rows: the timed data cannot show it), with
    duplicates; the 90-byte payload follows, through the chip's own
    form of the carried sort."""
    import json
    import os

    from dryad_tpu import DryadContext

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "traffic", "sort_100b.json")) as fh:
        rows = json.load(fh)["rows"]
    rng = np.random.default_rng(32)
    key = np.empty((rows, 10), np.uint8)
    key[:, :8] = rng.integers(0, 256, 8, dtype=np.uint8)
    tail = rng.integers(0, 1 << 16, rows, dtype=np.uint32)
    key[:, 8], key[:, 9] = tail >> 8, tail & 0xFF
    # a payload that is a function of the tail and of the byte's place
    payload = ((tail[:, None] * np.uint32(2654435761)
                + np.arange(90, dtype=np.uint32) * np.uint32(40503))
               >> np.uint32(11)).astype(np.uint8)
    ctx = DryadContext(num_partitions_=1)
    out = ctx.from_arrays({"key": key, "payload": payload}).order_by(["key"]).collect()
    order = np.argsort(tail, kind="stable")
    assert out["key"].shape == (rows, 10) and out["payload"].shape == (rows, 90)
    assert out["key"].dtype == out["payload"].dtype == np.uint8
    np.testing.assert_array_equal(out["key"], key[order])
    # duplicates carry equal payloads, so stability cannot hide a swap
    np.testing.assert_array_equal(out["payload"], payload[order])
    dispatched = [e for e in ctx.events.events()
                  if e["kind"] == "span" and e.get("cat") == "execute"]
    assert {e["row_words"] for e in dispatched} == {26}


def test_the_multi_output_job_on_chip(jaxmod):
    """``applyfork-1c``'s job at 2^21 rows (9 B a slot: both branches
    are over the 16 MiB under which a fetch does not ask; 2^20 would be
    under it) through the cell's own ``bind`` and ``compare``: one
    ``collect`` of three answers, ONE dispatch, the sorted branch a
    trimmed copy, the branch that is passed through copied whole."""
    import importlib.util
    import os

    from dryad_tpu import DryadContext

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_job_applyfork",
        os.path.join(root, "benchmarks", "jobs", "applyfork.py"))
    job = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(job)
    params = {"rows": 1 << 21, "hot_eighths": 3}
    table = job.make_table(np.random.default_rng([38, 1]), params, None, 0)
    ctx = DryadContext(num_partitions_=1)
    bound = job.bind(ctx, table, params)
    for answer in (bound.collect(), bound.collect()):
        checks = job.compare(table, answer, params)
        assert len(checks) == 7, checks
        assert all(value <= limit for value, limit in checks.values()), checks
    spans = [e for e in ctx.events.events() if e["kind"] == "span"]
    collects = [e for e in spans if e["name"] == "collect"]
    assert [e["outputs"] for e in collects] == [3, 3]
    assert len([e for e in spans if e.get("cat") == "execute"]) == 2
    trims = [e for e in spans if e["name"] == "fetch_trim"]
    assert [(e["output"], e["trimmed"]) for e in trims] == [(0, 1), (1, 0)] * 2
    decodes = [e for e in spans if e["name"] == "decode"][:3]
    assert decodes[0]["fetched"] < 1.2 * decodes[0]["rows"]
    assert decodes[1]["fetched"] == decodes[1]["capacity"] == params["rows"]
    assert decodes[0]["rows"] + decodes[1]["rows"] == params["rows"]


def test_tpch_q1_on_chip(jaxmod):
    """``tpch-q1-1c``'s job at 2^20 slots (250,000 orders, about a
    million rows) through the cell's own ``bind`` and ``compare``: the
    DATE predicate, the exact DECIMAL ``select`` (32 x 32 -> 64 and 64 x
    32 -> 64 multiplies), two group keys and five 64-bit sum channels
    folded on the chip, fresh and again, every sum equal to the NumPy
    int64 reference to the unit; the stage's dispatch says what the
    widest fold carries."""
    import importlib.util
    import os

    from dryad_tpu import DryadContext

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_job_tpch_q1",
        os.path.join(root, "benchmarks", "jobs", "tpch_q1.py"))
    job = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(job)
    params = {"orders": 250_000, "parts": 33_334, "slots": 1 << 20,
              "delta_days": 90, "partitions": 1}
    table = job.make_table(np.random.default_rng([49, 1]), params, None, 0)
    assert len(table["arrays"]["l_quantity"]) < params["slots"]
    ctx = DryadContext(num_partitions_=1)
    bound = job.bind(ctx, table, params)
    for answer in (bound.collect(), bound.collect()):
        checks = job.compare(table, answer, params)
        assert len(checks) == 9, checks
        assert all(value <= limit for value, limit in checks.values()), checks
        assert len(answer["count_order"]) == 4
    dispatched = [e for e in ctx.events.events()
                  if e["kind"] == "span" and e.get("cat") == "execute"]
    assert len(dispatched) == 2
    assert {(e["agg64_channels"], e["agg_state_words"], e["group_keys"])
            for e in dispatched} == {(5, 11, 2)}
    assert {e["xchg_elided"] for e in dispatched} == {2}


def test_the_user_defined_combiner_on_chip(jaxmod):
    """``groupby-skew-4c``'s query at 2^20 rows a chip over every chip
    there is, through the cell's own ``bind`` and ``compare``: the
    scan that traces the user's ``merge`` runs over a hottest group of
    6% of the rows, and across chips the exchange reads back with its
    overflow flag what the combiner left and what every chip received
    (no overflow at the default slack; nothing observed on one chip)."""
    import importlib.util
    import os

    from dryad_tpu import DryadContext

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_job_groupby_skew",
        os.path.join(root, "benchmarks", "jobs", "groupby_skew.py"))
    job = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(job)
    chips = len(jaxmod.devices())
    params = {"rows": chips << 20, "groups": 1 << 17, "zipf_theta": 0.99,
              "partitions": chips}
    table = job.make_table(np.random.default_rng([41, 1]), params, None, 0)
    ctx = DryadContext(num_partitions_=chips)
    bound = job.bind(ctx, table, params)
    for answer in (bound.collect(), bound.collect()):
        checks = job.compare(table, answer, params)
        assert len(checks) == 7, checks
        assert all(value <= limit for value, limit in checks.values()), checks
    events = ctx.events.events()
    assert not [e for e in events if e["kind"] == "stage_overflow"]
    seen = [e for e in events if e["kind"] == "exchange_observed"]
    assert len(seen) == (2 if chips > 1 else 0)
    for e in seen:
        assert e["combine_rows_in"] == params["rows"] and e["boost"] == 1
        assert sum(e["recv_rows"]) == e["combine_rows_out"] < params["rows"] // 2
        assert max(e["recv_rows"]) * chips < 1.05 * e["combine_rows_out"]
