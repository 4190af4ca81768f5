"""Compile-once dictionary coding (static-vs-operand param split).

The string coding tables (``ops/stringcode.py``) ride compiled
programs as call-time device operands on a power-of-two shape palette
(``stringcode_runtime_tables``, default on): the executor's compile
cache keys on the palette TIER, so a widening out-of-core vocabulary
pays O(log vocab) XLA compiles instead of one per chunk, and the
executor's operand pool (``exec/operands.py``) scatters only the
widened table delta to the device.  Off = the legacy baked-constant
path, kept as the differential baseline these tests compare against.
"""

import math
import os
import subprocess
import sys

import numpy as np
import pytest

from dryad_tpu import DryadConfig, DryadContext


def _widening_chunks(nchunks, rows=800, base=50, step=40, seed=0):
    """Chunk stream whose per-chunk vocabulary widens steadily."""
    rng = np.random.default_rng(seed)
    final = base + (nchunks - 1) * step
    words = np.array([f"w{j:05d}" for j in range(final)])
    return (
        [{"w": rng.choice(words[: base + i * step], rows)}
         for i in range(nchunks)],
        final,
    )


def _run_widening(runtime: bool, nchunks: int = 16):
    cfg = DryadConfig(stringcode_runtime_tables=runtime)
    ctx = DryadContext(num_partitions_=8, config=cfg)
    chunks, final_vocab = _widening_chunks(nchunks)
    out = (
        ctx.from_stream(iter([dict(c) for c in chunks]))
        .group_by("w", {"c": ("count", None)})
        .collect()
    )
    return ctx, out, chunks, final_vocab


def _norm(out):
    order = np.argsort(np.asarray([str(s) for s in out["w"]]))
    return (
        [str(out["w"][i]) for i in order],
        np.asarray(out["c"])[order],
    )


def _dense_compiles(ctx):
    """xla_compile events of the dense-string lowering (the per-chunk
    partial group program and its merge/finalize kin)."""
    return [
        e for e in ctx.executor.events.events()
        if e["kind"] == "xla_compile" and "group_by" in e.get("stage", "")
    ]


def test_widening_stream_identical_results_and_bounded_compiles(mesh8):
    """Acceptance: on a widening-vocab stream the dense-group compile
    count is bounded by palette tiers (<= ceil(log2 vocab) + O(1)) with
    runtime tables on, vs O(chunks) off — and the results are
    byte-identical between the two modes."""
    nchunks = 16
    ctx_on, out_on, _, final_vocab = _run_widening(True, nchunks)
    ctx_off, out_off, _, _ = _run_widening(False, nchunks)

    w_on, c_on = _norm(out_on)
    w_off, c_off = _norm(out_off)
    assert w_on == w_off
    assert c_on.dtype == c_off.dtype
    assert np.array_equal(c_on, c_off)

    on = _dense_compiles(ctx_on)
    off = _dense_compiles(ctx_off)
    tier_bound = math.ceil(math.log2(final_vocab)) + 2
    assert len(on) <= tier_bound, (
        f"{len(on)} dense compiles with runtime tables on; palette "
        f"bound is {tier_bound} (vocab {final_vocab})"
    )
    # legacy bakes table content: every widening chunk recompiles the
    # per-chunk partial program
    per_chunk_off = [e for e in off if e["stage"] == "input+group_by"]
    assert len(per_chunk_off) >= nchunks - 1
    assert len(on) < len(off)


def test_widening_stream_jobmetrics_compile_count(mesh8):
    """JobMetrics.compile_count (the ROADMAP open item's measurable)
    drops with runtime tables on the same stream."""
    from dryad_tpu.obs.metrics import JobMetrics

    ctx_on, _, _, _ = _run_widening(True, 12)
    ctx_off, _, _, _ = _run_widening(False, 12)
    m_on = JobMetrics.from_events(ctx_on.executor.events.events())
    m_off = JobMetrics.from_events(ctx_off.executor.events.events())
    assert m_on.compile_count < m_off.compile_count


def test_operand_lookup_matches_baked(mesh8):
    """lookup() through runtime operands returns the same codes (and
    the same tier-static miss sentinel) as the baked-constant path."""
    import jax.numpy as jnp

    from dryad_tpu.columnar.schema import StringDictionary
    from dryad_tpu.ops.stringcode import build_tables

    d = StringDictionary()
    for i in range(37):
        d.add(f"s{i}")
    code_t, dec_t = build_tables(d)
    h0 = jnp.asarray(dec_t.words[:, 0])
    h1 = jnp.asarray(dec_t.words[:, 1])
    baked = np.asarray(code_t.lookup(h0, h1))
    ops = tuple(jnp.asarray(a) for a in code_t.operand_arrays())
    via_ops = np.asarray(code_t.lookup(h0, h1, operands=ops))
    assert np.array_equal(baked, via_ops)
    miss = np.asarray(
        code_t.lookup(
            jnp.full((3,), 0xDEAD, jnp.uint32),
            jnp.full((3,), 0xBEEF, jnp.uint32),
            operands=ops,
        )
    )
    assert miss.tolist() == [code_t.num_codes_padded] * 3


def test_decode_padded_buffer_precomputed_and_sliced(mesh8):
    """DecodeTable builds its zero-padded gather buffer ONCE at
    construction (no per-call np.concatenate) and both slice paths
    (baked / operand) read identical rows."""
    import jax.numpy as jnp

    from dryad_tpu.ops.stringcode import DecodeTable, palette_domain

    K = 11
    words = np.arange(K * 4, dtype=np.uint32).reshape(K, 4)
    dec = DecodeTable(words)
    R = 2 * palette_domain(K)
    assert dec.words_padded.shape == (R, 4)
    assert np.array_equal(dec.words_padded[:K], words)
    assert not dec.words_padded[K:].any()
    got = np.asarray(dec.slice_rows(4, 8))
    exp = dec.words_padded[4:12]
    assert np.array_equal(got, exp)
    got_op = np.asarray(
        dec.slice_rows(4, 8, operands=(jnp.asarray(dec.words_padded),))
    )
    assert np.array_equal(got_op, exp)


def _chain_pairs(K, S, start=5):
    """K pairs whose slot hashes all start at one slot of an S-slot
    table: the host build chains them K long."""
    pairs = np.zeros((K, 2), np.uint32)
    pairs[:, 0] = start + S * np.arange(K, dtype=np.uint32)
    return pairs


def test_palette_tiers_are_pow2_and_shared():
    from dryad_tpu.ops.stringcode import CodeTable, palette_domain

    assert [palette_domain(n) for n in (0, 1, 4, 5, 64, 65)] == [
        4, 4, 4, 8, 64, 128,
    ]
    rng = np.random.default_rng(0)
    # different contents in one domain tier share the signature
    # (interchangeable at call time) whatever their longest chain: the
    # device lookup is a merge, so no probe budget shapes its trace
    a = CodeTable(rng.integers(0, 2**32, (40, 2)).astype(np.uint32))
    b = CodeTable(rng.integers(0, 2**32, (60, 2)).astype(np.uint32))
    c = CodeTable(_chain_pairs(60, 128))
    assert a.num_slots == b.num_slots == c.num_slots == 2 * palette_domain(60)
    assert c.max_probe == 60 > 4 * max(a.max_probe, b.max_probe)
    assert a.operand_signature() == b.operand_signature()
    assert a.operand_signature() == c.operand_signature()
    assert a.operand_signature() == ("CodeTable", 128)
    assert len({a.operand_sha(), b.operand_sha(), c.operand_sha()}) == 3


def _lookup_case(name):
    """(table pairs, row words) of one edge of the merge lookup."""
    rng = np.random.default_rng(29)

    def fresh(k):
        return rng.integers(1, 2**32, (k, 2)).astype(np.uint32)

    def mixed(pairs, n):  # hits with repeats, and misses
        rows = pairs[rng.integers(0, len(pairs), n)].copy()
        gone = rng.random(n) < 0.3
        rows[gone] = fresh(int(gone.sum()))
        return rows

    zeros = np.zeros((6, 2), np.uint32)
    if name == "hits_misses_repeats":
        pairs = fresh(300)
        return pairs, mixed(pairs, 2000)
    if name == "zero_words_without_entry":  # empty slots are (0, 0) too
        pairs = fresh(50)
        return pairs, np.concatenate([zeros, mixed(pairs, 100)])
    if name == "zero_words_with_entry":
        pairs = fresh(50)
        pairs[17] = 0
        return pairs, np.concatenate([zeros, mixed(pairs, 100)])
    if name == "chain_past_any_probe_bound":
        pairs = _chain_pairs(60, 128)
        return pairs, mixed(pairs, 500)
    if name == "few_rows_large_table":
        pairs = fresh(5000)
        return pairs, mixed(pairs, 7)
    if name == "many_rows_small_table":
        pairs = fresh(3)
        return pairs, mixed(pairs, 20000)
    if name == "code_in_two_slices":  # S = 2^16: past one int32 word
        pairs = fresh(20000)
        return pairs, mixed(pairs, 3000)
    if name == "no_rows":
        return fresh(10), np.zeros((0, 2), np.uint32)
    if name == "empty_table":
        return np.zeros((0, 2), np.uint32), np.concatenate(
            [zeros, fresh(20)]
        )
    raise AssertionError(name)


@pytest.mark.parametrize("path", ["baked", "operands"])
@pytest.mark.parametrize("case", [
    "hits_misses_repeats", "zero_words_without_entry",
    "zero_words_with_entry", "chain_past_any_probe_bound",
    "few_rows_large_table", "many_rows_small_table",
    "code_in_two_slices", "no_rows", "empty_table",
])
def test_merge_lookup_matches_numpy_dictionary(case, path):
    """Every row gets the code a Python dictionary over the table's
    (h0, h1) pairs gives it, a miss the padded domain — through the
    baked constants and through runtime operands."""
    import jax
    import jax.numpy as jnp

    from dryad_tpu.ops.stringcode import CodeTable

    pairs, rows = _lookup_case(case)
    table = CodeTable(pairs)
    ref = {(int(a), int(b)): c for c, (a, b) in enumerate(pairs)}
    want = [
        ref.get((int(a), int(b)), table.num_codes_padded) for a, b in rows
    ]
    if path == "operands":
        ops = tuple(jnp.asarray(a) for a in table.operand_arrays())
        got = jax.jit(
            lambda a, b, o: table.lookup(a, b, operands=o)
        )(jnp.asarray(rows[:, 0]), jnp.asarray(rows[:, 1]), ops)
    else:
        got = jax.jit(table.lookup)(
            jnp.asarray(rows[:, 0]), jnp.asarray(rows[:, 1])
        )
    assert got.dtype == jnp.int32 and got.shape == (len(rows),)
    assert np.asarray(got).tolist() == want


def test_one_trace_serves_a_table_of_any_chain():
    """A lookup traced from a short-chained table, fed the arrays of a
    table of the same tier whose chain is 60 long (past the old loop's
    budget of 4 rounds for the first), still hits every row."""
    import jax
    import jax.numpy as jnp

    from dryad_tpu.ops.stringcode import CodeTable

    rng = np.random.default_rng(3)
    short = CodeTable(rng.integers(0, 2**32, (60, 2)).astype(np.uint32))
    pairs = _chain_pairs(60, 128)
    long_ = CodeTable(pairs)
    assert short.max_probe <= 4 < long_.max_probe
    traced = jax.jit(lambda a, b, o: short.lookup(a, b, operands=o))
    got = traced(
        jnp.asarray(pairs[:, 0]), jnp.asarray(pairs[:, 1]),
        tuple(jnp.asarray(a) for a in long_.operand_arrays()),
    )
    assert np.asarray(got).tolist() == list(range(60))


def test_lowered_lookup_has_no_loop_and_no_gather():
    """The lookup is two sorts and scans: its lowered text holds no
    ``while`` (the probe loop) and no gather of any size (the loop's
    three row-sized ones), through both operand modes."""
    import jax
    import jax.numpy as jnp

    from dryad_tpu.ops.stringcode import CodeTable

    table = CodeTable(_chain_pairs(60, 128))
    rows = jax.ShapeDtypeStruct((4096,), jnp.uint32)
    ops = tuple(
        jax.ShapeDtypeStruct(a.shape, a.dtype)
        for a in table.operand_arrays()
    )
    for text in (
        jax.jit(table.lookup).lower(rows, rows).as_text(),
        jax.jit(
            lambda a, b, o: table.lookup(a, b, operands=o)
        ).lower(rows, rows, ops).as_text(),
    ):
        assert "stablehlo.while" not in text
        assert "gather" not in text
        assert text.count("stablehlo.sort") == 2


def test_operand_pool_scatters_only_the_widened_delta(mesh8):
    """Appending within a palette tier re-uses the resident device
    buffer: the pool scatters the delta rows instead of re-uploading,
    and the device content matches the new table exactly."""
    from dryad_tpu.columnar.schema import StringDictionary
    from dryad_tpu.exec.operands import DeviceOperandPool
    from dryad_tpu.obs.metrics import MetricsRegistry
    from dryad_tpu.ops.stringcode import build_tables

    d = StringDictionary()
    for i in range(100):
        d.add(f"s{i}")
    code1, dec1 = build_tables(d)
    for i in range(100, 120):  # 100 -> 120 stays inside domain 128
        d.add(f"s{i}")
    code2, dec2 = build_tables(d)
    # same buffer layout (the pool's residency key)
    assert [a.shape for a in code1.operand_arrays()] == [
        a.shape for a in code2.operand_arrays()
    ]

    metrics = MetricsRegistry()
    pool = DeviceOperandPool(metrics=metrics)
    dev1 = pool.get(code1)
    assert pool.full_uploads == 1 and pool.delta_scatters == 0
    full_bytes = metrics.counter("operand_h2d_bytes")
    dev2 = pool.get(code2)
    assert pool.delta_scatters == 1 and pool.full_uploads == 1
    delta_bytes = metrics.counter("operand_h2d_bytes") - full_bytes
    assert 0 < delta_bytes < full_bytes / 2
    for got, want in zip(dev2, code2.operand_arrays()):
        assert np.array_equal(np.asarray(got), want)
    # the scattered buffers answer for the widened table through the
    # trace of the narrower one (one tier, one trace)
    import jax.numpy as jnp

    assert code1.operand_signature() == code2.operand_signature()
    codes = code1.lookup(
        jnp.asarray(dec2.words[:, 0]), jnp.asarray(dec2.words[:, 1]),
        operands=dev2,
    )
    assert np.asarray(codes).tolist() == list(range(120))
    # same content again: resident, no traffic
    dev3 = pool.get(code2)
    assert dev3 is dev2 and pool.hits == 1
    # decode table widens append-only too
    pool.get(dec1)
    pool.get(dec2)
    assert pool.delta_scatters == 2
    # stale tables (a retry of an earlier job) still resolve correctly
    back = pool.get(code1)
    for got, want in zip(back, code1.operand_arrays()):
        assert np.array_equal(np.asarray(got), want)


def test_subset_tables_append_only_in_insertion_order():
    """build_tables_subset orders codes by dictionary insertion rank:
    widening the subset never renumbers existing codes or moves their
    probe slots — the invariant the pool's delta scatter rides."""
    from dryad_tpu.columnar.schema import StringDictionary
    from dryad_tpu.ops.stringcode import build_tables_subset

    d = StringDictionary()
    hs = [d.add(f"v{i}") for i in range(90)]
    c1, dec1 = build_tables_subset(d, np.asarray(hs[:70], np.uint64))
    c2, dec2 = build_tables_subset(d, np.asarray(hs[:90], np.uint64))
    assert c1.num_slots == c2.num_slots  # same palette tier
    assert np.array_equal(dec2.words[: c1.num_codes], dec1.words)
    filled = c1.slots_code >= 0
    assert np.array_equal(c2.slots_code[filled], c1.slots_code[filled])
    assert np.array_equal(c2.slots_h0[filled], c1.slots_h0[filled])


def test_fingerprints_process_stable():
    """__hash__/_fp derive from the content sha, not process-salted
    Python hash(): a fresh interpreter with a different PYTHONHASHSEED
    computes the identical fingerprint."""
    from dryad_tpu.ops.stringcode import CodeTable, DecodeTable

    pairs = (np.arange(24, dtype=np.uint32).reshape(12, 2) * 2654435761
             ).astype(np.uint32)
    words = np.arange(48, dtype=np.uint32).reshape(12, 4)
    fp_c = CodeTable(pairs)._fp
    fp_d = DecodeTable(words)._fp
    assert fp_c == int(CodeTable(pairs)._sha[:16], 16)
    prog = (
        "import numpy as np\n"
        "from dryad_tpu.ops.stringcode import CodeTable, DecodeTable\n"
        "pairs = (np.arange(24, dtype=np.uint32).reshape(12, 2)"
        " * 2654435761).astype(np.uint32)\n"
        "words = np.arange(48, dtype=np.uint32).reshape(12, 4)\n"
        "print(CodeTable(pairs)._fp, DecodeTable(words)._fp)\n"
    )
    env = dict(os.environ, PYTHONHASHSEED="4242", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env=env, timeout=120, cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert out.returncode == 0, out.stderr
    got_c, got_d = (int(x) for x in out.stdout.split())
    assert (got_c, got_d) == (fp_c, fp_d)


def test_runtime_tables_off_keeps_pool_idle(mesh8):
    """The legacy baked path never touches the operand pool (the
    differential baseline stays the pre-split engine)."""
    rng = np.random.default_rng(1)
    words = np.array([f"k{i}" for i in range(50)])

    def run(runtime):
        cfg = DryadConfig(stringcode_runtime_tables=runtime)
        ctx = DryadContext(num_partitions_=8, config=cfg)
        q = ctx.from_arrays({"w": rng.choice(words, 500)})
        out = q.group_by("w", {"c": ("count", None)}).collect()
        assert int(np.asarray(out["c"]).sum()) == 500
        return ctx.executor.operand_pool

    assert run(False).full_uploads == 0
    assert run(True).full_uploads > 0


def test_in_core_widening_reuses_compiled_program(mesh8):
    """In-core twin of the stream test: two group_by jobs whose
    dictionary widened within a palette domain share the compiled
    dense program (the second job's tables arrive purely as operands —
    a probe-bound tier crossing may still cost at most one compile)."""
    cfg = DryadConfig(stringcode_runtime_tables=True)
    ctx = DryadContext(num_partitions_=8, config=cfg)
    rng = np.random.default_rng(2)
    # 70 -> 100 distinct words: both inside palette domain 128
    w1 = np.array([f"a{i}" for i in range(70)])
    out1 = (
        ctx.from_arrays({"w": np.concatenate([w1, rng.choice(w1, 330)])})
        .group_by("w", {"c": ("count", None)}).collect()
    )
    n1 = len([
        e for e in ctx.executor.events.events()
        if e["kind"] == "xla_compile" and "group_by" in e["stage"]
    ])
    # widen the context dictionary inside the same palette domain
    w2 = np.array([f"a{i}" for i in range(100)])
    out2 = (
        ctx.from_arrays({"w": np.concatenate([w2, rng.choice(w2, 300)])})
        .group_by("w", {"c": ("count", None)}).collect()
    )
    n2 = len([
        e for e in ctx.executor.events.events()
        if e["kind"] == "xla_compile" and "group_by" in e["stage"]
    ])
    assert int(np.asarray(out1["c"]).sum()) == 400
    assert int(np.asarray(out2["c"]).sum()) == 400
    assert n2 - n1 <= 1, (
        "within-domain widen recompiled more than a probe-tier change"
    )
    # the widened table reached the device as a scatter, not an upload
    assert ctx.executor.operand_pool.delta_scatters > 0


def test_dict_miss_still_loud_with_runtime_tables(mesh8):
    """Fabricated hash words (absent from the dictionary) still fail
    loudly through the operand path's tier-static miss sentinel."""
    from dryad_tpu.exec.executor import StageFailedError

    cfg = DryadConfig(stringcode_runtime_tables=True)
    ctx = DryadContext(num_partitions_=8, config=cfg)
    q = ctx.from_arrays({"w": np.array([f"x{i}" for i in range(20)] * 5)})

    def fabricate(cols):
        out = dict(cols)
        out["w#h0"] = out["w#h0"] + np.uint32(7)  # no longer in the dict
        return out

    bad = q.select(fabricate, schema=q.schema)
    with pytest.raises(StageFailedError, match="dense"):
        bad.group_by("w", {"c": ("count", None)}).collect()
