"""RangePartition API coverage — the analog of the reference's
``RangePartitionAPICoverageTests.cs`` (842 LoC of overload coverage):
key types, directions, multi-key chains, boundary correctness, skew,
and interaction with order_by / assume_range_partition."""

import numpy as np
import pytest

from dryad_tpu import ColumnBatch, DryadContext


@pytest.fixture
def ctx():
    return DryadContext(num_partitions_=8)


def _partition_ranges(ctx, q, col, desc=False):
    """Collect (partition_index, min, max) via apply(with_index)."""
    import jax.numpy as jnp

    from dryad_tpu.columnar.schema import ColumnType

    def tag(batch, idx):
        return ColumnBatch(
            dict(batch.data, **{"pid": jnp.full(
                (batch.capacity,), idx, jnp.int32
            )}),
            batch.valid,
        )

    out = q.apply(
        tag, schema=q.schema.with_field("pid", ColumnType.INT32),
        with_index=True,
    ).collect()
    spans = {}
    for pid in set(out["pid"].tolist()):
        vals = out[col][out["pid"] == pid]
        if len(vals):
            spans[pid] = (vals.min(), vals.max())
    return spans


def test_int_keys_ascending_ranges_disjoint(ctx, rng):
    v = rng.integers(-1000, 1000, 4000).astype(np.int32)
    q = ctx.from_arrays({"k": v}).range_partition("k")
    spans = _partition_ranges(ctx, q, "k")
    ordered = [spans[p] for p in sorted(spans)]
    for (lo1, hi1), (lo2, hi2) in zip(ordered, ordered[1:]):
        assert hi1 <= lo2, "ascending partition ranges must be disjoint"


def test_float_keys_descending(ctx, rng):
    v = rng.standard_normal(4000).astype(np.float32)
    q = ctx.from_arrays({"k": v}).range_partition([("k", True)])
    spans = _partition_ranges(ctx, q, "k")
    ordered = [spans[p] for p in sorted(spans)]
    for (lo1, _hi1), (_lo2, hi2) in zip(ordered, ordered[1:]):
        assert lo1 >= hi2, "descending partition ranges must be disjoint"


def test_rows_preserved(ctx, rng):
    v = rng.integers(0, 100, 2048).astype(np.int32)
    out = ctx.from_arrays({"k": v}).range_partition("k").collect()
    assert sorted(out["k"].tolist()) == sorted(v.tolist())


def test_string_keys(ctx):
    words = np.array(
        [w for w in "the quick brown fox jumps over lazy dog".split()] * 50,
        object,
    )
    out = ctx.from_arrays({"w": words}).range_partition("w").collect()
    assert sorted(out["w"]) == sorted(words)


def test_skewed_keys_all_equal(ctx):
    v = np.zeros(2000, np.int32)
    out = ctx.from_arrays({"k": v}).range_partition("k").collect()
    assert len(out["k"]) == 2000


def test_order_by_after_range_partition(ctx, rng):
    v = rng.standard_normal(3000).astype(np.float32)
    out = (
        ctx.from_arrays({"k": v})
        .range_partition("k")
        .order_by([("k", False)])
        .collect()
    )
    np.testing.assert_allclose(out["k"], np.sort(v), rtol=1e-6)


def test_assume_range_partition_elides_exchange(ctx, rng):
    from dryad_tpu.plan.lower import lower

    v = rng.standard_normal(512).astype(np.float32)
    base = ctx.from_arrays({"k": v}).range_partition("k")
    q = base.assume_range_partition("k").order_by([("k", False)])
    graph = lower([q.node], ctx.config)
    kinds = [op.kind for s in graph.stages for op in s.ops]
    # one exchange for the range_partition itself; the order_by must not
    # add a second one (metadata says ranges already match)
    assert kinds.count("exchange_range") == 1


def test_multi_key_range_partition(ctx, rng):
    a = rng.integers(0, 4, 2000).astype(np.int32)
    b = rng.standard_normal(2000).astype(np.float32)
    out = (
        ctx.from_arrays({"a": a, "b": b})
        .range_partition(["a", "b"])
        .order_by([("a", False), ("b", False)])
        .collect()
    )
    pairs = sorted(zip(a.tolist(), b.tolist()))
    got = list(zip(out["a"].tolist(), out["b"].tolist()))
    assert got == pairs


def test_range_partition_unknown_column(ctx):
    q = ctx.from_arrays({"k": np.zeros(8, np.int32)})
    with pytest.raises(ValueError):
        q.range_partition("nope")


def test_string_order_beyond_four_byte_prefix(ctx):
    """8-byte memcomparable prefix: strings sharing a 4-byte prefix now
    sort correctly (previously hash-ordered beyond 4 bytes)."""
    words = np.array(
        ["prefix_a", "prefix_c", "prefix_b", "prefix_d", "pref",
         "prefix_aa"] * 20,
        object,
    )
    out = ctx.from_arrays({"w": words}).order_by([("w", False)]).collect()
    assert out["w"].tolist() == sorted(words.tolist())


def test_splitter_sample_count_scales_with_boost(mesh8):
    """An overflow retry refines the splitter election: the compiled
    retry stage samples boost-times more keys, not just boost-times the
    capacity (DrDynamicRangeDistributor.cpp:54-110 analog)."""
    import jax
    import jax.numpy as jnp

    from dryad_tpu.exec.kernels import StageContext, _k_exchange_range
    from dryad_tpu.columnar.batch import ColumnBatch
    from dryad_tpu.ops import sort as SORT

    seen = []
    orig = SORT.sample_splitters

    def spy(op, valid, P, m, axes):
        seen.append(m)
        return orig(op, valid, P, m, axes)

    cap = 8 << 20  # per-partition 2^20: rate*cap = 1048 > 512 clamp
    from unittest import mock

    from jax import shard_map
    from jax.sharding import PartitionSpec as P_

    mesh = mesh8
    with mock.patch.object(SORT, "sample_splitters", spy):
        for boost in (1, 2):
            ctx = StageContext(8, 1.2, boost)

            def run(k):
                b = ColumnBatch({"k": k}, jnp.ones((cap // 8,), jnp.bool_))
                ctx.slots[0] = b
                ctx.entry_caps[0] = b.capacity
                _k_exchange_range(
                    ctx, dict(slot=0, operands_fn=lambda bb: [bb.data["k"]],
                              rate=0.001),
                )
                return ctx.slots[0].data["k"]

            k = jnp.zeros((cap,), jnp.uint32)
            jax.eval_shape(
                lambda kk: shard_map(
                    run, mesh=mesh, in_specs=P_("p"), out_specs=P_("p"),
                    check_vma=False,
                )(kk),
                k,
            )
    assert seen[0] == 512 and seen[1] == 1024
