"""Unit tests for the operand-carrying sort primitives (ops/sort.py
``sort_carry`` / ``sort_batch_by_operands``) — the round-4 rewrite of
every ``take(sort_order(...))`` site.  The contract under test: the
carried result is IDENTICAL to applying the stable permutation from
``sort_order_by_operands`` (reference sort semantics:
``LinqToDryad/DryadLinqVertex.cs`` MergeSort operators)."""

import numpy as np
import pytest

from dryad_tpu.parallel.mesh import force_cpu_backend

force_cpu_backend(8)

import jax.numpy as jnp  # noqa: E402

from dryad_tpu.columnar.batch import ColumnBatch  # noqa: E402
from dryad_tpu.ops.sort import (  # noqa: E402
    sort_batch_by_operands,
    sort_carry,
    sort_order_by_operands,
)
from dryad_tpu.ops.sortkeys import to_sortable_u32  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(77)


def test_sort_carry_matches_permutation(rng):
    n = 4096
    keys = jnp.asarray(rng.integers(0, 50, n).astype(np.int32))
    valid = jnp.asarray(rng.random(n) < 0.8)
    payload_f = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    payload_i = jnp.asarray(rng.integers(-99, 99, n).astype(np.int32))
    ops = [to_sortable_u32(keys)]

    order = sort_order_by_operands(ops, valid)
    v, (sk,), (pf, pi) = sort_carry(ops, valid, [payload_f, payload_i])

    np.testing.assert_array_equal(np.asarray(v), np.asarray(valid)[order])
    np.testing.assert_array_equal(
        np.asarray(sk), np.asarray(ops[0])[order]
    )
    np.testing.assert_array_equal(np.asarray(pf), np.asarray(payload_f)[order])
    np.testing.assert_array_equal(np.asarray(pi), np.asarray(payload_i)[order])


def test_sort_carry_stability_ties(rng):
    # Equal keys keep original relative order (is_stable contract the
    # ranked group-join relies on).
    n = 1024
    keys = jnp.zeros((n,), jnp.uint32)  # all ties
    valid = jnp.ones((n,), jnp.bool_)
    idx = jnp.arange(n, dtype=jnp.int32)
    _, _, (si,) = sort_carry([keys], valid, [idx])
    np.testing.assert_array_equal(np.asarray(si), np.arange(n))


def test_sort_carry_invalid_rows_last(rng):
    n = 512
    keys = jnp.asarray(rng.integers(0, 10, n).astype(np.int32))
    valid = jnp.asarray(rng.random(n) < 0.5)
    v, _, _ = sort_carry([to_sortable_u32(keys)], valid)
    nv = int(np.sum(np.asarray(valid)))
    got = np.asarray(v)
    assert got[:nv].all() and not got[nv:].any()


@pytest.mark.parametrize(
    "carry,wide",
    [(None, False), (True, False), (True, True), (False, True)],
    ids=["platform", "carry", "carry+2d", "gather+2d"],
)
def test_sort_batch_by_operands_matches_take(rng, monkeypatch, carry, wide):
    """Either data movement (forced, or the platform's own), with and
    without a column that cannot ride the sort (trailing dimension)."""
    from dryad_tpu.ops import sort as SORT

    if carry is not None:
        monkeypatch.setattr(SORT, "_carry_profitable", lambda: carry)
    n = 2048
    data = {
        "k": jnp.asarray(rng.integers(-1000, 1000, n).astype(np.int32)),
        "v": jnp.asarray(rng.standard_normal(n).astype(np.float32)),
        "b": jnp.asarray(rng.random(n) < 0.5),
    }
    if wide:
        data["m"] = jnp.asarray(rng.integers(0, 99, (n, 2, 3)).astype(np.int32))
    valid = jnp.asarray(rng.random(n) < 0.9)
    b = ColumnBatch(data, valid)
    ops = [to_sortable_u32(b.data["k"]), to_sortable_u32(b.data["v"])]

    ref = b.take(sort_order_by_operands(ops, valid))
    got = sort_batch_by_operands(b, ops)

    np.testing.assert_array_equal(np.asarray(got.valid), np.asarray(ref.valid))
    for c in b.columns:
        np.testing.assert_array_equal(
            np.asarray(got.data[c]), np.asarray(ref.data[c]), err_msg=c
        )


def test_group_reduce_count_all_segment_shapes(rng):
    # count-by-adjacent-difference edge cases: single segment, all
    # singletons, empty input, trailing invalid rows.
    from dryad_tpu.ops.segmented import AggSpec, group_reduce

    def check(keys, validm):
        n = len(keys)
        b = ColumnBatch(
            {"k": jnp.asarray(np.asarray(keys, np.int32)),
             "v": jnp.asarray(np.ones(n, np.float32))},
            jnp.asarray(np.asarray(validm, bool)),
        )
        out = group_reduce(
            b, ["k"], [AggSpec("count", None, "c"), AggSpec("mean", "v", "m")]
        )
        ov = np.asarray(out.valid)
        ks = np.asarray(out.data["k"])[ov]
        cs = np.asarray(out.data["c"])[ov]
        ms = np.asarray(out.data["m"])[ov]
        ref = {}
        for k, va in zip(keys, validm):
            if va:
                ref[k] = ref.get(k, 0) + 1
        assert dict(zip(ks.tolist(), cs.tolist())) == ref
        assert np.allclose(ms, 1.0)

    check([5] * 64, [True] * 64)                      # one segment
    check(list(range(64)), [True] * 64)               # all singletons
    check([1, 1, 2, 3], [False, False, False, False])  # empty
    check([9, 9, 4, 4, 4, 7, 7, 7], [True, True, True, False,
                                     True, True, False, True])
