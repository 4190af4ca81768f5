"""Pallas TPU kernel: dense-key bucket reduction on the MXU.

The fast path for GroupBy over *dense integer* keys (key in [0, K) with
K known at trace time — categorical codes, dictionary ranks): instead of
the general sort + segmented-reduce + shuffle pipeline
(``ops/segmented.py``, the TPU analog of the reference's GroupBy
machinery), the bucket histogram is computed as a **factorized one-hot
matmul**.  Split each key into ``hi = k // 128`` and ``lo = k % 128``;
then for every value column

    acc[hi, lo] += v   ==   acc += one_hot(hi)^T @ (one_hot(lo) * v)

which is a real (rows x A) @ (rows x 128) MXU contraction.  The VPU
builds only ``A + 128`` one-hot lanes per row (vs K for a direct
one-hot), the one-hot factors live in VMEM for the lifetime of a row
block, and the (A, 128) accumulator IS the bucket table — reshaped to
(K,) at the end.  Cross-partition combination is then a single
``psum_scatter`` — the aggregation *tree* of the reference
(``DrDynamicAggregateManager.h:35-168``) becomes one XLA collective and
the shuffle disappears entirely.

Block shapes obey the Mosaic tiling rule (last two dims divisible by
(8, 128) or equal to the array): rows are fed as (1, R) lane vectors
with R a multiple of 128 (rows ride the lane dim, so the one-hot
factors are generated directly in contraction orientation), and
accumulators are (A, 128) with A a multiple of 8.  The round-2 kernel
used (1, block) row blocks against a (nb, block) array, which fails
the sublane rule and would not lower on a real chip.

The kernel runs under Pallas on TPU (or in interpret mode, used on CPU
in tests); on other platforms ``bucket_sum_count`` runs a pure-XLA scan
over row chunks of the identical factorized math — which also keeps the
scan's HBM traffic at ~(A+256)·4 bytes/row instead of the 4·K
bytes/row a materialized one-hot pays.  The platform decides, never an
exception: a backend that fails to initialize is an error here, not a
reason to take the scan.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

DEFAULT_BLOCK = 1024
_LO = 128  # lane factor: lo = key % _LO indexes the accumulator lanes
_LO_SHIFT = 7  # hi = key >> _LO_SHIFT
assert 1 << _LO_SHIFT == _LO
# VMEM working-set budget per grid step (bytes), by ``device_kind``.
# The step's live set is the transposed one-hot factors — (128, R) lo
# plane, one (128, R) rhs plane per value column, an (A, R) hi plane —
# plus the resident (A, 128) accumulators.  The v5e figure sits under
# half of Mosaic's default 16 MiB scoped-VMEM limit there, leaving room
# for double buffering and dot scratch.  A TPU that is not in the table
# is an error, not a default: its limit has to be looked up, not
# assumed to be a v5e's.
_VMEM_BUDGET_BY_KIND = {
    "TPU v5 lite": 6 * 1024 * 1024,  # what jax calls a v5e
}
# Off-TPU the kernel only runs interpreted (no VMEM); the figure merely
# shapes the grid, so tests trace the same blocks the v5e compiles.
_VMEM_BUDGET_INTERPRET = _VMEM_BUDGET_BY_KIND["TPU v5 lite"]


def _vmem_budget() -> int:
    if not _on_tpu():
        return _VMEM_BUDGET_INTERPRET
    kind = jax.devices()[0].device_kind
    if kind not in _VMEM_BUDGET_BY_KIND:
        raise ValueError(
            f"pallas_bucket: no VMEM budget recorded for device_kind "
            f"{kind!r} (known: {sorted(_VMEM_BUDGET_BY_KIND)}); add its "
            "figure to _VMEM_BUDGET_BY_KIND"
        )
    return _VMEM_BUDGET_BY_KIND[kind]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _hi_width(num_buckets: int) -> int:
    """Sublane extent A of the accumulator: ceil(K/128), padded to 8."""
    return _round_up(max(1, -(-num_buckets // _LO)), 8)


def _stack_stride(a_pad: int) -> int:
    """Sublane stride of one plane in the stacked hi factor: bf16 tiles
    are (16, 128), so planes start on 16-sublane boundaries (extra
    iota rows past ``a_pad`` compare unequal to every hi index and
    contribute zero)."""
    return _round_up(a_pad, 16)


def _stacking_enabled(a_pad: int) -> bool:
    """Stacked-plane formulation applies below the 128-sublane pass
    boundary (per-term dots above it).  Shared by the kernel AND the
    VMEM sizing so an unstacked kernel never runs against a stacked
    budget."""
    return a_pad <= 128


def _row_block(a_pad: int, n_vals: int, total_planes: int) -> Optional[int]:
    """Rows per grid step, multiple of 128 (rows ride the lane dim),
    sized to the VMEM budget.  ``total_planes`` = 1 (counts) + sum of
    split-bf16 terms over the value columns.  Per-row live set: the
    inputs, the (128, R) lo one-hot, and — stacked formulation,
    a_pad <= 128 — the (planes * stride, R) hi stack; the f32
    accumulators and the dot output are resident off the top.  None
    when the fixed arrays alone blow the budget (huge num_buckets) —
    callers must use the XLA scan, which has no VMEM ceiling."""
    if _stacking_enabled(a_pad):
        hi_rows = total_planes * _stack_stride(a_pad) + _stack_stride(a_pad)
        out_rows = total_planes * _stack_stride(a_pad)
    else:
        # unstacked formulation: hi one-hot + per-term lo-side planes
        hi_rows = a_pad + 2 * _LO
        out_rows = a_pad
    acc_bytes = a_pad * _LO * 4 * (1 + n_vals) + out_rows * _LO * 4
    left = _vmem_budget() - acc_bytes
    if left <= 0:
        return None
    # one-hots budgeted at 4B/element (bf16 payload, 2x slack for
    # Mosaic relayout scratch), inputs at their real widths.
    r = left // (4 * (hi_rows + _LO) + 5 + 4 * n_vals + 16)
    if r < 128:
        return None
    return min(8192, (r // 128) * 128)


def _split_terms(v, n: int):
    """Decompose f32 ``v`` into ``n`` bf16 terms summing to ~v; term j
    carries mantissa bits [8j, 8j+8)."""
    import jax.numpy as jnp

    terms = []
    rem = v
    for _ in range(n - 1):
        t = rem.astype(jnp.bfloat16)
        terms.append(t)
        rem = rem - t.astype(jnp.float32)
    terms.append(rem.astype(jnp.bfloat16))
    return terms


def _val_splits(values) -> Tuple[int, ...]:
    """bf16 terms per value column: 3 for integers (exact to 2^24,
    the documented dense-path contract), 2 for floats (~2^-16)."""
    import jax.numpy as jnp

    return tuple(
        3 if jnp.issubdtype(jnp.asarray(v).dtype, jnp.integer) else 2
        for v in values
    )


def _make_kernel(n_vals: int, a_pad: int, splits: Tuple[int, ...] = ()):
    """Kernel over refs (k, mask, v_0..v_{n-1}, cnt, sum_0..sum_{n-1}).

    Row refs are (1, R) lane vectors; accumulators are (A, 128) tables
    addressed as [hi, lo].  Both one-hot factors are generated directly
    in contraction orientation — (A, R) and (128, R), rows on lanes —
    so the dots are plain NT matmuls with no data-dependent transposes
    (a dim-0 contraction here costs a Mosaic relayout of the whole
    one-hot; measured 2x slower end-to-end).

    EVERY dot runs single-pass bf16xbf16->f32 — the MXU's native rate.
    Counts are exact there (0/1 products).  Value sums use SPLIT-bf16
    accumulation: v decomposes into ``splits[i]`` bf16 terms (each
    carrying the next 8 mantissa bits), every term's one-hot products
    are exactly representable, and the f32 accumulator adds them — so
    2 terms give ~2^-16 relative representation error (float columns)
    and 3 terms keep integers exact to 2^24 (the documented dense-path
    contract).

    STACKED PLANES (a_pad <= 128): an MXU pass costs the same for any
    output sublane extent <= 128 (the contraction length R, not the
    output tile, is the clock), so
    the count plane and every value-term plane (``oh_hi * t`` — the
    term multiplied into the SMALL A-row factor, not the 128-row lo
    factor, cutting the VPU multiply 128/A-fold) stack into ONE hi
    factor of (planes * stride, R) and ONE dot per row block.  At
    K=4096 (A=32) count + one float column = 3 planes = 96 sublanes =
    ONE native pass, vs 3 separate dots before (and vs 1 + ~6 f32-rate
    passes in round 3).  Planes sit on 16-sublane strides (bf16 tile
    alignment); the padded iota rows never match a hi index, so they
    only add zeros.  For a_pad > 128 every plane is already >= 1 full
    pass and stacking buys nothing: the per-term dots remain, with the
    term multiplied into whichever factor is smaller (the lo plane)."""

    stride = _stack_stride(a_pad)
    stacked = _stacking_enabled(a_pad)

    def kernel(*refs):
        k_ref, m_ref = refs[0], refs[1]
        v_refs = refs[2 : 2 + n_vals]
        cnt_ref = refs[2 + n_vals]
        sum_refs = refs[3 + n_vals :]

        i = pl.program_id(0)
        kb = k_ref[...]  # (1, R) int32
        mb = m_ref[...]  # (1, R) bool
        R = kb.shape[1]

        lo_iota = jax.lax.broadcasted_iota(jnp.int32, (_LO, R), 0)
        # mask folded into the lo factor zeroes invalid rows out of both
        # the counts and every sum in one place.
        oh_lo = (((kb & (_LO - 1)) == lo_iota) & mb).astype(jnp.bfloat16)

        @pl.when(i == 0)
        def _init():
            cnt_ref[...] = jnp.zeros((a_pad, _LO), jnp.float32)
            for s in sum_refs:
                s[...] = jnp.zeros((a_pad, _LO), jnp.float32)

        contract_lanes = (((1,), (1,)), ((), ()))
        if stacked:
            hi_iota = jax.lax.broadcasted_iota(jnp.int32, (stride, R), 0)
            oh_hi = ((kb >> _LO_SHIFT) == hi_iota).astype(jnp.bfloat16)
            planes = [oh_hi]
            for j, v_ref in enumerate(v_refs):
                v = v_ref[...].astype(jnp.float32)  # (1, R)
                for t in _split_terms(v, splits[j] if splits else 2):
                    planes.append(oh_hi * t)
            stack = (
                planes[0] if len(planes) == 1
                else jnp.concatenate(planes, axis=0)
            )
            out = jax.lax.dot_general(
                stack, oh_lo, contract_lanes,
                preferred_element_type=jnp.float32,
            )  # (planes * stride, 128) f32
            cnt_ref[...] += out[:a_pad]
            off = stride
            for j, s_ref in enumerate(sum_refs):
                acc = None
                for _ in range(splits[j] if splits else 2):
                    d = out[off : off + a_pad]
                    acc = d if acc is None else acc + d
                    off += stride
                s_ref[...] += acc
        else:
            hi_iota = jax.lax.broadcasted_iota(jnp.int32, (a_pad, R), 0)
            oh_hi = ((kb >> _LO_SHIFT) == hi_iota).astype(jnp.bfloat16)
            cnt_ref[...] += jax.lax.dot_general(
                oh_hi, oh_lo, contract_lanes,
                preferred_element_type=jnp.float32,
            )
            for j, (v_ref, s_ref) in enumerate(zip(v_refs, sum_refs)):
                v = v_ref[...].astype(jnp.float32)  # (1, R)
                acc = None
                for t in _split_terms(v, splits[j] if splits else 2):
                    d = jax.lax.dot_general(
                        oh_hi, oh_lo * t, contract_lanes,
                        preferred_element_type=jnp.float32,
                    )
                    acc = d if acc is None else acc + d
                s_ref[...] += acc

    return kernel


def _on_tpu() -> bool:
    """True when jax's default backend is a TPU.  No exception is
    swallowed: a backend that cannot initialize raises here."""
    return jax.default_backend() == "tpu"


def _default_strategy() -> str:
    """Bucket-reduce strategy, from the platform alone: the one-hot MXU
    matmul on TPU (scatters serialize there), plain scatter-add
    (``segment_sum`` on unsorted keys — no sort) elsewhere, where it
    beats the sort path."""
    return "matmul" if _on_tpu() else "scatter"


def _scatter_bucket(
    keys: jax.Array,
    values: Sequence[jax.Array],
    valid: jax.Array,
    k_full: int,
) -> Tuple[List[jax.Array], jax.Array]:
    """Scatter-add bucket reduce: exact f32 adds, HBM-bound (roofline
    ~2.3e10 rows/s IF the backend vectorizes scatters)."""
    seg = jnp.where(valid, keys, k_full)  # invalid -> dropped sentinel
    cnt = jax.ops.segment_sum(
        valid.astype(jnp.float32), seg, k_full + 1
    )[:k_full]
    sums = [
        jax.ops.segment_sum(
            jnp.where(valid, v.astype(jnp.float32), 0.0), seg, k_full + 1
        )[:k_full]
        for v in values
    ]
    return sums, cnt


def bucket_sum_count(
    keys: jax.Array,
    values: Sequence[jax.Array],
    valid: jax.Array,
    num_buckets: int,
    block: int = DEFAULT_BLOCK,
    interpret: Optional[bool] = None,
    strategy: Optional[str] = None,
) -> Tuple[List[jax.Array], jax.Array]:
    """Per-bucket sums of each value column + row counts.

    ``keys``: int32, in [0, num_buckets) for valid rows (values are
    clamped defensively; callers guarantee range).  Returns
    ``([sum per value col], counts)``, each of shape (num_buckets,) f32.
    ``interpret``: force Pallas interpret mode (CPU testing); default
    picks the Pallas kernel on TPU and the XLA fallback elsewhere.
    ``block`` caps the rows-per-step of the XLA fallback's scan.
    ``strategy``: "matmul" (factorized one-hot, MXU) or "scatter"
    (plain segment_sum) — default from the platform
    (:func:`_default_strategy`).
    """
    n = keys.shape[0]
    a_pad = _hi_width(num_buckets)
    k_full = a_pad * _LO  # accumulator capacity >= num_buckets
    keys = jnp.clip(
        jnp.where(valid, keys, 0).astype(jnp.int32), 0, k_full - 1
    )
    if (strategy or _default_strategy()) == "scatter" and interpret is not True:
        flat_s, flat_c = _scatter_bucket(keys, values, valid, k_full)
        return [s[:num_buckets] for s in flat_s], flat_c[:num_buckets]

    def pad_to(npad):
        nonlocal keys, valid, values
        if npad != n:
            pad = npad - n
            keys = jnp.concatenate([keys, jnp.zeros((pad,), keys.dtype)])
            valid = jnp.concatenate([valid, jnp.zeros((pad,), jnp.bool_)])
            values = [
                jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
                for v in values
            ]

    splits = _val_splits(values)
    R = _row_block(a_pad, len(values), 1 + sum(splits))
    if interpret is True and R is None:
        # An explicit interpret=True means the caller wants the Pallas
        # kernel exercised; silently taking the XLA scan would stop
        # tests from covering it with no signal.
        raise ValueError(
            "bucket_sum_count: interpret=True requested but the Pallas "
            f"path is refused (VMEM budget: a_pad={a_pad}, "
            f"n_vals={len(values)})"
        )
    use_pallas = R is not None and (
        interpret is True or (interpret is None and _on_tpu())
    )
    if use_pallas:
        npad = _round_up(max(n, R), R)
        pad_to(npad)
        row = lambda x: x.reshape(1, npad)
        row_spec = pl.BlockSpec((1, R), lambda i: (0, i))
        out_spec = pl.BlockSpec((a_pad, _LO), lambda i: (0, 0))
        outs = pl.pallas_call(
            _make_kernel(len(values), a_pad, splits),
            grid=(npad // R,),
            in_specs=[row_spec] * (2 + len(values)),
            out_specs=[out_spec] * (1 + len(values)),
            out_shape=[jax.ShapeDtypeStruct((a_pad, _LO), jnp.float32)]
            * (1 + len(values)),
            interpret=bool(interpret),
        )(row(keys), row(valid), *[row(v) for v in values])
        cnt, sums = outs[0], list(outs[1:])
    else:
        # Pure-XLA scan over row chunks of the same
        # factorized math (identical semantics).  The chunk shrinks
        # with the hi-factor width so the per-step (chunk, a_pad)
        # one-hot stays ~<=64MB — a huge num_buckets (the path Pallas
        # refuses on VMEM grounds) would otherwise materialize
        # multi-GB intermediates per scan step.
        cap = max(8, ((64 << 20) // (4 * a_pad)) // 8 * 8)
        chunk = max(8, min(32768, _round_up(block, 8), cap))
        npad = _round_up(max(n, chunk), chunk)
        pad_to(npad)
        nb = npad // chunk
        k2 = keys.reshape(nb, chunk)
        m2 = valid.reshape(nb, chunk)
        v2 = [v.reshape(nb, chunk) for v in values]
        lo_iota = jnp.arange(_LO, dtype=jnp.int32)[None, :]
        hi_iota = jnp.arange(a_pad, dtype=jnp.int32)[None, :]

        def body(acc, xs):
            kb, mb, *vbs = xs
            # identical split-bf16 math to the Pallas kernel (products
            # exactly representable; f32 accumulate)
            oh_lo = (
                ((kb[:, None] & (_LO - 1)) == lo_iota) & mb[:, None]
            ).astype(jnp.bfloat16)
            oh_hi = (
                (kb[:, None] >> _LO_SHIFT) == hi_iota
            ).astype(jnp.bfloat16)
            cnt_a, sums_a = acc
            cnt_a = cnt_a + jax.lax.dot_general(
                oh_hi, oh_lo, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            new_sums = []
            for j, (s, vb) in enumerate(zip(sums_a, vbs)):
                v = vb[:, None].astype(jnp.float32)
                for t in _split_terms(v, splits[j] if splits else 2):
                    s = s + jax.lax.dot_general(
                        oh_hi, oh_lo * t, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                new_sums.append(s)
            return (cnt_a, new_sums), None

        init = (
            jnp.zeros((a_pad, _LO), jnp.float32),
            [jnp.zeros((a_pad, _LO), jnp.float32) for _ in values],
        )
        (cnt, sums), _ = jax.lax.scan(body, init, (k2, m2, *v2))

    flat = lambda t: t.reshape(k_full)[:num_buckets]
    return [flat(s) for s in sums], flat(cnt)
