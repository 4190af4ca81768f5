"""Order-preserving key transforms for on-device sorting.

Every sortable device column maps to a uint32 whose unsigned order equals
the column's logical order (int32 bias flip; IEEE-754 total-order trick
for float32).  Descending keys are bitwise-complemented.  This gives
OrderBy/ThenBy chains (reference ``DryadLinqQueryable.cs`` OrderBy /
ThenByDescending operators) one uniform lexicographic sort on uint32
operands via ``lax.sort(num_keys=...)``.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp


def to_sortable_u32(col: jax.Array, descending: bool = False) -> jax.Array:
    if col.dtype == jnp.uint32:
        k = col
    elif col.dtype == jnp.int32:
        k = col.astype(jnp.uint32) ^ jnp.uint32(0x80000000)
    elif col.dtype == jnp.bool_:
        k = col.astype(jnp.uint32)
    elif col.dtype == jnp.float32:
        bits = jax.lax.bitcast_convert_type(col, jnp.uint32)
        sign = bits >> 31
        # Negative floats: flip all bits; non-negative: set the sign bit.
        k = jnp.where(sign == 1, ~bits, bits | jnp.uint32(0x80000000))
    else:
        raise TypeError(f"unsortable device column dtype {col.dtype}")
    return ~k if descending else k


def sort_order(
    key_cols: Sequence[jax.Array],
    valid: jax.Array,
    descending: Sequence[bool] | None = None,
) -> jax.Array:
    """Stable row permutation: valid rows first, ordered by the keys.

    Invalid rows sort last (their key is forced to the max), so a batch
    gathered by this order is simultaneously compacted and sorted.

    NOTE: when the goal is sorted DATA, prefer
    ``ops.sort.sort_batch_by_operands`` / ``sort_carry`` — on the TPU
    applying a permutation with ``take()`` is an XLA ``gather`` per
    column, while carrying columns through ``lax.sort`` is nearly free
    (``PERF.md`` section 6, PR 25).  Use the permutation form only when
    the order must be applied to something that cannot ride the sort.
    """
    n = valid.shape[0]
    desc = list(descending) if descending is not None else [False] * len(key_cols)
    if len(desc) != len(key_cols):
        raise ValueError(
            f"descending has {len(desc)} entries for {len(key_cols)} key columns"
        )
    operands: List[jax.Array] = [jnp.logical_not(valid).astype(jnp.uint32)]
    for col, d in zip(key_cols, desc):
        operands.append(to_sortable_u32(col, d))
    operands.append(jnp.arange(n, dtype=jnp.int32))  # payload: row index
    sorted_ops = jax.lax.sort(
        tuple(operands), num_keys=len(operands) - 1, is_stable=True
    )
    return sorted_ops[-1]


def lexi_less(
    a_cols: Sequence[jax.Array], b_cols: Sequence[jax.Array]
) -> jax.Array:
    """Elementwise lexicographic a < b over parallel key columns."""
    lt = jnp.zeros(a_cols[0].shape, jnp.bool_)
    eq = jnp.ones(a_cols[0].shape, jnp.bool_)
    for a, b in zip(a_cols, b_cols):
        ka, kb = to_sortable_u32(a), to_sortable_u32(b)
        lt = lt | (eq & (ka < kb))
        eq = eq & (ka == kb)
    return lt


def keys_equal_adjacent(key_cols: Sequence[jax.Array]) -> jax.Array:
    """For sorted columns: row i equals row i-1 on all keys (row 0 -> False)."""
    n = key_cols[0].shape[0]
    eq = jnp.arange(n, dtype=jnp.int32) > 0
    for col in key_cols:
        prev = jnp.roll(col, 1)
        eq = eq & (col == prev)
    return eq
