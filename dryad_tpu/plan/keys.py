"""Logical key -> physical device-column lowering.

Grouping/joining and ordering need different physical views of a
logical column: equality keys are the identity columns (hash words for
strings), while ordering keys are uint32 operand lists whose
lexicographic order equals the logical order (reference analog: the
comparer/key-selector machinery of OrderBy/GroupBy nodes,
``DryadLinqQueryNode.cs``).
"""

from __future__ import annotations

from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from dryad_tpu.columnar.batch import ColumnBatch
from dryad_tpu.columnar.schema import ColumnType, Schema
from dryad_tpu.ops.sortkeys import to_sortable_u32


def equality_cols(schema: Schema, names: Sequence[str]) -> List[str]:
    """Physical columns whose tuple-equality == logical key equality."""
    out: List[str] = []
    for n in names:
        out.extend(schema.field(n).identity_names)
    return out


def group_carry_cols(schema: Schema, names: Sequence[str]) -> List[str]:
    """Physical columns to carry as group keys (includes string ranks so
    ordering info survives a group-by)."""
    out: List[str] = []
    for n in names:
        out.extend(schema.field(n).device_names)
    return out


class OrderingOperands:
    """Callable: batch -> uint32 operand list, lexicographic order ==
    logical (column, descending) chain order.

    INT64: (sign-flipped high word, low word).  STRING: (8-byte prefix
    rank words, hash words) — exact for 8-byte prefixes, hash-order
    beyond (documented engine semantic for string ordering).  BYTES:
    its big-endian words in byte order, one operand a word — exact
    ``memcmp`` order over every byte (the last word's zero padding is
    the same in every row, so it never decides).

    VALUE-equal (not identity-equal): re-lowering the same logical plan
    builds a new instance, and the compiled-stage cache keys ops by
    their params — an identity-keyed callable here would recompile the
    sort pipeline on every collect().
    """

    def __init__(self, schema: Schema, keys: Sequence[Tuple[str, bool]]):
        self.fields = tuple((schema.field(n), bool(d)) for n, d in keys)

    def __eq__(self, other) -> bool:
        return (
            type(other) is OrderingOperands and other.fields == self.fields
        )

    def __hash__(self) -> int:
        return hash(self.fields)

    def __call__(self, batch: ColumnBatch) -> List[jax.Array]:
        ops: List[jax.Array] = []
        for f, desc in self.fields:
            if f.ctype.is_bytes:
                words = [batch.data[n] for n in f.device_names]
                ops.extend(~w if desc else w for w in words)
            elif f.ctype == ColumnType.STRING:
                r0 = batch.data[f"{f.name}#r0"]
                r1 = batch.data[f"{f.name}#r1"]
                h0 = batch.data[f"{f.name}#h0"]
                h1 = batch.data[f"{f.name}#h1"]
                triple = [r0, r1, h1, h0]
                ops.extend(~t if desc else t for t in triple)
            elif f.ctype.storage in (ColumnType.INT64, ColumnType.FLOAT64):
                # FLOAT64 words are the order-preserving signed-int64
                # image of the double, so the int64 operand transform
                # orders both types correctly
                hi = batch.data[f"{f.name}#h1"] ^ jnp.uint32(0x80000000)
                lo = batch.data[f"{f.name}#h0"]
                ops.extend([~hi, ~lo] if desc else [hi, lo])
            else:
                ops.append(to_sortable_u32(batch.data[f.name], desc))
        return ops


def ordering_operands(
    schema: Schema, keys: Sequence[Tuple[str, bool]]
) -> Callable[[ColumnBatch], List[jax.Array]]:
    return OrderingOperands(schema, keys)
