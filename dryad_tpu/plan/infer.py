"""Schema inference for user projection functions.

``select``/``select_many`` take a function over *physical* columns; when
the caller doesn't declare the output schema we trace it with
``jax.eval_shape`` on dummy columns and reconstruct logical fields from
the physical names: ``x#h0``/``x#h1``/``x#r0``/``x#r1`` quads are STRING,
``x#h0``/``x#h1`` pairs are INT64, ``x#b0`` ... ``x#b<k-1>`` runs are
BYTES, everything else maps by dtype.  A DECIMAL value a typed function
returns (``ops/wide.py::Dec``) says its own scale and width; a DATE
survives under its name (it is an int32 on the device).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from dryad_tpu.columnar.schema import BYTES, ColumnType, DecimalType, Schema
from dryad_tpu.ops import wide

_DEVICE_DTYPES = {
    ColumnType.INT32: jnp.int32,
    ColumnType.FLOAT32: jnp.float32,
    ColumnType.BOOL: jnp.bool_,
    ColumnType.UINT32: jnp.uint32,
}


def dummy_cols(schema: Schema, n: int = 4) -> Dict[str, jax.ShapeDtypeStruct]:
    out: Dict[str, jax.ShapeDtypeStruct] = {}
    for f in schema.fields:
        if f.ctype.is_split:
            for d in f.device_names:
                out[d] = jax.ShapeDtypeStruct((n,), jnp.uint32)
        else:
            out[f.name] = jax.ShapeDtypeStruct(
                (n,), _DEVICE_DTYPES[f.ctype.storage]
            )
    return out


_DTYPE_TO_TYPE = {
    jnp.dtype(jnp.int32): ColumnType.INT32,
    jnp.dtype(jnp.float32): ColumnType.FLOAT32,
    jnp.dtype(jnp.bool_): ColumnType.BOOL,
    jnp.dtype(jnp.uint32): ColumnType.UINT32,
}


def schema_from_physical(
    cols: Dict[str, jax.ShapeDtypeStruct],
    like: Schema = None,
) -> Schema:
    """Reconstruct a logical schema from physical columns.

    A bare ``#h0/#h1`` word pair is ambiguous (INT64 and FLOAT64 share
    the layout), so a surviving logical name inherits its type from
    ``like`` (the input schema) when given; word pairs NEW to the output
    default to INT64.
    """
    names = set(cols.keys())
    fields: List[Tuple[str, ColumnType]] = []
    seen = set()
    for name in cols:
        if "#" in name:
            base = name.split("#")[0]
            if base in seen:
                continue
            seen.add(base)
            mine = {n for n in names if n.startswith(f"{base}#")}
            if any(n.startswith(f"{base}#b") for n in mine):
                # BYTES: the words say how many, not how wide the last
                # one is; a surviving column keeps its input width
                if mine != {f"{base}#b{i}" for i in range(len(mine))}:
                    raise ValueError(
                        f"incomplete split column set for {base!r}: "
                        f"{sorted(mine)}"
                    )
                ctype = BYTES(4 * len(mine))
                if like is not None and base in like:
                    kept = like.field(base).ctype
                    if kept.is_bytes and kept.words == len(mine):
                        ctype = kept
                fields.append((base, ctype))
                continue
            has = {f"{base}#{s}" for s in ("h0", "h1", "r0", "r1")} & names
            if has == {f"{base}#h0", f"{base}#h1", f"{base}#r0", f"{base}#r1"}:
                fields.append((base, ColumnType.STRING))
            elif has == {f"{base}#h0", f"{base}#h1"}:
                if (
                    like is not None
                    and base in like
                    and like.field(base).ctype.is_split
                ):
                    fields.append((base, like.field(base).ctype))
                else:
                    fields.append((base, ColumnType.INT64))
            else:
                raise ValueError(
                    f"incomplete split column set for {base!r}: {sorted(has)}"
                )
        else:
            dt = jnp.dtype(cols[name].dtype)
            if dt not in _DTYPE_TO_TYPE:
                raise TypeError(f"column {name!r} has unsupported dtype {dt}")
            ctype = _DTYPE_TO_TYPE[dt]
            if (
                like is not None and name in like
                and like.field(name).ctype is ColumnType.DATE
                and ctype is ColumnType.INT32
            ):
                ctype = ColumnType.DATE  # days stay days under their name
            fields.append((name, ctype))
    return Schema(fields)


def infer_select_schema(schema: Schema, fn) -> Schema:
    """The schema of ``fn``'s output.  A typed ``fn`` (``api/query.py``
    wraps one where the input has DECIMAL columns) is traced over its
    LOGICAL columns (``fn.logical``), so that each :class:`wide.Dec` it
    returns names its own DECIMAL type."""
    shapes = dummy_cols(schema)
    out = jax.eval_shape(lambda c: getattr(fn, "logical", fn)(c), shapes)
    if not isinstance(out, dict):
        raise TypeError("select fn must return a dict of physical columns")
    decimals = {
        name: DecimalType(v.scale, v.wide)
        for name, v in out.items() if isinstance(v, wide.Dec)
    }
    inferred = schema_from_physical(wide.unwrap(out), like=schema)
    return Schema([
        (f.name, decimals.get(f.name, f.ctype)) for f in inferred.fields
    ])


def infer_select_many_schema(schema: Schema, fn, factor: int) -> Schema:
    shapes = dummy_cols(schema)
    out_cols, _valid = jax.eval_shape(lambda c: fn(c), shapes)
    flat = {
        n: jax.ShapeDtypeStruct((s.shape[0] * factor,), s.dtype)
        for n, s in out_cols.items()
    }
    return schema_from_physical(flat, like=schema)
