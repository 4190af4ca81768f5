"""ColumnBatch — the HBM-resident record container.

The TPU-native replacement for the reference's streamed row records
(``DryadLinqBinaryReader/Writer``, ``RChannelItem`` arrays): a fixed
*capacity* struct-of-arrays with a boolean validity mask.  Static shapes
keep every stage jit-compilable; deletion/filtering clears mask bits,
and compaction happens on-device when a shuffle or sort needs dense rows.

A ColumnBatch is a registered pytree, so it flows through ``jit``,
``shard_map`` and collectives directly.  Device columns are *physical*
columns: logical INT64/STRING columns are two uint32 word columns (see
``columnar.schema.device_column_names``).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dryad_tpu.columnar.schema import (
    ColumnType,
    Schema,
    StringDictionary,
    join64,
    split64,
)
from dryad_tpu.obs.span import UNTRACED, Tracer


def encode_physical(
    field, a: np.ndarray, dictionary: Optional[StringDictionary]
) -> Dict[str, np.ndarray]:
    """One logical host column -> its physical device/store columns
    (STRING: Hash64 words + memcomparable prefix ranks; INT64/FLOAT64:
    order-preserving split words).  Shared by device ingest and the
    streaming store writer, so ``.dpf`` parts written out-of-core read
    back through the same ``store`` binding path."""
    if field.ctype == ColumnType.STRING:
        if dictionary is None:
            raise ValueError(f"STRING column {field.name} needs a dictionary")
        from dryad_tpu.columnar.schema import string_prefix_rank

        strs = [str(s) for s in a]
        hashes = dictionary.add_all(strs)
        lo, hi = split64(hashes)
        sarr = np.array(strs, object)
        return {
            f"{field.name}#h0": lo,
            f"{field.name}#h1": hi,
            f"{field.name}#r0": string_prefix_rank(sarr),
            f"{field.name}#r1": string_prefix_rank(sarr, offset=4),
        }
    if field.ctype == ColumnType.INT64:
        lo, hi = split64(a.astype(np.int64))
        return {f"{field.name}#h0": lo, f"{field.name}#h1": hi}
    if field.ctype == ColumnType.FLOAT64:
        from dryad_tpu.columnar.schema import f64_to_ordered_i64

        lo, hi = split64(f64_to_ordered_i64(a))
        return {f"{field.name}#h0": lo, f"{field.name}#h1": hi}
    return {field.name: a.astype(field.ctype.numpy_dtype)}


def encode_table(
    schema: Schema,
    arrays: Dict[str, np.ndarray],
    dictionary: Optional[StringDictionary],
) -> Tuple[Dict[str, np.ndarray], int]:
    """Logical host table -> ``(physical host columns, row count)``.
    Host-only (NumPy in, NumPy out): the sharded ingest edge
    (``parallel.distribute.from_host_table``) places these columns
    itself, so nothing here may touch a device."""
    n = None
    for name in schema.names:
        a = np.asarray(arrays[name])
        if n is None:
            n = len(a)
        elif len(a) != n:
            raise ValueError("ragged input columns")
    phys: Dict[str, np.ndarray] = {}
    for f in schema.fields:
        phys.update(
            encode_physical(f, np.asarray(arrays[f.name]), dictionary)
        )
    return phys, n or 0


@jax.tree_util.register_pytree_node_class
class ColumnBatch:
    """Fixed-capacity columnar batch with a validity mask.

    ``data`` maps physical column name -> array of shape ``(capacity,)``
    (or ``(n_partitions * capacity,)`` for a global view of a sharded
    batch — the container is shape-agnostic beyond requiring all columns
    and the mask to share their leading dimension).
    """

    def __init__(self, data: Dict[str, jax.Array], valid: jax.Array):
        self.data = dict(data)
        self.valid = valid

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        names = sorted(self.data.keys())
        children = [self.data[n] for n in names] + [self.valid]
        return children, tuple(names)

    @classmethod
    def tree_unflatten(cls, names, children):
        data = dict(zip(names, children[:-1]))
        return cls(data, children[-1])

    # -- basic properties --------------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self.valid.shape[0])

    @property
    def columns(self) -> List[str]:
        return sorted(self.data.keys())

    def count(self) -> jax.Array:
        """Number of valid rows (traced value)."""
        return jnp.sum(self.valid.astype(jnp.int32))

    def __getitem__(self, name: str) -> jax.Array:
        return self.data[name]

    # -- jit-safe transforms ----------------------------------------------
    def with_column(self, name: str, values: jax.Array) -> "ColumnBatch":
        new = dict(self.data)
        new[name] = values
        return ColumnBatch(new, self.valid)

    def select(self, names: Sequence[str]) -> "ColumnBatch":
        return ColumnBatch({n: self.data[n] for n in names}, self.valid)

    def drop(self, names: Sequence[str]) -> "ColumnBatch":
        keep = {n: v for n, v in self.data.items() if n not in set(names)}
        return ColumnBatch(keep, self.valid)

    def rename(self, mapping: Dict[str, str]) -> "ColumnBatch":
        new = {mapping.get(n, n): v for n, v in self.data.items()}
        return ColumnBatch(new, self.valid)

    def filter(self, keep_mask: jax.Array) -> "ColumnBatch":
        """Row filter: AND a predicate into the validity mask (Where)."""
        return ColumnBatch(self.data, jnp.logical_and(self.valid, keep_mask))

    def compact(self) -> "ColumnBatch":
        """Move valid rows to the front (stable).

        Sort-based compaction: key = !valid, stable, so valid rows keep
        their order at the front.  Invalid slots retain stale values but
        their mask bits are off.  The columns move as every sorted batch
        does (``ops.sort.sort_carry``: riding the sort on TPU).
        """
        from dryad_tpu.ops.sort import sort_batch_by_operands

        return sort_batch_by_operands(self, [])

    def take(self, order: jax.Array) -> "ColumnBatch":
        """Row gather by index array (caller manages mask semantics)."""
        data = {n: v[order] for n, v in self.data.items()}
        return ColumnBatch(data, self.valid[order])

    def pad_to(self, capacity: int) -> "ColumnBatch":
        cur = self.capacity
        if capacity == cur:
            return self
        if capacity < cur:
            raise ValueError(f"pad_to({capacity}) below current capacity {cur}")
        extra = capacity - cur
        data = {
            n: jnp.concatenate([v, jnp.zeros((extra,) + v.shape[1:], v.dtype)])
            for n, v in self.data.items()
        }
        valid = jnp.concatenate([self.valid, jnp.zeros((extra,), jnp.bool_)])
        return ColumnBatch(data, valid)

    @staticmethod
    def concatenate(batches: Sequence["ColumnBatch"]) -> "ColumnBatch":
        """Static concat along rows (the Concat operator's device step)."""
        names = batches[0].columns
        for b in batches[1:]:
            if b.columns != names:
                raise ValueError("concat of batches with differing columns")
        data = {n: jnp.concatenate([b.data[n] for b in batches]) for n in names}
        valid = jnp.concatenate([b.valid for b in batches])
        return ColumnBatch(data, valid)

    @staticmethod
    def empty(col_dtypes: Dict[str, jnp.dtype], capacity: int) -> "ColumnBatch":
        data = {n: jnp.zeros((capacity,), dt) for n, dt in col_dtypes.items()}
        return ColumnBatch(data, jnp.zeros((capacity,), jnp.bool_))

    # -- host conversion ---------------------------------------------------
    @staticmethod
    def from_numpy(
        schema: Schema,
        arrays: Dict[str, np.ndarray],
        capacity: Optional[int] = None,
        dictionary: Optional[StringDictionary] = None,
    ) -> "ColumnBatch":
        """Encode host arrays (logical columns) into a device batch.

        STRING columns require ``dictionary`` and are hashed via the
        framework Hash64 (``columnar.schema.hash64_str``); INT64 columns
        are split into uint32 word pairs.  Rows are padded to
        ``capacity`` with mask bits off.
        """
        phys, n = encode_table(schema, arrays, dictionary)
        cap = capacity if capacity is not None else n
        if cap < n:
            raise ValueError(f"capacity {cap} < row count {n}")

        data: Dict[str, jnp.ndarray] = {}
        for pname, pvals in phys.items():
            padded = np.zeros((cap,), pvals.dtype)
            padded[:n] = pvals
            data[pname] = jnp.asarray(padded)
        valid = np.zeros((cap,), np.bool_)
        valid[:n] = True
        return ColumnBatch(data, jnp.asarray(valid))

    def fetch_host(
        self, extra: Sequence[jax.Array] = (), tracer: Tracer = UNTRACED
    ):
        """(valid, columns, extras) on the host, via ONE
        ``jax.device_get`` so PJRT overlaps all the device->host copies
        (copy_to_host_async then a single block).  A per-column
        ``np.asarray`` loop pays one synchronous transfer round-trip
        per column, which dominates egress through a high-latency
        link.  ``extra`` arrays (e.g. deferred
        dict-miss counters) ride the same transfer; ``extras`` is empty
        when none were passed.

        The wait ``device_get`` would make itself is made first, so
        "the program had not finished" (``fetch_wait``) and "the copy
        took long" (``fetch_copy``) are two spans of ``tracer``;
        ``fetch_copy``'s ``bytes`` is the batch's (``valid`` + columns,
        what ``d2h_bytes`` counts), without the few bytes of ``extra``."""
        assert "#valid" not in self.data, "'#valid' is a reserved name"
        wanted = ({"#valid": self.valid, **self.data}, list(extra))
        with tracer.span("fetch_wait", cat="readback"):
            jax.block_until_ready(wanted)
        nbytes = sum(a.size * a.dtype.itemsize for a in wanted[0].values())
        with tracer.span(
            "fetch_copy", cat="readback", bytes=nbytes,
            capacity=self.capacity, columns=len(self.data),
        ):
            host, extras = jax.device_get(wanted)
        valid = host.pop("#valid")
        return valid, host, extras

    def to_numpy(
        self,
        schema: Schema,
        dictionary: Optional[StringDictionary] = None,
        _host: Optional[Tuple[np.ndarray, Dict[str, np.ndarray]]] = None,
    ) -> Dict[str, np.ndarray]:
        """Decode valid rows back to host logical columns.  ``_host``:
        already-fetched ``(valid, columns)`` from :meth:`fetch_host`
        (callers that batched the transfer with extra arrays)."""
        valid, host = _host if _host is not None else self.fetch_host()[:2]
        return decode_physical_table(schema, valid, host, dictionary)


def decode_physical_table(
    schema: Schema,
    valid,
    host: Dict[str, np.ndarray],
    dictionary: Optional[StringDictionary] = None,
) -> Dict[str, np.ndarray]:
    """Physical host columns -> logical table (``valid`` is a bool mask
    or a full slice).  The inverse of :func:`encode_physical`."""
    out: Dict[str, np.ndarray] = {}
    for f in schema.fields:
        if f.ctype == ColumnType.STRING:
            lo = host[f"{f.name}#h0"][valid]
            hi = host[f"{f.name}#h1"][valid]
            hashes = join64(lo, hi)
            if dictionary is None:
                out[f.name] = hashes  # fall back to raw hashes
            else:
                out[f.name] = np.array(
                    dictionary.lookup_all(hashes), dtype=object
                )
        elif f.ctype == ColumnType.INT64:
            lo = host[f"{f.name}#h0"][valid]
            hi = host[f"{f.name}#h1"][valid]
            out[f.name] = join64(lo, hi, signed=True)
        elif f.ctype == ColumnType.FLOAT64:
            from dryad_tpu.columnar.schema import ordered_i64_to_f64

            lo = host[f"{f.name}#h0"][valid]
            hi = host[f"{f.name}#h1"][valid]
            out[f.name] = ordered_i64_to_f64(join64(lo, hi, signed=True))
        else:
            out[f.name] = np.asarray(host[f.name])[valid]
    return out
