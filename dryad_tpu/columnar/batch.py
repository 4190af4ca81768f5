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

import functools
import math
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from dryad_tpu.columnar.schema import (
    ColumnType,
    Schema,
    StringDictionary,
    bytes_to_words,
    join64,
    split64,
    words_to_bytes,
)
from dryad_tpu.obs.span import UNTRACED, Tracer


def encode_physical(
    field, a: np.ndarray, dictionary: Optional[StringDictionary]
) -> Dict[str, np.ndarray]:
    """One logical host column -> its physical device/store columns
    (STRING: Hash64 words + memcomparable prefix ranks; INT64/FLOAT64:
    order-preserving split words; BYTES: big-endian words).  Shared by
    device ingest and the
    streaming store writer, so ``.dpf`` parts written out-of-core read
    back through the same ``store`` binding path."""
    if field.ctype.is_bytes:
        words = bytes_to_words(a, field.ctype.width)
        return dict(zip(field.device_names, words))
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
    if field.ctype.storage is ColumnType.INT64:  # INT64, a wide DECIMAL
        lo, hi = split64(a.astype(np.int64))
        return {f"{field.name}#h0": lo, f"{field.name}#h1": hi}
    if field.ctype == ColumnType.FLOAT64:
        from dryad_tpu.columnar.schema import f64_to_ordered_i64

        lo, hi = split64(f64_to_ordered_i64(a))
        return {f"{field.name}#h0": lo, f"{field.name}#h1": hi}
    return {field.name: host_to_device(field.ctype, a)}


def host_to_device(ctype, a: np.ndarray) -> np.ndarray:
    """A one-word logical column in its device dtype.  A DATE's days
    since 1970-01-01 are counted from whatever unit the array has; every
    other type is a cast."""
    if ctype is ColumnType.DATE and a.dtype.kind == "M":
        a = a.astype("datetime64[D]").astype(np.int64)
    return a.astype(ctype.storage.numpy_dtype)


def encode_table(
    schema: Schema,
    arrays: Dict[str, np.ndarray],
    dictionary: Optional[StringDictionary],
) -> Tuple[Dict[str, np.ndarray], int]:
    """Logical host table -> ``(physical host columns at n rows, row
    count)``.  Host-only (NumPy in, NumPy out).  The sharded ingest
    edge does not come through here: ``parallel.distribute.
    from_host_table`` writes a column's physical form straight into
    its ``P * capacity`` layout."""
    n = None
    for name in schema.names:
        a = np.asarray(arrays[name])
        if n is None:
            n = len(a)
        elif len(a) != n:
            raise ValueError("ragged input columns")
    phys: Dict[str, np.ndarray] = {}
    for f in schema.fields:
        phys.update(encode_physical(f, np.asarray(arrays[f.name]), dictionary))
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
        self, extra: Sequence[jax.Array] = (), tracer: Tracer = UNTRACED,
        metrics=None,
    ):
        """(valid, columns, extras, rows) on the host, via ONE
        ``jax.device_get`` so PJRT overlaps all the device->host copies
        (copy_to_host_async then a single block).  A per-column
        ``np.asarray`` loop pays one synchronous transfer round-trip
        per column, which dominates egress through a high-latency
        link.  ``extra`` arrays (e.g. deferred
        dict-miss counters) ride the same transfer; ``extras`` is empty
        when none were passed.

        **What is copied.**  Padding does not travel.  For a batch
        whose whole copy is ``TRIM_MIN_BYTES`` or more (and that lies
        on this process's devices in one layout), a small program over
        ``valid`` alone says, a shard, how many rows are valid and how
        far they reach (``rows``, a :class:`ShardRows`; one readback of
        2 x P integers).  Where the furthest reach fits a smaller tier
        of :func:`trim_tiers` than the capacity, a second small program
        cuts ``valid`` and every column to ``[:tier]`` a shard on the
        device, and the one ``device_get`` copies P x tier slots
        instead of P x capacity: ``valid`` and ``columns`` then hold
        shard s in ``[s * tier, (s + 1) * tier)``.  Nothing is assumed
        about the layout, it is measured a fetch: a batch with holes is
        cut after its last valid row and masked on the host as ever, a
        full one is copied whole.  Under the gate, or across processes,
        ``rows`` is None and the arrays come back at capacity.

        The wait ``device_get`` would make itself is made first, so
        "the program had not finished" (``fetch_wait``), "asking and
        cutting" (``fetch_trim``, only over the gate) and "the copy
        took long" (``fetch_copy``) are spans of ``tracer``;
        ``fetch_copy``'s ``bytes`` is what was copied of the batch
        (``valid`` + columns, without the few bytes of ``extra``), and
        so is what ``metrics`` (the executor's registry) gains under
        ``d2h_bytes``; ``d2h_bytes_trimmed`` gains the bytes left on
        the device, ``xla_compiles`` the first use of either small
        program at a shape."""
        assert "#valid" not in self.data, "'#valid' is a reserved name"
        arrays = {"#valid": self.valid, **self.data}
        extra = list(extra)
        with tracer.span("fetch_wait", cat="readback"):
            jax.block_until_ready((arrays, extra))
        whole = _nbytes(arrays)
        rows = None
        if whole >= TRIM_MIN_BYTES and _one_local_layout(arrays):
            arrays, rows = _trim_to_extent(arrays, tracer, metrics)
        nbytes = _nbytes(arrays)
        with tracer.span(
            "fetch_copy", cat="readback", account=True, bytes=nbytes,
            capacity=self.capacity, columns=len(self.data),
        ):
            host, extras = jax.device_get((arrays, extra))
            # a cut copy goes here, and the host copy of each of its
            # shards with it (``host`` is jax's assembly of them)
            del arrays
        if metrics is not None:
            metrics.add("d2h_bytes", nbytes)
            metrics.add("d2h_bytes_trimmed", whole - nbytes)
        valid = host.pop("#valid")
        return valid, host, extras, rows

    def to_numpy(
        self,
        schema: Schema,
        dictionary: Optional[StringDictionary] = None,
        _host: Optional[Tuple[np.ndarray, Dict[str, np.ndarray]]] = None,
        tracer: Tracer = UNTRACED,
    ) -> Dict[str, np.ndarray]:
        """Decode valid rows back to host logical columns.  ``_host``:
        already-fetched ``(rows, columns)`` from :meth:`fetch_host`
        (callers that batched the transfer with extra arrays); ``rows``
        is whatever :func:`decode_physical_table` takes."""
        valid, host = _host if _host is not None else self.fetch_host()[:2]
        return decode_physical_table(schema, valid, host, dictionary, tracer)


# fetch_host asks how far a batch's valid rows reach only when the
# batch's whole copy (capacity x bytes a row, every shard) is at least
# this: where asking is cheaper than the padding could be.  Asking is
# one small program, a synchronous readback of 2 x P integers and the
# dispatch of the cut.  Measured on the four-chip v5e host over batches
# of 9 B a slot (PERF.md section 6, PR 31): a whole fetch takes 1.24 ms
# + 0.114 ms a MB up to 38 MB (small copies run at 8.8 GB/s; the 0.8
# GB/s of section 5 is the rate of answers of hundreds of MB), a fetch
# that asks and finds 1% valid 3.0 - 3.1 ms flat, one that asks and
# finds the batch full 1.2 - 1.4 ms over the whole fetch.  So an answer
# that is all padding pays the asking back from 16 MB on (9.4 MB: 2.1
# ms whole, 3.1 asked; 37.7 MB: 5.4 whole, 3.1 asked), and under that
# asking can only lose.  (At the 0.8 GB/s of large answers the same
# 1.2 ms would buy 1 MB, ISSUE 31's guess; the measured rate of small
# copies is what a small answer pays.)
TRIM_MIN_BYTES = 16 << 20


class ShardRows(NamedTuple):
    """What :meth:`ColumnBatch.fetch_host` measured of a batch on the
    device: of each shard's ``tier`` fetched slots, ``counts`` are valid
    and all of them lie in the first ``extents``."""

    tier: int
    counts: Tuple[int, ...]
    extents: Tuple[int, ...]

    @property
    def packed(self) -> bool:
        """No hole before a shard's last valid row: its rows are a
        slice."""
        return self.counts == self.extents

    def slices(self) -> List[slice]:
        """The valid rows of the fetched arrays, a shard (``packed``)."""
        return [
            slice(s * self.tier, s * self.tier + n)
            for s, n in enumerate(self.counts)
        ]


@functools.lru_cache(maxsize=64)
def trim_tiers(capacity: int) -> Tuple[int, ...]:
    """The sizes a shard of ``capacity`` slots may be cut to for the
    copy back, ascending: ``capacity / 2^(k/4)`` rounded up to 8 rows,
    down to 8, and ``capacity`` itself last.  A bounded ladder (about
    ``4 log2(capacity)`` rungs, so as many trim programs at worst) that
    copies at most a fifth over what the valid rows need; the powers of
    two below ``capacity`` are rungs exactly."""
    tiers = set()
    k = 0
    while True:
        rows = math.ceil(
            capacity * 2.0 ** (-(k % 4) / 4.0) / (1 << (k // 4))
        )
        tier = min(capacity, -(-rows // 8) * 8)
        tiers.add(tier)
        if tier <= 8:
            return tuple(sorted(tiers))
        k += 1


def _nbytes(arrays) -> int:
    """Bytes of a dict of arrays, on the device or on the host."""
    return sum(a.size * a.dtype.itemsize for a in arrays.values())


def _one_local_layout(arrays: Dict[str, jax.Array]) -> bool:
    """Every array is a device array this process can read whole, laid
    over the devices as ``valid`` is (one device, or a mesh's named
    sharding): the shards can then be cut alike.  The multi-controller
    gang's batches are not, and keep the whole fetch."""
    valid = arrays["#valid"]
    if not isinstance(valid, jax.Array) or valid.shape[0] == 0:
        return False
    sharding = valid.sharding
    if not (
        isinstance(sharding, NamedSharding) or len(sharding.device_set) == 1
    ):
        return False
    return all(
        isinstance(a, jax.Array)
        and a.is_fully_addressable
        and a.sharding.is_equivalent_to(sharding, a.ndim)
        for a in arrays.values()
    )


# the two egress programs, compiled: (name, sharding, shapes and
# dtypes[, tier]) -> executable.  Bounded by the tier ladder a shape.
_EGRESS_PROGRAMS: Dict[tuple, Callable] = {}


def _egress_program(key, shard_fn, args, tracer, metrics) -> Callable:
    """``shard_fn`` (a shard's arrays -> a shard's arrays) as a program
    of its own over ``args``' sharding, compiled once a ``key``.  A
    compile is a ``compile`` span and counts into ``xla_compiles`` /
    ``xla_compile_s`` like a stage's."""
    prog = _EGRESS_PROGRAMS.get(key)
    if prog is not None:
        return prog
    name, sharding = key[0], key[1]
    if isinstance(sharding, NamedSharding):
        fn = jax.shard_map(
            shard_fn, mesh=sharding.mesh, in_specs=(sharding.spec,),
            out_specs=sharding.spec, check_vma=False,
        )
    else:  # one device: the shard is the array

        def fn(shard):
            return shard_fn(shard)

    fn.__name__ = fn.__qualname__ = name
    t0 = time.monotonic()
    with tracer.span(name, cat="compile"):
        prog = jax.jit(fn).lower(*args).compile()
    if metrics is not None:
        metrics.add("xla_compiles", 1.0, stage=name)
        metrics.add("xla_compile_s", time.monotonic() - t0, stage=name)
    _EGRESS_PROGRAMS[key] = prog
    return prog


def _shard_extent(valid: jax.Array) -> jax.Array:
    """One shard's (valid rows, index of the last valid row + 1)."""
    with jax.named_scope("dryad.egress.extent"):
        last = jnp.where(
            valid, jnp.arange(1, valid.shape[0] + 1, dtype=jnp.int32), 0
        )
        return jnp.stack(
            [jnp.sum(valid, dtype=jnp.int32), jnp.max(last)]
        )[None]


def _shard_prefix(tier: int, shard: Dict[str, jax.Array]):
    """One shard's first ``tier`` slots of every array."""
    with jax.named_scope("dryad.egress.trim"):
        return {n: a[:tier] for n, a in shard.items()}


def _trim_to_extent(arrays, tracer: Tracer, metrics):
    """``arrays`` (``#valid`` and the columns, one layout) cut on the
    device to the tier their valid rows reach, and the
    :class:`ShardRows` read back on the way.  At the top tier the
    arrays come back as they are."""
    valid = arrays["#valid"]
    sharding = valid.sharding
    capacity = sharding.shard_shape(valid.shape)[0]  # slots a shard
    with tracer.span(
        "fetch_trim", cat="readback", capacity=valid.shape[0],
        shards=valid.shape[0] // capacity,
    ) as sp:
        extent_of = _egress_program(
            ("dryad_egress_extent", sharding, valid.shape),
            _shard_extent, (valid,), tracer, metrics,
        )
        measured = np.asarray(jax.device_get(extent_of(valid)))
        counts = tuple(measured[:, 0].tolist())
        extents = tuple(measured[:, 1].tolist())
        reach = max(extents)
        tier = next(t for t in trim_tiers(capacity) if t >= reach)
        sp.add(
            tier=tier, extent_max=reach, count=sum(counts),
            trimmed=int(tier < capacity),
        )
        if tier < capacity:
            shapes = tuple(
                (n, a.shape, a.dtype.name) for n, a in sorted(arrays.items())
            )
            arrays = _egress_program(
                ("dryad_egress_trim", sharding, shapes, tier),
                functools.partial(_shard_prefix, tier), (arrays,),
                tracer, metrics,
            )(arrays)
    return arrays, ShardRows(tier, counts, extents)


def decode_physical_table(
    schema: Schema,
    valid,
    host: Dict[str, np.ndarray],
    dictionary: Optional[StringDictionary] = None,
    tracer: Tracer = UNTRACED,
) -> Dict[str, np.ndarray]:
    """Physical host columns -> logical table.  ``valid`` says which
    slots are rows: a bool mask, a full slice, or a list of slices, one
    a shard (:meth:`ShardRows.slices`: the fetched prefix of each shard
    where it has no hole, so no mask is walked), whose rows are joined
    in shard order.  The inverse of :func:`encode_physical`; turning a
    BYTES column's words back into bytes is an ``unpack`` span of
    ``tracer`` (``bytes`` of the column as handed out, ``rows``)."""
    if isinstance(valid, list):

        def rows(name):
            return np.concatenate([host[name][s] for s in valid])

    else:

        def rows(name):
            return np.asarray(host[name])[valid]

    out: Dict[str, np.ndarray] = {}
    for f in schema.fields:
        if f.ctype.is_bytes:
            out[f.name] = _unpack_bytes(f, valid, host, rows, tracer)
        elif f.ctype == ColumnType.STRING:
            hashes = join64(rows(f"{f.name}#h0"), rows(f"{f.name}#h1"))
            if dictionary is None:
                out[f.name] = hashes  # fall back to raw hashes
            else:
                out[f.name] = np.array(
                    dictionary.lookup_all(hashes), dtype=object
                )
        elif f.ctype.storage is ColumnType.INT64:  # INT64, a wide DECIMAL
            out[f.name] = join64(
                rows(f"{f.name}#h0"), rows(f"{f.name}#h1"), signed=True
            )
        elif f.ctype == ColumnType.FLOAT64:
            from dryad_tpu.columnar.schema import ordered_i64_to_f64

            out[f.name] = ordered_i64_to_f64(join64(
                rows(f"{f.name}#h0"), rows(f"{f.name}#h1"), signed=True
            ))
        elif f.ctype is ColumnType.DATE:
            out[f.name] = rows(f.name).astype(np.int64).astype("datetime64[D]")
        else:
            out[f.name] = rows(f.name)
    return out


def _unpack_bytes(field, valid, host, rows, tracer: Tracer) -> np.ndarray:
    """One BYTES column of :func:`decode_physical_table`, ``[rows,
    width]`` uint8.  Where the valid rows are slices of the fetched
    words, each shard's are unpacked straight into its rows of the
    answer: no copy of the words is made first."""
    width, names = field.ctype.width, field.device_names
    if isinstance(valid, list):
        parts = [[host[c][s] for c in names] for s in valid]  # views
    else:
        parts = [[rows(c) for c in names]]
    total = sum(len(words[0]) for words in parts)
    with tracer.span(
        "unpack", cat="decode", account=True, bytes=total * width,
        rows=total, bytes_out=total * width,
    ):
        out = np.empty((total, width), np.uint8)
        at = 0
        for words in parts:
            n = len(words[0])
            words_to_bytes(words, width, out[at : at + n])
            at += n
    return out
