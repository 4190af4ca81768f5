"""Schema / type system for columnar batches.

The reference ships a row-oriented binary record format with per-type
(de)serializers (``LinqToDryad/DryadLinqBinaryReader.cs``,
``DryadLinqSerialization.cs``).  The TPU-native design is columnar
(struct-of-arrays in HBM): a ``Schema`` is an ordered list of named,
typed columns; records are rows across those columns.

Strings cannot live on a TPU, so STRING columns are dictionary-encoded at
ingest: each string becomes a 64-bit hash carried as TWO uint32 device
columns (``name#h0``/``name#h1`` — avoids requiring jax x64 mode), with a
host-side :class:`StringDictionary` mapping hashes back to strings at
egress.  This follows the reference's own precedent of hashing record
keys with a deterministic 64-bit hash (``LinqToDryad/Hash64.cs``).
"""

from __future__ import annotations

import dataclasses
import enum
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np


class ColumnType(enum.Enum):
    INT32 = "int32"
    INT64 = "int64"  # stored on device as two uint32 words (#h0 low, #h1 high)
    FLOAT32 = "float32"
    # Stored on device as the ORDER-PRESERVING signed-int64 image of the
    # IEEE-754 bits (two uint32 words): exact round-trip, and every
    # int64 comparison/sort/min/max kernel applies unchanged.  No f64
    # arithmetic on device (x64 stays off): sum/mean are rejected with
    # a cast-to-f32 suggestion.
    FLOAT64 = "float64"
    BOOL = "bool"
    UINT32 = "uint32"
    STRING = "string"  # dictionary-encoded: two uint32 hash words + host dict
    # A calendar day.  Host form ``datetime64[D]``, in and out; device
    # form ONE int32 column of days since 1970-01-01, so every int32
    # kernel (compare, sort, min / max, group key) applies unchanged.
    # In a row function it is that int32: compare it with :func:`date`.
    DATE = "date"

    @property
    def is_split(self) -> bool:
        """True when the logical column maps to multiple uint32 device columns."""
        return self in (ColumnType.INT64, ColumnType.FLOAT64, ColumnType.STRING)

    @property
    def is_bytes(self) -> bool:
        return False

    @property
    def storage(self) -> "ColumnType":
        """The type whose device form and kernels this one shares."""
        return ColumnType.INT32 if self is ColumnType.DATE else self

    @property
    def numpy_dtype(self) -> np.dtype:
        return {
            ColumnType.INT32: np.dtype(np.int32),
            ColumnType.INT64: np.dtype(np.int64),
            ColumnType.FLOAT32: np.dtype(np.float32),
            ColumnType.FLOAT64: np.dtype(np.float64),
            ColumnType.BOOL: np.dtype(np.bool_),
            ColumnType.UINT32: np.dtype(np.uint32),
            ColumnType.STRING: np.dtype(object),
            ColumnType.DATE: np.dtype("datetime64[D]"),
        }[self]


@dataclasses.dataclass(frozen=True)
class BytesType:
    """Fixed-width opaque bytes, ``BYTES(width)``: the column type that
    carries its width.  Takes a :class:`ColumnType` member's place in a
    :class:`Field` and answers what the members answer.

    Host form: a 2-D ``uint8`` array ``[rows, width]``, in and out.
    Device form: ``ceil(width / 4)`` uint32 columns ``#b0``, ``#b1``,
    ..., each four bytes as one big-endian word, the last zero-padded
    on the right, so the words' lexicographic order is the bytes'
    ``memcmp`` order and, every value having the same width, the
    padding never decides.  No dictionary, no hash: the words are the
    value."""

    width: int

    def __post_init__(self) -> None:
        if not isinstance(self.width, int) or self.width < 1:
            raise ValueError(f"BYTES width must be a positive int, got {self.width!r}")

    is_split = True
    is_bytes = True

    @property
    def value(self) -> str:
        return f"bytes[{self.width}]"

    @property
    def words(self) -> int:
        """uint32 device columns a value takes."""
        return -(-self.width // 4)

    @property
    def numpy_dtype(self) -> np.dtype:
        return np.dtype(np.uint8)

    def __repr__(self) -> str:
        return f"BYTES({self.width})"


    @property
    def storage(self):
        return self


BYTES = BytesType


@dataclasses.dataclass(frozen=True)
class DecimalType:
    """An exact fixed-point number, ``DECIMAL(scale)``: scaled integers
    with ``scale`` digits after the point, the type carrying the scale
    as :class:`BytesType` carries its width.

    Host form: an integer array of the SCALED values (12.34 at scale 2
    is 1234), in and out: ``int32`` for the narrow form (32 bits, one
    int32 device column, the form a table's columns take where they
    fit), ``int64`` for the wide one (``DECIMAL(scale, wide=True)``: 64
    bits, two uint32 device words ``#h0`` / ``#h1`` as INT64 has them).
    The engine keeps the scale through ``select`` (``ops/wide.py::Dec``:
    a product's scale is the sum of its factors' and its form wide),
    ``group_by`` (``sum`` is wide at the column's scale and exact
    modulo 2^64, ``min`` / ``max`` / ``first`` keep the type, ``mean``
    is an f32 in units) and ``order_by``; nothing rounds."""

    scale: int
    wide: bool = False

    def __post_init__(self) -> None:
        if not isinstance(self.scale, int) or not 0 <= self.scale <= 18:
            raise ValueError(f"DECIMAL scale must be an int in 0..18, got {self.scale!r}")

    is_bytes = False

    @property
    def is_split(self) -> bool:
        return self.wide

    @property
    def value(self) -> str:
        return f"decimal{64 if self.wide else 32}[{self.scale}]"

    @property
    def numpy_dtype(self) -> np.dtype:
        return np.dtype(np.int64 if self.wide else np.int32)

    @property
    def storage(self) -> ColumnType:
        return ColumnType.INT64 if self.wide else ColumnType.INT32

    def __repr__(self) -> str:
        return f"DECIMAL({self.scale}{', wide=True' if self.wide else ''})"


DECIMAL = DecimalType


def date(day) -> int:
    """A calendar day (``"1998-09-02"``, a ``datetime.date``, a
    ``datetime64``) as a DATE column holds it on the device: days since
    1970-01-01.  What a ``where`` compares a DATE column with."""
    return int(np.datetime64(day, "D").astype(np.int64))


def parse_ctype(value: str):
    """The column type a manifest's string names: the inverse of
    ``ctype.value`` (``"int32"`` ..., ``"bytes[10]"``,
    ``"decimal32[2]"``)."""
    if value.startswith("bytes[") and value.endswith("]"):
        return BytesType(int(value[6:-1]))
    for prefix, wide in (("decimal32[", False), ("decimal64[", True)):
        if value.startswith(prefix) and value.endswith("]"):
            return DecimalType(int(value[len(prefix):-1]), wide)
    return ColumnType(value)


def device_column_names(name: str, ctype) -> List[str]:
    """Physical device-column names backing one logical column.

    INT64 / a wide DECIMAL -> ``#h0`` (low word), ``#h1`` (high word).
    STRING -> ``#h0``/``#h1`` (Hash64 words, the identity) plus ``#r0``/``#r1``,
    an order-preserving uint32 rank of the first 4 UTF-8 bytes
    (big-endian), so range partitioning / OrderBy on strings is exact on
    4-byte prefixes with hash-order tie-breaking beyond that.
    BYTES(w) -> ``#b0`` ... ``#b<ceil(w / 4) - 1>``, big-endian words in
    byte order (:class:`BytesType`).
    """
    if ctype.is_bytes:
        return [f"{name}#b{i}" for i in range(ctype.words)]
    if ctype == ColumnType.STRING:
        return [f"{name}#h0", f"{name}#h1", f"{name}#r0", f"{name}#r1"]
    if ctype.storage in (ColumnType.INT64, ColumnType.FLOAT64):
        return [f"{name}#h0", f"{name}#h1"]
    return [name]


# Rows a block of the two transposing passes below: a block of a
# 128-byte column and its words (2 x 2 MiB) stays in the host's cache,
# where one whole-table transposing copy walks a page a row a word.
# A table of many blocks is cut into as many runs of rows as there are
# threads, because most of a pass over hundreds of MiB is the first
# touch of newly mapped pages, which threads take side by side
# (NumPy's copies release the GIL).  On the chip's host, 2^23 records
# of 100 bytes: ``pack`` 1.267 -> 0.365 s, ``unpack`` 1.326 -> 0.507 s
# with four threads, and the requery's run-to-run spread 1.23% -> 0.35%
# (PERF.md section 6, PR 32).
_WORD_BLOCK_ROWS = 1 << 14
_WORD_THREADS = 4


def _over_row_blocks(rows: int, width: int, work) -> None:
    """``work(block, lo, n)`` for every block of rows ``[lo, lo + n)``;
    ``block`` is a zeroed ``[block rows, 4 * words]`` uint8 scratch, one
    a thread."""
    words = -(-width // 4)

    def run(lo: int, hi: int) -> None:
        block = np.zeros((min(hi - lo, _WORD_BLOCK_ROWS), 4 * words), np.uint8)
        for at in range(lo, hi, _WORD_BLOCK_ROWS):
            work(block, at, min(_WORD_BLOCK_ROWS, hi - at))

    blocks = -(-rows // _WORD_BLOCK_ROWS)
    threads = min(_WORD_THREADS, blocks // 4)
    if threads < 2:
        run(0, rows)
        return
    per = -(-blocks // threads) * _WORD_BLOCK_ROWS
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(
            lambda lo: run(lo, min(rows, lo + per)), range(0, rows, per)
        ))


def bytes_to_words(
    values: np.ndarray, width: int, out: Optional[Sequence[np.ndarray]] = None
) -> List[np.ndarray]:
    """``[rows, width]`` uint8 -> ``ceil(width / 4)`` uint32 arrays of
    big-endian words, the last zero-padded; written into ``out`` (one
    array of ``rows`` a word) where given, else into the rows of one
    new ``[words, rows]`` array.  Array passes a block of rows, never a
    row."""
    a = np.asarray(values)
    if a.dtype != np.uint8 or a.ndim != 2 or a.shape[1] != width:
        raise ValueError(
            f"BYTES({width}) takes a [rows, {width}] uint8 array, got "
            f"{a.dtype} {a.shape}"
        )
    rows = a.shape[0]
    words = -(-width // 4)
    if out is None:
        out = np.empty((words, rows), np.uint32)
    elif len(out) != words or any(
        w.dtype != np.uint32 or w.shape != (rows,) for w in out
    ):
        raise ValueError(
            f"BYTES({width}) packs {rows} rows into {words} uint32 arrays "
            f"of {rows}, got {[(str(w.dtype), w.shape) for w in out]}"
        )

    def pack(block, lo, n):  # the block's padding bytes stay zero
        block[:n, :width] = a[lo : lo + n]
        as_words = block.view(">u4")
        for i, w in enumerate(out):
            w[lo : lo + n] = as_words[:n, i]

    _over_row_blocks(rows, width, pack)
    return list(out)


def words_to_bytes(
    words: Sequence[np.ndarray], width: int, out: Optional[np.ndarray] = None
) -> np.ndarray:
    """The inverse of :func:`bytes_to_words`: ``[rows, width]`` uint8,
    written into ``out`` where given."""
    rows = len(words[0])
    if out is None:
        out = np.empty((rows, width), np.uint8)

    def unpack(block, lo, n):
        as_words = block.view(">u4")
        for i, w in enumerate(words):
            as_words[:n, i] = w[lo : lo + n]
        out[lo : lo + n] = block[:n, :width]

    _over_row_blocks(rows, width, unpack)
    return out


def string_prefix_rank(strings: "np.ndarray", offset: int = 0) -> "np.ndarray":
    """uint32 big-endian rank of UTF-8 bytes [offset, offset+4) of each
    string — memcomparable prefix words (``#r0`` offset 0, ``#r1``
    offset 4: exact ordering for 8-byte prefixes, hash-order beyond)."""
    out = np.zeros(len(strings), np.uint32)
    for i, s in enumerate(strings):
        b = str(s).encode("utf-8")[offset : offset + 4]
        r = 0
        for j in range(4):
            r = (r << 8) | (b[j] if j < len(b) else 0)
        out[i] = r
    return out


FNV_OFFSET = 0xCBF29CE484222325
FNV_PRIME = 0x100000001B3
_MASK64 = (1 << 64) - 1


def hash64_bytes(data: bytes) -> int:
    """Deterministic 64-bit FNV-1a hash.

    The framework-wide string hash, the analog of the reference's
    deterministic ``Hash64`` (``LinqToDryad/Hash64.cs``) used so every
    machine partitions identically.  Implemented identically in the
    native runtime (``runtime/native/dryadnative.cpp``).
    """
    h = FNV_OFFSET
    for b in data:
        h ^= b
        h = (h * FNV_PRIME) & _MASK64
    return h


def hash64_str(s: str) -> int:
    return hash64_bytes(s.encode("utf-8"))


def split64(values: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Split uint64/int64 array into (low, high) uint32 words."""
    v = values.astype(np.uint64)
    lo = (v & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (v >> np.uint64(32)).astype(np.uint32)
    return lo, hi


def join64(lo: np.ndarray, hi: np.ndarray, signed: bool = False) -> np.ndarray:
    v = lo.astype(np.uint64) | (hi.astype(np.uint64) << np.uint64(32))
    return v.view(np.int64) if signed else v


_SIGN64 = np.uint64(1 << 63)


def f64_to_ordered_i64(values: np.ndarray) -> np.ndarray:
    """Order-preserving signed-int64 image of float64 values.

    The classic memcomparable-double transform (negatives: ~bits;
    non-negatives: bits | signbit) shifted into the signed domain
    (xor signbit), so signed-int64 comparisons order exactly like the
    doubles under IEEE-754 totalOrder semantics: -0.0 orders below
    +0.0, sign-negative NaNs below -inf, sign-positive NaNs above +inf
    (the documented engine semantic for float64 ordering).
    """
    bits = np.ascontiguousarray(values, np.float64).view(np.uint64)
    neg = (bits & _SIGN64) != 0
    t = np.where(neg, ~bits ^ _SIGN64, bits)
    return t.view(np.int64)


def ordered_i64_to_f64(vals: np.ndarray) -> np.ndarray:
    """Inverse of :func:`f64_to_ordered_i64`."""
    s = np.ascontiguousarray(vals, np.int64).view(np.uint64)
    neg = (s & _SIGN64) != 0  # negatives map to signed-negative images
    bits = np.where(neg, ~(s ^ _SIGN64), s)
    return bits.view(np.float64)


class StringDictionary:
    """Host-side hash -> string mapping for dictionary-encoded columns.

    Built at ingest, consulted only at egress (the reference keeps string
    payloads in channel bytes; we keep them on the host and ship hashes).
    """

    def __init__(self) -> None:
        self._map: Dict[int, str] = {}

    def __len__(self) -> int:
        return len(self._map)

    def add(self, s: str) -> int:
        h = hash64_str(s)
        existing = self._map.get(h)
        if existing is not None and existing != s:
            # 64-bit collision between distinct strings: astronomically
            # unlikely; surface loudly rather than silently merging keys.
            raise ValueError(f"hash64 collision: {existing!r} vs {s!r}")
        self._map[h] = s
        return h

    def add_all(self, strings: Iterable[str]) -> np.ndarray:
        return np.array([self.add(s) for s in strings], dtype=np.uint64)

    def lookup(self, h: int) -> str:
        return self._map[int(h)]

    def lookup_all(self, hashes: np.ndarray) -> List[str]:
        return [self._map[int(h)] for h in np.asarray(hashes).ravel()]

    def merge(self, other: "StringDictionary") -> "StringDictionary":
        out = StringDictionary()
        out._map.update(self._map)
        for h, s in other._map.items():
            if h in out._map and out._map[h] != s:
                raise ValueError(f"hash64 collision merging dictionaries: {s!r}")
            out._map[h] = s
        return out

    def items(self):
        # Snapshot: a streaming prefetch thread may register tokens
        # concurrently with a consumer iterating the dictionary (e.g.
        # build_tables during lowering) — a live view would raise
        # "dict changed size during iteration".
        return list(self._map.items())


@dataclasses.dataclass(frozen=True)
class Field:
    name: str
    ctype: ColumnType  # or a BytesType, which carries the width

    @property
    def device_names(self) -> List[str]:
        return device_column_names(self.name, self.ctype)

    @property
    def identity_names(self) -> List[str]:
        """The device columns whose tuple-equality is value equality:
        all of them, but for STRING, whose ``#r`` rank words only order."""
        names = self.device_names
        return names[:2] if self.ctype == ColumnType.STRING else names


class Schema:
    """Ordered, named, typed columns of a dataset."""

    def __init__(self, fields: Sequence[Tuple[str, ColumnType]]):
        self.fields: List[Field] = [Field(n, t) for n, t in fields]
        names = [f.name for f in self.fields]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate column names in schema: {names}")
        self._by_name = {f.name: f for f in self.fields}

    @property
    def names(self) -> List[str]:
        return [f.name for f in self.fields]

    def field(self, name: str) -> Field:
        return self._by_name[name]

    def __contains__(self, name: str) -> bool:
        return name in self._by_name

    def __eq__(self, other: object) -> bool:
        return isinstance(other, Schema) and self.fields == other.fields

    def __repr__(self) -> str:
        cols = ", ".join(f"{f.name}:{f.ctype.value}" for f in self.fields)
        return f"Schema({cols})"

    def device_names(self) -> List[str]:
        out: List[str] = []
        for f in self.fields:
            out.extend(f.device_names)
        return out

    def device_dtypes(self) -> Dict[str, np.dtype]:
        """Physical device column -> its dtype: the words of a split
        column are uint32, any other column keeps its own."""
        return {
            n: np.dtype(np.uint32) if f.ctype.is_split
            else f.ctype.storage.numpy_dtype
            for f in self.fields for n in f.device_names
        }

    def with_field(self, name: str, ctype: ColumnType) -> "Schema":
        return Schema([(f.name, f.ctype) for f in self.fields] + [(name, ctype)])

    def select(self, names: Sequence[str]) -> "Schema":
        return Schema([(n, self._by_name[n].ctype) for n in names])
