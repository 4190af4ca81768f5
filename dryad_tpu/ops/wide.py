"""Exact 64-bit integer arithmetic on ``(lo, hi)`` uint32 word pairs,
and the DECIMAL value a ``select`` / ``where`` function computes with.

jax's x64 mode stays off, so a 64-bit integer lives on the device as
two uint32 words (``columnar/schema.py``: ``#h0`` low, ``#h1`` high,
two's complement).  This module is the ONE implementation of arithmetic
on such pairs: add / subtract / negate / compare, and the multiplies a
scaled-integer expression needs (32 x 32 -> 64, 64 x 32 -> 64, 64 x 64
-> 64), the high half of a 32-bit product built from 16-bit limbs
(the TPU's vector unit multiplies 32 bits into 32).  Everything wraps
modulo 2^64, as NumPy's int64 does; nothing here detects an overflow.
``ops/segmented.py``'s 64-bit aggregates add and compare through it.

:class:`Dec` is what a DECIMAL column looks like inside a user's row
function (``Query.select`` / ``Query.where``): scaled integers, narrow
(one int32 word) or wide (a pair), with the scale as static data, so
that ``price * (1 - discount) * (1 + tax)`` is written as SQL writes it
and comes out a DECIMAL whose scale is the sum of its factors'.  No
float takes part.

Every device operation made here carries the scope ``dryad.decimal``.
"""

from __future__ import annotations

import decimal
import functools
from typing import Dict, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from dryad_tpu.columnar.schema import DecimalType, device_column_names

Pair = Tuple[jax.Array, jax.Array]  # (lo, hi), uint32

SCOPE = "dryad.decimal"
_LOW16 = 0xFFFF


def _scoped(fn):
    @functools.wraps(fn)
    def inner(*args, **kwargs):
        with jax.named_scope(SCOPE):
            return fn(*args, **kwargs)

    return inner


def _u32(x) -> jax.Array:
    return jnp.asarray(x).astype(jnp.uint32)


# -- word pairs ---------------------------------------------------------------

def widen(x) -> Pair:
    """A signed 32-bit column as a sign-extended pair."""
    x = jnp.asarray(x).astype(jnp.int32)
    return x.astype(jnp.uint32), (x >> 31).astype(jnp.uint32)


def add64(alo, ahi, blo, bhi) -> Pair:
    """``a + b`` modulo 2^64: the low words' carry goes into the high."""
    slo = alo + blo  # uint32 wraps mod 2^32
    carry = (slo < blo).astype(jnp.uint32)
    return slo, ahi + bhi + carry


def neg64(lo, hi) -> Pair:
    return add64(~lo, ~hi, jnp.uint32(1), jnp.uint32(0))


def sub64(alo, ahi, blo, bhi) -> Pair:
    borrow = (alo < blo).astype(jnp.uint32)
    return alo - blo, ahi - bhi - borrow


def less64(alo, ahi, blo, bhi) -> jax.Array:
    """Signed ``a < b``: the high words as int32, then the low unsigned."""
    ahs, bhs = ahi.astype(jnp.int32), bhi.astype(jnp.int32)
    return (ahs < bhs) | ((ahs == bhs) & (alo < blo))


def equal64(alo, ahi, blo, bhi) -> jax.Array:
    return (alo == blo) & (ahi == bhi)


def mulhi_u32(a, b) -> jax.Array:
    """The high 32 bits of the UNSIGNED product of two uint32 words, in
    16-bit limbs: each partial product fits 32 bits, and the middle
    column's sum (three 16-bit terms and a carry) does too."""
    a0, a1 = a & _LOW16, a >> 16
    b0, b1 = b & _LOW16, b >> 16
    p01, p10 = a0 * b1, a1 * b0
    mid = ((a0 * b0) >> 16) + (p01 & _LOW16) + (p10 & _LOW16)
    return a1 * b1 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)


def mul32_32(a, b) -> Pair:
    """The exact signed 64-bit product of two int32 columns.  The
    unsigned high half, less ``b`` where ``a`` is negative and ``a``
    where ``b`` is (an operand read as unsigned is 2^32 too large)."""
    a, b = jnp.asarray(a).astype(jnp.int32), jnp.asarray(b).astype(jnp.int32)
    au, bu = a.astype(jnp.uint32), b.astype(jnp.uint32)
    hi = mulhi_u32(au, bu)
    hi = hi - jnp.where(a < 0, bu, jnp.uint32(0)) - jnp.where(b < 0, au, jnp.uint32(0))
    return au * bu, hi


def mul64_32(lo, hi, b) -> Pair:
    """``a * b`` modulo 2^64 for a signed pair and an int32 column."""
    b = jnp.asarray(b).astype(jnp.int32)
    bu = b.astype(jnp.uint32)
    out_hi = mulhi_u32(lo, bu) + hi * bu - jnp.where(b < 0, lo, jnp.uint32(0))
    return lo * bu, out_hi


def mul64(alo, ahi, blo, bhi) -> Pair:
    """``a * b`` modulo 2^64 (two's complement: the same bits signed or
    not)."""
    return alo * blo, mulhi_u32(alo, blo) + alo * bhi + ahi * blo


def pair_to_f32(lo, hi) -> jax.Array:
    """The f32 nearest a signed pair, but for one rounding of each
    half's conversion and one of their sum (a few parts in 2^24 of the
    value).  Of the MAGNITUDE, with the sign put back: the halves of a
    small negative number are -2^32 and nearly 2^32, and their f32 sum
    is 0."""
    negative = hi.astype(jnp.int32) < 0
    nlo, nhi = neg64(lo, hi)
    lo, hi = jnp.where(negative, nlo, lo), jnp.where(negative, nhi, hi)
    size = hi.astype(jnp.float32) * jnp.float32(4294967296.0) + lo.astype(jnp.float32)
    return jnp.where(negative, -size, size)


def tree_reduce(combine, identity: Pair, lo, hi) -> Pair:
    """One pair from a column of pairs under an associative, commutative
    ``combine(alo, ahi, blo, bhi)``: the column padded to a power of two
    with ``identity`` and halved ``log2 n`` times, the upper half onto
    the lower.  Each level is one elementwise pass over half the
    elements of the one before: a program of ``log2 n`` small fusions,
    where ``lax.associative_scan`` builds every prefix and compiled to
    no TPU program at all at 2^23 slots (``ops/segmented.py``)."""
    n = lo.shape[0]
    size = 1 << max(n - 1, 0).bit_length()
    if size != n:
        pad = [(0, size - n, 0)]
        lo = jax.lax.pad(lo, jnp.asarray(identity[0], lo.dtype), pad)
        hi = jax.lax.pad(hi, jnp.asarray(identity[1], hi.dtype), pad)
    while size > 1:
        size //= 2
        lo, hi = combine(lo[:size], hi[:size], lo[size:], hi[size:])
    return lo[0], hi[0]


# -- the DECIMAL value of a row function ------------------------------------------

Literal = Union[int, decimal.Decimal]


def _literal(value: Literal) -> Tuple[int, int]:
    """``(scaled integer, scale)`` of a Python literal.  A float is
    refused: 0.1 is not a tenth."""
    if isinstance(value, bool) or not isinstance(value, (int, decimal.Decimal)):
        raise TypeError(
            f"a DECIMAL combines with DECIMAL columns, ints and "
            f"decimal.Decimal literals, not {type(value).__name__}: a float "
            f"is not exact"
        )
    if isinstance(value, int):
        return value, 0
    sign, digits, exp = value.as_tuple()
    if not isinstance(exp, int):
        raise ValueError(f"not a finite decimal: {value!r}")
    whole = int("".join(map(str, digits)) or "0") * (-1 if sign else 1)
    return (whole, -exp) if exp < 0 else (whole * 10**exp, 0)


def _const(value: int, wide: bool) -> Tuple[jax.Array, ...]:
    """A scaled integer as the words of a scalar: narrow where asked and
    it fits an int32."""
    if not -(2**63) <= value < 2**63:
        raise OverflowError(f"{value} does not fit 64 bits")
    if not wide and -(2**31) <= value < 2**31:
        return (jnp.int32(value),)
    u = value & (2**64 - 1)
    return jnp.uint32(u & 0xFFFFFFFF), jnp.uint32(u >> 32)


@jax.tree_util.register_pytree_node_class
class Dec:
    """A DECIMAL column (or scalar) on the device: ``words`` is one
    int32 array (narrow, 32 bits) or a ``(lo, hi)`` uint32 pair (wide,
    64 bits) of scaled integers, ``scale`` the digits after the point.

    ``+`` ``-`` ``*`` and the comparisons take another :class:`Dec`, an
    ``int`` or a ``decimal.Decimal``.  A sum or difference has the
    larger scale of its terms and is wide if either is or if one had to
    be rescaled; a product has the sum of the scales and is ALWAYS wide
    (32 x 32 -> 64).  Nothing
    rounds and nothing checks a range: narrow arithmetic wraps modulo
    2^32 and wide modulo 2^64, so a value known to fit 32 bits is made
    narrow again with :meth:`narrow`, explicitly."""

    def __init__(self, words: Sequence, scale: int):
        self.words = tuple(words)
        self.scale = int(scale)
        if len(self.words) not in (1, 2):
            raise ValueError("a Dec is one int32 word or a (lo, hi) pair")

    def tree_flatten(self):
        return self.words, self.scale

    @classmethod
    def tree_unflatten(cls, scale, words):
        return cls(words, scale)

    @property
    def wide(self) -> bool:
        return len(self.words) == 2

    def __repr__(self) -> str:
        return f"Dec(scale={self.scale}, {'wide' if self.wide else 'narrow'})"

    # -- forms ---------------------------------------------------------------
    @_scoped
    def widen(self) -> "Dec":
        return self if self.wide else Dec(widen(self.words[0]), self.scale)

    @_scoped
    def narrow(self) -> "Dec":
        """The low word as an int32: the value, where it fits 32 bits."""
        if not self.wide:
            return self
        return Dec((self.words[0].astype(jnp.int32),), self.scale)

    @_scoped
    def to_f32(self) -> jax.Array:
        """The value in units, rounded to f32."""
        raw = (
            pair_to_f32(*self.words) if self.wide
            else self.words[0].astype(jnp.float32)
        )
        return raw / jnp.float32(10.0**self.scale)

    def _rescaled(self, scale: int) -> "Dec":
        if scale == self.scale:
            return self
        factor = 10 ** (scale - self.scale)
        if factor >= 2**31:
            raise OverflowError(f"cannot rescale by 10^{scale - self.scale}")
        if self.wide:
            return Dec(mul64_32(*self.words, jnp.int32(factor)), scale)
        # 32 x 32 -> 64: the rescaled value need not fit the narrow form
        return Dec(mul32_32(self.words[0], jnp.int32(factor)), scale)

    def _with(self, other) -> Tuple["Dec", "Dec"]:
        """Both operands at one scale and one width."""
        if not isinstance(other, Dec):
            value, scale = _literal(other)
            top = max(scale, self.scale)
            other = Dec(_const(value * 10 ** (top - scale), self.wide), top)
        scale = max(self.scale, other.scale)
        a, b = self._rescaled(scale), other._rescaled(scale)
        if a.wide != b.wide:
            a, b = a.widen(), b.widen()
        return a, b

    # -- arithmetic ------------------------------------------------------------
    @_scoped
    def __add__(self, other) -> "Dec":
        a, b = self._with(other)
        if a.wide:
            return Dec(add64(*a.words, *b.words), a.scale)
        return Dec((a.words[0] + b.words[0],), a.scale)

    __radd__ = __add__

    @_scoped
    def __sub__(self, other) -> "Dec":
        a, b = self._with(other)
        if a.wide:
            return Dec(sub64(*a.words, *b.words), a.scale)
        return Dec((a.words[0] - b.words[0],), a.scale)

    @_scoped
    def __rsub__(self, other) -> "Dec":
        a, b = self._with(other)
        return b - a

    @_scoped
    def __neg__(self) -> "Dec":
        if self.wide:
            return Dec(neg64(*self.words), self.scale)
        return Dec((-self.words[0],), self.scale)

    @_scoped
    def __mul__(self, other) -> "Dec":
        if not isinstance(other, Dec):
            value, scale = _literal(other)
            other = Dec(_const(value, False), scale)
        scale = self.scale + other.scale
        a, b = (self, other) if self.wide or not other.wide else (other, self)
        if not a.wide:
            return Dec(mul32_32(a.words[0], b.words[0]), scale)
        if not b.wide:
            return Dec(mul64_32(*a.words, b.words[0]), scale)
        return Dec(mul64(*a.words, *b.words), scale)

    __rmul__ = __mul__

    # -- comparisons -------------------------------------------------------------
    @_scoped
    def _less(self, other, swap: bool = False) -> jax.Array:
        a, b = self._with(other)
        if swap:
            a, b = b, a
        if a.wide:
            return less64(*a.words, *b.words)
        return a.words[0] < b.words[0]

    def __lt__(self, other):
        return self._less(other)

    def __gt__(self, other):
        return self._less(other, swap=True)

    def __le__(self, other):
        return ~self._less(other, swap=True)

    def __ge__(self, other):
        return ~self._less(other)

    @_scoped
    def __eq__(self, other):  # noqa: D105 - elementwise, as an array's
        a, b = self._with(other)
        if a.wide:
            return equal64(*a.words, *b.words)
        return a.words[0] == b.words[0]

    def __ne__(self, other):
        return ~(self == other)

    __hash__ = None


# -- between a kernel's physical columns and a row function's --------------------

def wrap(cols: Dict, decimals: Sequence) -> Dict:
    """The physical columns of a batch as a row function sees them: each
    DECIMAL field of ``decimals`` (``columnar/schema.py::Field``) one
    :class:`Dec` under its logical name, every other column as it is."""
    out = dict(cols)
    for f in decimals:
        words = [out.pop(n) for n in f.device_names]
        if f.ctype.wide:
            out[f.name] = Dec([_u32(w) for w in words], f.ctype.scale)
        else:
            out[f.name] = Dec((jnp.asarray(words[0]).astype(jnp.int32),), f.ctype.scale)
    return out


def unwrap(cols: Dict) -> Dict:
    """What a row function returned as physical columns: a :class:`Dec`
    becomes its word or its ``#h0`` / ``#h1`` pair."""
    out = {}
    for name, value in cols.items():
        if isinstance(value, Dec):
            names = device_column_names(name, DecimalType(value.scale, value.wide))
            out.update(zip(names, value.words))
        else:
            out[name] = value
    return out
