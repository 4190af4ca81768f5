"""Query — the lazy table handle and operator surface.

The analog of ``DryadLinqQuery<T>`` + the ``DryadLinqQueryable``
extension-method surface (``LinqToDryad/DryadLinqQuery.cs:299``,
``DryadLinqQueryable.cs:39``): a Query wraps a logical plan node;
operators build new nodes; ``collect``/``submit`` trigger lowering and
execution through the context.  Operator parity map (reference op ->
here): Select->select, Where->where, SelectMany->select_many,
GroupBy->group_by, Join/GroupJoin->join/group_join_count,
OrderBy/ThenBy->order_by, Distinct->distinct, Concat->concat,
Union/Intersect/Except->union/intersect/except_, HashPartition->
hash_partition, RangePartition->range_partition, Apply/
ApplyPerPartition->apply, ApplyWithPartitionIndex->apply(with_index),
Fork->fork, DoWhile->do_while, Take->take, Count/Sum/Min/Max/Average->
count/sum_/min_/max_/mean (+ *_as_query lazy forms), Zip->zip_,
SlidingWindow->sliding_window, Assume{Hash,Range}Partition->
assume_hash_partition/assume_range_partition, ToStore/Submit->
to_store/submit/collect.
"""

from __future__ import annotations

import operator
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from dryad_tpu.api.decomposable import Decomposable
from dryad_tpu.columnar.schema import ColumnType, DecimalType, Schema
from dryad_tpu.ops import wide
from dryad_tpu.plan import infer
from dryad_tpu.plan.nodes import Node, PartitionInfo

KeyArg = Union[str, Sequence[str]]
OrderArg = Union[str, Tuple[str, Union[bool, str]]]  # bool True / "desc" = descending

JOIN_STRATEGIES = ("shuffle", "broadcast", "auto")


def _check_strategy(strategy: str) -> None:
    if strategy not in JOIN_STRATEGIES:
        raise ValueError(
            f"unknown join strategy {strategy!r}; expected one of {JOIN_STRATEGIES}"
        )

def _sum_type(ct):
    """A sum keeps its column's type, but a DECIMAL's is always wide:
    a column of money fits 32 bits, its total does not (SQL's SUM of a
    DECIMAL(p, s) is a DECIMAL(38, s) for the same reason)."""
    return DecimalType(ct.scale, wide=True) if isinstance(ct, DecimalType) else ct


def _agg_type(op: str, col: Optional[str], ct):
    """The type of ``op`` over a column of type ``ct``, for both
    engines: days do not add up."""
    if ct is ColumnType.DATE and op in ("sum", "mean"):
        raise ValueError(f"aggregate {op!r} unsupported on DATE column {col!r}")
    return _AGG_TYPE_RULES[op](ct)


_AGG_TYPE_RULES = {
    "count": lambda ct: ColumnType.INT32,
    "sum": _sum_type,
    "min": lambda ct: ct,
    "max": lambda ct: ct,
    "first": lambda ct: ct,
    "mean": lambda ct: ColumnType.FLOAT32,
    "any": lambda ct: ColumnType.BOOL,
    "all": lambda ct: ColumnType.BOOL,
}


def _keys(k: KeyArg) -> List[str]:
    return [k] if isinstance(k, str) else list(k)


def _order_keys(keys: Sequence[OrderArg]) -> List[Tuple[str, bool]]:
    out: List[Tuple[str, bool]] = []
    for k in keys:
        if isinstance(k, str):
            out.append((k, False))
            continue
        name, d = k[0], k[1]
        # accept "asc"/"desc" strings: a bare bool(...) would read the
        # truthy string "asc" as DESCENDING — a silent wrong order.
        if isinstance(d, str):
            if d not in ("asc", "desc"):
                raise ValueError(
                    f"order direction for {name!r} must be 'asc', 'desc' "
                    f"or a bool (True=descending), got {d!r}")
            d = d == "desc"
        out.append((name, bool(d)))
    return out


class _Project:
    """Name-projection row fn: picklable for job packages, VALUE-equal
    so re-lowering a rebuilt query hits the compiled-stage cache."""

    def __init__(self, phys: List[str]):
        self.phys = tuple(phys)

    def __eq__(self, other) -> bool:
        return type(other) is _Project and other.phys == self.phys

    def __hash__(self) -> int:
        return hash(("_Project", self.phys))

    def __call__(self, cols: Dict) -> Dict:
        return {c: cols[c] for c in self.phys}


class _Typed:
    """A row function over LOGICAL columns, as a kernel calls it over
    physical ones: each DECIMAL column of the input reaches ``fn`` as
    one ``ops/wide.py::Dec`` under its logical name (scaled integers
    whose scale the engine knows: ``price * (1 - discount)`` is exact
    and comes out a DECIMAL), every other column as the device holds it
    (a DATE as int32 days: compare it with ``dryad_tpu.date(...)``);
    a ``Dec`` the function returns becomes its physical word or pair.
    ``select`` and ``where`` wrap their function in one where the input
    has a DECIMAL column, and only there, so every other plan is what
    it was.  Picklable if ``fn`` is, and VALUE-equal so re-lowering a
    rebuilt query hits the compiled-stage cache."""

    def __init__(self, fn, schema: Schema):
        self.fn = fn
        self.decimals = tuple(
            f for f in schema.fields if isinstance(f.ctype, DecimalType)
        )

    def __eq__(self, other) -> bool:
        return (
            type(other) is _Typed and other.fn == self.fn
            and other.decimals == self.decimals
        )

    def __hash__(self) -> int:
        return hash(("_Typed", self.fn, self.decimals))

    def logical(self, cols: Dict):
        """``fn``'s own answer: what schema inference reads the DECIMAL
        types from."""
        return self.fn(wide.wrap(cols, self.decimals))

    def __call__(self, cols: Dict):
        out = self.logical(cols)
        return wide.unwrap(out) if isinstance(out, dict) else out


def _typed(fn, schema: Schema):
    has_decimal = any(isinstance(f.ctype, DecimalType) for f in schema.fields)
    return _Typed(fn, schema) if has_decimal else fn


_VOCAB_PRESERVING = frozenset({
    "where", "take", "skip", "tail", "reverse", "order_by",
    "hash_partition", "range_partition", "assume_partition", "tee",
    "with_rank", "take_while", "skip_while", "distinct",
})


def static_str_vocab(node, col):
    """Static hash-vocabulary bound for a STRING column, walked back to
    ingest through value-preserving nodes (the string twin of the
    INT32 range walk): the union of the reaching ingests' per-column
    hash sets, or None when something could fabricate values
    (select/apply/join/default_if_empty).  Shared by the API gate and
    the lowering's subset-table build."""
    import numpy as np

    if node.kind == "input":
        return (node.params.get("str_vocab") or {}).get(col)
    if node.kind == "concat":
        vs = [static_str_vocab(i, col) for i in node.inputs]
        if any(v is None for v in vs):
            return None
        return np.unique(np.concatenate(vs)) if vs else None
    if node.kind == "select" and isinstance(node.params.get("fn"), _Project):
        return static_str_vocab(node.inputs[0], col)
    if node.kind in _VOCAB_PRESERVING and node.inputs:
        return static_str_vocab(node.inputs[0], col)
    return None


class Query:
    """Lazy distributed table: a logical plan node plus its context."""

    def __init__(self, ctx, node: Node):
        self.ctx = ctx
        self.node = node

    @property
    def schema(self) -> Schema:
        return self.node.schema

    def _require_cols(self, names: Sequence[str], where: str = "") -> None:
        missing = [n for n in names if n not in self.schema]
        if missing:
            raise ValueError(
                f"unknown column(s) {missing} {where}; have {self.schema.names}"
            )

    # -- row-wise operators -----------------------------------------------
    def select(self, fn: Callable[[Dict], Dict], schema: Optional[Schema] = None) -> "Query":
        """Projection/map over physical columns (reference Select).

        Partition metadata is dropped: ``fn`` may rewrite key *values*
        even when the key *name* survives, which would make shuffle
        elision silently wrong.  Use ``project`` (name-only projection)
        or ``assume_*_partition`` to retain metadata.

        A DECIMAL column reaches ``fn`` (and ``where``'s) as one exact
        value under its logical name, not as physical words
        (:class:`_Typed`), and an expression over such values is a
        DECIMAL column of the output with the scale the arithmetic
        gives; a DATE is its int32 days and stays a DATE under its name.
        """
        fn = _typed(fn, self.schema)
        out_schema = schema or infer.infer_select_schema(self.schema, fn)
        node = Node("select", [self.node], out_schema, PartitionInfo(), fn=fn)
        return Query(self.ctx, node)

    def project(self, names: KeyArg) -> "Query":
        """Column projection by name."""
        names = _keys(names)
        out_schema = self.schema.select(names)
        # a picklable callable (not a closure): projections must survive
        # job packaging (exec.jobpackage)
        fn = _Project(out_schema.device_names())
        keep = self.node.partition
        if keep.keys and not all(k in out_schema for k in keep.keys):
            keep = PartitionInfo()
        return Query(self.ctx, Node("select", [self.node], out_schema, keep, fn=fn))

    def where(self, fn: Callable[[Dict], Any]) -> "Query":
        fn = _typed(fn, self.schema)
        node = Node("where", [self.node], self.schema, self.node.partition, fn=fn)
        return Query(self.ctx, node)

    def select_many(
        self,
        fn: Callable[[Dict], Tuple[Dict, Any]],
        factor: int,
        schema: Optional[Schema] = None,
    ) -> "Query":
        """Flat-map: fn maps each row to ``factor`` rows.

        fn(cols) -> (out_cols each shaped (n, factor, ...), valid (n, factor)).
        """
        out_schema = schema or infer.infer_select_many_schema(self.schema, fn, factor)
        node = Node(
            "select_many", [self.node], out_schema, PartitionInfo(),
            fn=fn, factor=int(factor),
        )
        return Query(self.ctx, node)

    # -- grouping / aggregation -------------------------------------------
    def group_by(
        self,
        keys: KeyArg,
        aggs: Optional[Dict[str, Tuple[str, Optional[str]]]] = None,
        decomposable: Optional[Decomposable] = None,
        dense: Optional[int] = None,
        salt: Optional[int] = None,
    ) -> "Query":
        """GroupBy with builtin aggregates or a Decomposable.

        ``aggs``: out_name -> (op, col) with op in
        sum|count|min|max|mean|first|any|all (col None for count).
        int64 columns aggregate exactly with 64-bit arithmetic;
        sum/mean WRAP mod 2^64 when a group's true total exceeds the
        int64 range (numpy int64 semantics — C# long Average instead
        throws OverflowException there).  float64 supports
        min/max/first (totalOrder); cast to float32 for sums.
        A DECIMAL column's ``sum`` is a wide (64-bit) DECIMAL of the
        same scale, exact modulo 2^64 whichever width the column has,
        its ``min`` / ``max`` / ``first`` keep its type and its ``mean``
        is an f32 in units (the exact sum, one f32 division by the count
        and one by 10^scale); a DATE takes ``min`` / ``max`` / ``first``.

        **Which path a group-by takes.**  A sum that has to be exact
        (INT64, DECIMAL), more than one key, or a key that is not a
        bounded INT32 / a dictionary STRING goes down the SORT path:
        the rows sorted by key with their columns carried, a segmented
        scan whose 64-bit channels add with carry
        (``ops/segmented.py``, ``ops/wide.py``), one compaction; cost
        grows with the rows, not with the groups, so four groups cost
        what four million do.  The dense MXU path below (``dense=K``,
        or picked by the engine for ONE bounded INT32 / STRING key over
        plain 32-bit columns) sums in f32 and refuses split and DECIMAL
        columns: exact money goes down the sort path today, however
        few the groups.

        ``salt=S`` spreads each key over S shuffle destinations
        (partial-reduce on (key, salt), exchange, reduce, then exchange
        on the key alone) — the skew escape hatch for heavy-hitter keys,
        the analog of the reference's data-size-driven hash
        redistribution (``DrDynamicDistributor.h:26,79``).  Costs a
        second shuffle.  Builtin aggregates only.  A ``group_by``
        combines on the chip BEFORE it exchanges (``plan/lower.py``), so
        however hot a key is it crosses the mesh as one row a chip, and
        what a destination receives is its share of the distinct keys:
        a combine-first group-by that fits its chips cannot overflow a
        bucket by skew, and ``salt`` buys it nothing.  It can matter
        only where the combined rows bound for one destination would
        not fit there (more distinct keys a destination than
        ``shuffle_slack`` allows for).

        ``dense=K`` declares the single INT32 key lies in [0, K): the
        engine then skips the sort+shuffle pipeline and reduces on the
        MXU via one-hot matmul buckets (Pallas kernel on TPU) followed
        by one ``psum_scatter`` — the aggregation-tree fast path.  Only
        sum/count/mean aggregates; rows with keys outside [0, K) are
        dropped.  Output is range-partitioned and ordered by the key.

        Dense-path precision: counts are exact at any row count (the
        kernel folds a partition in blocks of 2^24 rows, int32 between
        blocks and across the mesh).  SUM columns
        accumulate on the MXU via split-bf16 terms
        (``ops/pallas_bucket.py``): integer values use 3 terms and stay
        EXACT up to 2^24 per value (totals still accumulate in f32, so
        an integer sum loses exactness once a per-bucket total exceeds
        2^24 — use the default sort-based path when exact large integer
        sums matter); float values use 2 terms (~2^-16 per-element
        representation error, amplified by cancellation in near-zero
        groups).
        """
        keys = _keys(keys)
        if salt is not None:
            if salt < 2:
                raise ValueError("salt must be >= 2")
            if dense is not None or decomposable is not None:
                raise ValueError("salt applies to builtin-agg group_by only")
        if dense is not None:
            if decomposable is not None:
                raise ValueError("dense group_by takes builtin aggs only")
            if len(keys) != 1:
                raise ValueError("dense group_by requires exactly one key")
            if self.schema.field(keys[0]).ctype != ColumnType.INT32:
                raise ValueError("dense group_by key must be INT32")
            if dense < 1:
                raise ValueError("dense bucket count must be >= 1")
            bad = [
                op for op, _c, _o in (
                    (op, c, o) for o, (op, c) in (aggs or {}).items()
                ) if op not in ("sum", "count", "mean")
            ]
            if not aggs:
                raise ValueError("group_by needs aggs")
            if bad:
                raise ValueError(
                    f"dense group_by supports sum/count/mean, got {bad}"
                )
            exact = [
                c for _o, (_op, c) in aggs.items()
                if c is not None and (
                    self.schema.field(c).ctype.is_split
                    or isinstance(self.schema.field(c).ctype, DecimalType)
                )
            ]
            if exact:
                raise ValueError(
                    f"dense group_by aggregates f32 on the MXU; columns "
                    f"{exact} are 64-bit/split or DECIMAL types — use the "
                    f"default sort-based path"
                )
        fields: List[Tuple[str, ColumnType]] = [
            (k, self.schema.field(k).ctype) for k in keys
        ]
        if decomposable is not None:
            fields += list(decomposable.out_fields)
            node = Node(
                "group_by", [self.node], Schema(fields),
                PartitionInfo.hashed(keys), keys=keys, decomposable=decomposable,
            )
            return Query(self.ctx, node)
        if not aggs:
            raise ValueError("group_by needs aggs or a decomposable")
        agg_list = []
        for out_name, (op, col) in aggs.items():
            if op not in _AGG_TYPE_RULES:
                raise ValueError(f"unknown aggregate {op!r}")
            ct = self.schema.field(col).ctype if col is not None else ColumnType.INT32
            fields.append((out_name, _agg_type(op, col, ct)))
            agg_list.append((op, col, out_name))
        if dense is not None:
            part = PartitionInfo.ranged(
                [(keys[0], False)], ordered=[(keys[0], False)]
            )
            node = Node(
                "group_by", [self.node], Schema(fields), part,
                keys=keys, aggs=agg_list, dense=int(dense),
            )
        elif (k_int := self._auto_dense_int(keys, agg_list, salt)) is not None:
            # int auto-dense: ingest-bounded [0, K) key domain rides the
            # MXU bucket path with a range-miss guard (sort/shuffle
            # path and its 12x-slower segmented reduce skipped entirely)
            part = PartitionInfo.ranged(
                [(keys[0], False)], ordered=[(keys[0], False)]
            )
            node = Node(
                "group_by", [self.node], Schema(fields), part,
                keys=keys, aggs=agg_list, dense=k_int, guard_range=True,
            )
        else:
            auto = self._auto_dense_eligible(keys, agg_list, salt)
            # The auto-dense path physically partitions output by
            # dictionary CODE range, which matches neither a hash nor a
            # key-order range claim — so the node claims NOTHING and
            # downstream consumers re-exchange (a stale hashed claim
            # would elide a join's left exchange and drop matches).
            part = (
                PartitionInfo() if auto else PartitionInfo.hashed(keys)
            )
            node = Node(
                "group_by", [self.node], Schema(fields), part,
                keys=keys, aggs=agg_list, salt=salt, auto_dense=auto,
            )
        return Query(self.ctx, node)

    # node kinds that pass column VALUES through unchanged, so an
    # ingest-time range bound on a column still holds at their output.
    # default_if_empty is NOT here: its defaults dict can fabricate a
    # key outside the ingest range (code-review r4).
    _VALUE_PRESERVING = frozenset({
        "where", "take", "skip", "tail", "reverse",
        "order_by", "hash_partition", "range_partition",
        "assume_partition", "tee", "with_rank", "take_while",
        "skip_while", "distinct",
    })

    def _int_key_range(self, node, col) -> Optional[Tuple[int, int]]:
        """Static (min, max) bound for an INT32 column, walked back to
        ingest through value-preserving nodes only (select/apply/join
        may fabricate values, so they break the bound; project() lowers
        to a "select" with a recognizable name-only _Project fn)."""
        if node.kind == "input":
            return (node.params.get("col_stats") or {}).get(col)
        if node.kind == "concat":
            rs = [self._int_key_range(i, col) for i in node.inputs]
            if any(r is None for r in rs):
                return None
            return (min(r[0] for r in rs), max(r[1] for r in rs))
        if node.kind == "select" and isinstance(
            node.params.get("fn"), _Project
        ):
            return self._int_key_range(node.inputs[0], col)
        if node.kind in self._VALUE_PRESERVING and node.inputs:
            return self._int_key_range(node.inputs[0], col)
        return None

    def _auto_dense_int(self, keys, agg_list, salt) -> Optional[int]:
        """Int auto-dense gate (the integer twin of the STRING rewrite):
        a plain group_by over ONE INT32 key whose ingest-time range is
        [0, K) with K <= auto_dense_limit rides the MXU bucket path —
        no sort, no shuffle.  Returns K or None.  Unlike the explicit
        ``dense=`` API (which documents dropping out-of-range rows),
        this rewrite adds a range-miss guard: values fabricated after
        ingest fail loudly instead of silently vanishing."""
        cfg = self.ctx.config
        if salt or not getattr(cfg, "auto_dense_ints", True):
            return None
        if len(keys) != 1:
            return None
        if self.schema.field(keys[0]).ctype is not ColumnType.INT32:
            return None
        plain = (
            ColumnType.INT32, ColumnType.UINT32,
            ColumnType.FLOAT32, ColumnType.BOOL,
        )
        for op, col, _name in agg_list:
            if op not in ("sum", "count", "mean"):
                return None
            if col is not None and self.schema.field(col).ctype not in plain:
                return None
        rng = self._int_key_range(self.node, keys[0])
        limit = getattr(cfg, "auto_dense_limit", 1 << 17)
        # 0-based domains only (the common categorical-code shape);
        # negative or offset ranges keep the sort path
        if rng is None or rng[0] < 0 or rng[1] + 1 > limit:
            return None
        return rng[1] + 1

    def _auto_dense_eligible(self, keys, agg_list, salt) -> bool:
        """Build-time gate for the auto-dense STRING group_by lowering
        (``plan/lower.py`` re-checks at lowering; a vocabulary grown
        past the limit falls back to the sort path, which the
        claim-free partition metadata keeps correct).

        The vocabulary bound is PER-INGEST when provenance allows
        (``static_str_vocab``): a context that once ingested a huge
        unrelated vocabulary no longer disables the fast path for every
        later query — only the key column's own domain matters (and the
        coding tables shrink to it)."""
        cfg = self.ctx.config
        if salt or not getattr(cfg, "auto_dense_strings", True):
            return False
        d = getattr(self.ctx, "dictionary", None)
        limit = getattr(cfg, "auto_dense_limit", 1 << 17)
        if d is None or len(d) == 0:
            return False
        if len(keys) != 1:
            return False
        vocab = static_str_vocab(self.node, keys[0])
        bound = len(vocab) if vocab is not None else len(d)
        if not 0 < bound <= limit:
            return False
        if self.schema.field(keys[0]).ctype is not ColumnType.STRING:
            return False
        plain = (
            ColumnType.INT32, ColumnType.UINT32,
            ColumnType.FLOAT32, ColumnType.BOOL,
        )
        for op, col, _name in agg_list:
            if op not in ("sum", "count", "mean"):
                return False
            if col is not None and self.schema.field(col).ctype not in plain:
                return False
        return True

    def distinct(self, keys: Optional[KeyArg] = None) -> "Query":
        keys = _keys(keys) if keys is not None else self.schema.names
        # Distinct over exactly one STRING column (the whole schema) is
        # the vocabulary query — the auto-dense rewrite computes it as a
        # shuffle-free bucket count>0 + decode; like auto-dense group_by
        # the output is code-range partitioned, so the node claims
        # nothing (see _auto_dense_eligible).
        auto = (
            self.schema.names == list(keys)
            and self._auto_dense_eligible(keys, [("count", None, "#c")], None)
        )
        node = Node(
            "distinct", [self.node], self.schema,
            PartitionInfo() if auto else PartitionInfo.hashed(keys),
            keys=keys, auto_dense=auto,
        )
        return Query(self.ctx, node)

    # -- joins --------------------------------------------------------------
    def _join_partition_info(self, lk: List[str], strategy: str) -> PartitionInfo:
        """Output placement depends on strategy: a broadcast join leaves
        the left side where it is; a shuffle join co-hash-partitions;
        'auto' is decided at trace time, so nothing can be assumed."""
        if strategy == "broadcast":
            return self.node.partition
        if strategy == "auto":
            return PartitionInfo()
        return PartitionInfo.hashed(lk)

    def join(
        self,
        other: "Query",
        left_keys: KeyArg,
        right_keys: Optional[KeyArg] = None,
        expansion: float = 4.0,
        suffix: str = "_r",
        strategy: str = "auto",
    ) -> "Query":
        """Inner equi-join (reference Join): co-hash-partition + local
        join, or replicate a small right side (``strategy`` in
        shuffle|broadcast|auto; broadcast is the
        ``DrDynamicBroadcastManager`` copy-tree as one ``all_gather``)."""
        _check_strategy(strategy)
        lk = _keys(left_keys)
        rk = _keys(right_keys) if right_keys is not None else lk
        self._require_cols(lk, "in join left keys")
        other._require_cols(rk, "in join right keys")
        fields = [(f.name, f.ctype) for f in self.schema.fields]
        lnames = {f.name for f in self.schema.fields}
        for f in other.schema.fields:
            if f.name in rk:
                continue
            name = f.name if f.name not in lnames else f"{f.name}{suffix}"
            fields.append((name, f.ctype))
        node = Node(
            "join", [self.node, other.node], Schema(fields),
            self._join_partition_info(lk, strategy),
            left_keys=lk, right_keys=rk, join_kind="inner",
            expansion=expansion, suffix=suffix, strategy=strategy,
        )
        return Query(self.ctx, node)

    def semi_join(
        self, other: "Query", left_keys: KeyArg,
        right_keys: Optional[KeyArg] = None, expansion: float = 4.0,
        strategy: str = "auto",
    ) -> "Query":
        return self._semi(other, left_keys, right_keys, expansion, False, strategy)

    def anti_join(
        self, other: "Query", left_keys: KeyArg,
        right_keys: Optional[KeyArg] = None, expansion: float = 4.0,
        strategy: str = "auto",
    ) -> "Query":
        return self._semi(other, left_keys, right_keys, expansion, True, strategy)

    def _semi(self, other, left_keys, right_keys, expansion, anti, strategy="shuffle") -> "Query":
        _check_strategy(strategy)
        lk = _keys(left_keys)
        rk = _keys(right_keys) if right_keys is not None else lk
        self._require_cols(lk, "in join left keys")
        other._require_cols(rk, "in join right keys")
        node = Node(
            "join", [self.node, other.node], self.schema,
            self._join_partition_info(lk, strategy),
            left_keys=lk, right_keys=rk,
            join_kind="anti" if anti else "semi", expansion=expansion,
            strategy=strategy,
        )
        return Query(self.ctx, node)

    # -- set operations (reference Union/Intersect/Except) -------------------
    def concat(self, *others: "Query") -> "Query":
        for o in others:
            if o.schema.names != self.schema.names:
                raise ValueError("concat requires identical schemas")
        node = Node(
            "concat", [self.node] + [o.node for o in others], self.schema,
            PartitionInfo(),
        )
        return Query(self.ctx, node)

    def union(self, other: "Query") -> "Query":
        return self.concat(other).distinct()

    def intersect(self, other: "Query") -> "Query":
        return self.distinct().semi_join(other, self.schema.names)

    def except_(self, other: "Query") -> "Query":
        return self.distinct().anti_join(other, self.schema.names)

    # -- partitioning -------------------------------------------------------
    def hash_partition(self, keys: KeyArg) -> "Query":
        keys = _keys(keys)
        node = Node(
            "hash_partition", [self.node], self.schema,
            PartitionInfo.hashed(keys), keys=keys,
        )
        return Query(self.ctx, node)

    def range_partition(self, keys: KeyArg) -> "Query":
        ks = _order_keys(_keys(keys))
        self._require_cols([n for n, _ in ks], "in range_partition")
        node = Node(
            "range_partition", [self.node], self.schema,
            PartitionInfo.ranged(ks), keys=ks,
        )
        return Query(self.ctx, node)

    def assume_hash_partition(self, keys: KeyArg) -> "Query":
        node = Node(
            "assume_partition", [self.node], self.schema,
            PartitionInfo.hashed(_keys(keys)),
        )
        return Query(self.ctx, node)

    def assume_range_partition(self, keys: KeyArg) -> "Query":
        node = Node(
            "assume_partition", [self.node], self.schema,
            PartitionInfo.ranged(_order_keys(_keys(keys))),
        )
        return Query(self.ctx, node)

    def assume_order_by(self, keys: Sequence[OrderArg]) -> "Query":
        ks = _order_keys(keys)
        node = Node(
            "assume_partition", [self.node], self.schema,
            PartitionInfo.ranged(ks, ks),
        )
        return Query(self.ctx, node)

    # -- ordering -----------------------------------------------------------
    def order_by(self, keys: Sequence[OrderArg]) -> "Query":
        """Global sort: range partition + local sort (reference
        OrderBy/ThenBy chain collapses into one keys list)."""
        ks = _order_keys(keys)
        self._require_cols([n for n, _ in ks], "in order_by")
        node = Node(
            "order_by", [self.node], self.schema,
            # spread: the skew-proof exchange may split equal keys
            # across a partition boundary (plan/nodes.py PartitionInfo)
            PartitionInfo.ranged(ks, ks, spread=True), keys=ks,
        )
        return Query(self.ctx, node)

    def with_rank(self, out: str = "rank") -> "Query":
        """Attach each row's global engine-order position as an INT32
        column — the indexed-operator primitive (reference LongSelect /
        indexed Select/Where overloads): ``q.with_rank().select(...)``
        gives every row its index."""
        if out in self.schema.names:
            raise ValueError(f"column {out!r} already exists")
        node = Node(
            "with_rank", [self.node],
            self.schema.with_field(out, ColumnType.INT32),
            self.node.partition, out=out,
        )
        return Query(self.ctx, node)

    def take(self, n: int) -> "Query":
        # LINQ Take clamps negative counts to an empty sequence; the
        # kernel compares uint32 ranks, so a raw negative would wrap.
        node = Node(
            "take", [self.node], self.schema, self.node.partition,
            n=max(0, int(n)),
        )
        return Query(self.ctx, node)

    def skip(self, n: int) -> "Query":
        """Drop the first n rows of global engine order (reference Skip)."""
        node = Node(
            "skip", [self.node], self.schema, self.node.partition,
            n=max(0, int(n)),
        )
        return Query(self.ctx, node)

    def tail(self, n: int) -> "Query":
        """Keep the last n rows of global engine order (the Last /
        TakeLast shape of the reference dispatch)."""
        node = Node(
            "tail", [self.node], self.schema, self.node.partition,
            n=max(0, int(n)),
        )
        return Query(self.ctx, node)

    def take_while(self, fn: Callable[[Dict], Any]) -> "Query":
        """Rows strictly before the first predicate failure in global
        engine order (reference TakeWhile)."""
        node = Node(
            "take_while", [self.node], self.schema, self.node.partition, fn=fn
        )
        return Query(self.ctx, node)

    def skip_while(self, fn: Callable[[Dict], Any]) -> "Query":
        """Rows from the first predicate failure onward (SkipWhile)."""
        node = Node(
            "skip_while", [self.node], self.schema, self.node.partition, fn=fn
        )
        return Query(self.ctx, node)

    def reverse(self) -> "Query":
        """Globally reverse row order (reference Reverse,
        ``DryadLinqQueryGen.cs:2731``)."""
        node = Node("reverse", [self.node], self.schema, PartitionInfo())
        return Query(self.ctx, node)

    def default_if_empty(self, defaults: Optional[Dict[str, Any]] = None) -> "Query":
        """If empty, a single default row (reference DefaultIfEmpty).

        ``defaults``: logical column -> value; unlisted columns default
        to zero / empty string."""
        # The default row materializes on partition 0, which breaks any
        # inherited hash/range placement — downstream shuffles must not
        # be elided.
        node = Node(
            "default_if_empty", [self.node], self.schema, PartitionInfo(),
            defaults=self._physical_row(defaults or {}),
        )
        return Query(self.ctx, node)

    def of_type(self, tag_col: str, value: Any) -> "Query":
        """Keep rows whose type-tag column equals ``value`` (reference
        OfType; a columnar engine models subtype unions as a tag
        column, so OfType is tag equality)."""
        self._require_cols([tag_col], "in of_type")
        f = self.schema.field(tag_col)
        if f.ctype.is_split:
            phys = self._physical_row({tag_col: value})
            words = [(n, phys[n]) for n in f.identity_names]

            def fn(cols):
                same = cols[words[0][0]] == words[0][1]
                for n, w in words[1:]:
                    same = same & (cols[n] == w)
                return same
        else:
            def fn(cols):
                return cols[tag_col] == value
        return self.where(fn)

    # -- element access (eager, reference First/Last/Single/ElementAt) ------
    def _one_row(self, q: "Query") -> Optional[Dict[str, Any]]:
        table = q.collect()
        n = len(next(iter(table.values()), []))
        if n == 0:
            return None
        return {k: v[0] if np.asarray(v).ndim else v for k, v in table.items()}

    def first(self) -> Dict[str, Any]:
        row = self._one_row(self.take(1))
        if row is None:
            raise ValueError("first() on an empty sequence")
        return row

    def first_or_default(self) -> Optional[Dict[str, Any]]:
        return self._one_row(self.take(1))

    def last(self) -> Dict[str, Any]:
        row = self._one_row(self.tail(1))
        if row is None:
            raise ValueError("last() on an empty sequence")
        return row

    def last_or_default(self) -> Optional[Dict[str, Any]]:
        return self._one_row(self.tail(1))

    def single(self) -> Dict[str, Any]:
        table = self.take(2).collect()
        n = len(next(iter(table.values()), []))
        if n == 0:
            raise ValueError("single() on an empty sequence")
        if n > 1:
            raise ValueError("single() on a sequence with more than one row")
        return {k: v[0] for k, v in table.items()}

    def single_or_default(self) -> Optional[Dict[str, Any]]:
        table = self.take(2).collect()
        n = len(next(iter(table.values()), []))
        if n > 1:
            raise ValueError("single_or_default() on a sequence with more than one row")
        return {k: v[0] for k, v in table.items()} if n else None

    def element_at(self, n: int) -> Dict[str, Any]:
        if n < 0:
            raise IndexError(f"element_at({n}) out of range")
        row = self._one_row(self.skip(n).take(1))
        if row is None:
            raise IndexError(f"element_at({n}) out of range")
        return row

    def element_at_or_default(self, n: int) -> Optional[Dict[str, Any]]:
        if n < 0:
            return None
        return self._one_row(self.skip(n).take(1))

    def contains(self, row: Dict[str, Any]) -> bool:
        """Whole-row membership (reference Contains)."""
        if set(row) != set(self.schema.names):
            raise ValueError(
                f"contains() row must bind every column {self.schema.names}"
            )
        arrays = {k: np.asarray([v]) for k, v in row.items()}
        one = self.ctx.from_arrays(arrays, schema=self.schema)
        # One-row probe: broadcast it instead of shuffling the table.
        return (
            self.semi_join(one, self.schema.names, strategy="broadcast").count()
            > 0
        )

    def sequence_equal(self, other: "Query") -> bool:
        """Element-wise equality of two sequences in global engine order
        (reference SequenceEqual)."""
        if [
            (f.name, f.ctype) for f in self.schema.fields
        ] != [(f.name, f.ctype) for f in other.schema.fields]:
            return False
        n1, n2 = self.count(), other.count()
        if n1 != n2:
            return False
        if n1 == 0:
            return True
        from dryad_tpu.ops.join import _suffixed
        from dryad_tpu.plan import keys as K

        suffix = "__sq"
        z = self.zip_(other, suffix=suffix)
        lcols = K.equality_cols(self.schema, self.schema.names)
        rcols = [_suffixed(c, suffix) for c in lcols]

        def fn(cols):
            m = None
            for l, r in zip(lcols, rcols):
                e = cols[l] == cols[r]
                m = e if m is None else (m & e)
            return {"eq": m}

        eq = z.select(fn, schema=Schema([("eq", ColumnType.BOOL)]))
        return bool(eq.all_("eq"))

    # -- outer joins / group-join --------------------------------------------
    def left_join(
        self,
        other: "Query",
        left_keys: KeyArg,
        right_keys: Optional[KeyArg] = None,
        right_defaults: Optional[Dict[str, Any]] = None,
        expansion: float = 4.0,
        suffix: str = "_r",
        strategy: str = "auto",
    ) -> "Query":
        """Left-outer equi-join: unmatched left rows survive with
        default-valued right columns (the GroupJoin + DefaultIfEmpty
        left-outer idiom of the reference)."""
        _check_strategy(strategy)
        lk = _keys(left_keys)
        rk = _keys(right_keys) if right_keys is not None else lk
        self._require_cols(lk, "in join left keys")
        other._require_cols(rk, "in join right keys")
        fields = [(f.name, f.ctype) for f in self.schema.fields]
        lnames = {f.name for f in self.schema.fields}
        for f in other.schema.fields:
            if f.name in rk:
                continue
            name = f.name if f.name not in lnames else f"{f.name}{suffix}"
            fields.append((name, f.ctype))
        phys_defaults = other._physical_row(right_defaults or {})
        node = Node(
            "join", [self.node, other.node], Schema(fields),
            self._join_partition_info(lk, strategy),
            left_keys=lk, right_keys=rk, join_kind="left",
            expansion=expansion, suffix=suffix,
            right_defaults=phys_defaults, strategy=strategy,
        )
        return Query(self.ctx, node)

    def group_join(
        self,
        other: "Query",
        left_keys: KeyArg,
        right_keys: Optional[KeyArg] = None,
        aggs: Optional[Dict[str, Tuple[str, Optional[str]]]] = None,
        defaults: Optional[Dict[str, Any]] = None,
        expansion: float = 4.0,
        strategy: str = "auto",
        selector: Optional[Callable[["Query"], "Query"]] = None,
        order: Optional[Sequence[OrderArg]] = None,
        rank_limit: Optional[int] = None,
        lid_col: str = "gj_lid",
        rank_col: str = "gj_rank",
        suffix: str = "_r",
    ) -> "Query":
        """GroupJoin (reference ``DryadLinqQueryable.cs`` GroupJoin
        overloads; dispatch ``DryadLinqQueryGen.cs:3439ff``): per left
        row, the group of exactly-matching right rows.  Three shapes:

        - neither ``aggs`` nor ``selector``: match count per left row
          (``group_join_count``).
        - ``aggs``: aggregates over the matched group via right-side
          pre-aggregation; unmatched lefts survive with ``defaults``
          (count-like aggregates default to 0).
        - ``selector``: the FULL result-selector form.  ``selector``
          receives the expanded (left x matching-right) pairs as a
          Query carrying every left column, the right non-key columns
          (clashes suffixed), plus ``lid_col`` (INT32 global left-row
          id) and ``rank_col`` (INT32 group-local position of the
          match).  It returns a Query that keeps ``lid_col``,
          typically one row per group — e.g.
          ``lambda p: p.where(lambda c: c["gj_rank"] < 3)
          .group_by("gj_lid", {"top3_sum": ("sum", "v")})`` for
          top-k-per-key, or rank-pivot selects for concat-style
          results.  The selector output is left-outer-joined back onto
          the left rows, so unmatched lefts survive with ``defaults``
          (the GroupJoin + DefaultIfEmpty composition); selector
          columns clashing with left names get ``"_s"``.

          With ``order`` (an ``order_by``-style key list over RIGHT
          columns), ranks follow that value order within each group —
          deterministic under any partitioning.  Without it they
          follow the right side's engine order.

          ``rank_limit=k`` bounds each group to its first k matches
          BEFORE pair expansion, so hot keys stop multiplying pair
          counts quadratically: top-k-per-key runs at ~k x left-rows
          memory regardless of skew (a selector filtering
          ``gj_rank < k`` sees identical pairs either way; matches
          past rank k-1 are simply absent).  Without it, a key with m
          left x m right occurrences expands m^2 pairs and a skewed
          input can exceed every capacity boost.
        """
        lk = _keys(left_keys)
        rk = _keys(right_keys) if right_keys is not None else lk
        if rank_limit is not None and selector is None:
            raise ValueError(
                "group_join: rank_limit only applies to the selector form"
            )
        if selector is not None:
            if aggs:
                raise ValueError("group_join: pass aggs OR selector, not both")
            for c in (lid_col, rank_col):
                # a right column with the helper name would be silently
                # clobbered by the rank output, so reject both sides
                if c in self.schema.names or c in other.schema.names:
                    raise ValueError(
                        f"group_join helper column {c!r} clashes with an "
                        "input column; rename via lid_col=/rank_col="
                    )
            left2 = self.with_rank(lid_col)
            pairs = left2._ranked_join(
                other, lk, rk, rank_out=rank_col, order=order,
                expansion=expansion, suffix=suffix, strategy=strategy,
                rank_limit=rank_limit,
            )
            sel = selector(pairs)
            if lid_col not in sel.schema.names:
                raise ValueError(
                    f"group_join selector result must keep the {lid_col!r} "
                    "column (one row per left-row group)"
                )
            out = left2.left_join(
                sel, [lid_col], right_defaults=defaults, expansion=2.0,
                suffix="_s", strategy=strategy,
            )
            keep = [
                c for c in out.schema.names if c not in (lid_col, rank_col)
            ]
            return out.project(keep)
        if not aggs:
            return self.group_join_count(
                other, lk, rk, expansion=expansion, strategy=strategy
            )
        right_agg = other.group_by(rk, aggs)
        dflt = dict(defaults or {})
        for out_name, (op, _col) in aggs.items():
            if op == "count" and out_name not in dflt:
                dflt[out_name] = 0
        return self.left_join(
            right_agg, lk, rk, right_defaults=dflt, expansion=expansion,
            strategy=strategy,
        )

    def _ranked_join(
        self,
        other: "Query",
        left_keys: List[str],
        right_keys: List[str],
        rank_out: str,
        order: Optional[Sequence[OrderArg]] = None,
        expansion: float = 4.0,
        suffix: str = "_r",
        strategy: str = "auto",
        rank_limit: Optional[int] = None,
    ) -> "Query":
        """Inner equi-join that also emits each pair's group-local match
        rank (full GroupJoin's enumerable group).  ``rank_limit=k``
        bounds each group to its first k matches before expansion —
        see :meth:`group_join`."""
        _check_strategy(strategy)
        if rank_limit is not None:
            try:  # accept any integral type (np.int32 etc.), reject bool
                if isinstance(rank_limit, (bool, np.bool_)):
                    raise TypeError
                rank_limit = operator.index(rank_limit)
            except TypeError:
                raise ValueError(
                    f"rank_limit must be a positive int, got {rank_limit!r}"
                ) from None
            if rank_limit < 1:
                raise ValueError(
                    f"rank_limit must be a positive int, got {rank_limit!r}"
                )
        self._require_cols(left_keys, "in group_join left keys")
        other._require_cols(right_keys, "in group_join right keys")
        ks = _order_keys(order) if order is not None else None
        if ks is not None:
            other._require_cols([n for n, _ in ks], "in group_join order")
        fields = [(f.name, f.ctype) for f in self.schema.fields]
        lnames = {f.name for f in self.schema.fields}
        for f in other.schema.fields:
            if f.name in right_keys:
                continue
            name = f.name if f.name not in lnames else f"{f.name}{suffix}"
            fields.append((name, f.ctype))
        fields.append((rank_out, ColumnType.INT32))
        node = Node(
            "join", [self.node, other.node], Schema(fields),
            self._join_partition_info(left_keys, strategy),
            left_keys=left_keys, right_keys=right_keys, join_kind="ranked",
            rank_out=rank_out, order=ks, expansion=expansion, suffix=suffix,
            strategy=strategy, rank_limit=rank_limit,
        )
        return Query(self.ctx, node)

    def _physical_row(self, values: Dict[str, Any]) -> Dict[str, Any]:
        """Encode one logical row (missing columns -> zero/empty) into
        physical column scalars, registering strings in the context
        dictionary."""
        from dryad_tpu.columnar.batch import ColumnBatch

        arrays = {}
        for f in self.schema.fields:
            v = values.get(f.name)
            if f.ctype.is_bytes:
                width = f.ctype.width
                if v is None:
                    v = bytes(width)
                if isinstance(v, (bytes, bytearray)):
                    v = np.frombuffer(bytes(v), np.uint8)
                arrays[f.name] = np.asarray(v, np.uint8).reshape(1, width)
                continue
            if v is None:
                v = "" if f.ctype == ColumnType.STRING else 0
            arrays[f.name] = np.asarray([v])
        b = ColumnBatch.from_numpy(
            self.schema, arrays, capacity=1, dictionary=self.ctx.dictionary
        )
        return {k: np.asarray(v)[0] for k, v in b.data.items()}

    def aggregate_decomposable(self, dec: "Decomposable") -> Dict[str, Any]:
        """Whole-table custom aggregate (reference Aggregate with a
        decomposable combiner): one-group group_by, returns the single
        result row."""
        phys = self.schema.device_names()

        def add_key(cols):
            import jax.numpy as jnp

            out = {c: cols[c] for c in phys}
            out["__g"] = jnp.zeros_like(
                next(iter(cols.values())), dtype=jnp.int32
            )
            return out

        keyed = self.select(
            add_key, schema=self.schema.with_field("__g", ColumnType.INT32)
        )
        g = keyed.group_by("__g", decomposable=dec)
        out_names = [n for n, _ in dec.out_fields]
        table = g.project(out_names).collect()
        return {k: (v[0] if len(v) else None) for k, v in table.items()}

    def group_join_count(
        self,
        other: "Query",
        left_keys: KeyArg,
        right_keys: Optional[KeyArg] = None,
        out: str = "match_count",
        expansion: float = 4.0,
        strategy: str = "auto",
    ) -> "Query":
        """GroupJoin's aggregate shape (reference GroupJoin): per left
        row, the count of matching right rows as a new INT32 column.
        Richer group aggregations compose via join + group_by."""
        _check_strategy(strategy)
        lk = _keys(left_keys)
        rk = _keys(right_keys) if right_keys is not None else lk
        self._require_cols(lk, "in group_join left keys")
        other._require_cols(rk, "in group_join right keys")
        fields = [(f.name, f.ctype) for f in self.schema.fields]
        fields.append((out, ColumnType.INT32))
        node = Node(
            "join", [self.node, other.node], Schema(fields),
            self._join_partition_info(lk, strategy),
            left_keys=lk, right_keys=rk, join_kind="count",
            expansion=expansion, out=out, strategy=strategy,
        )
        return Query(self.ctx, node)

    def zip_(self, other: "Query", suffix: str = "_r") -> "Query":
        """Pair rows by global position (reference Zip,
        ``DryadLinqQueryGen.cs`` Zip dispatch): result length is the
        shorter input's length (LINQ Zip semantics)."""
        fields = [(f.name, f.ctype) for f in self.schema.fields]
        lnames = {f.name for f in self.schema.fields}
        for f in other.schema.fields:
            name = f.name if f.name not in lnames else f"{f.name}{suffix}"
            fields.append((name, f.ctype))
        node = Node(
            "zip", [self.node, other.node], Schema(fields), PartitionInfo(),
            suffix=suffix,
        )
        return Query(self.ctx, node)

    def sliding_window(self, size: int, cols: Optional[KeyArg] = None) -> "Query":
        """Sliding windows over the global row sequence (reference
        SlidingWindow, ``DryadLinqQueryable.cs:1318``): for each window
        of ``size`` consecutive rows, emit columns ``{c}_w{j}`` (j-th
        row of the window).  Restricted to non-split (numeric/bool)
        columns; yields n-size+1 windows.
        """
        cols = _keys(cols) if cols is not None else self.schema.names
        self._require_cols(cols, "in sliding_window")
        fields: List[Tuple[str, ColumnType]] = []
        for c in cols:
            ct = self.schema.field(c).ctype
            if ct.is_split:
                raise ValueError(
                    f"sliding_window unsupported on {ct.value} column {c!r}"
                )
            for j in range(size):
                fields.append((f"{c}_w{j}", ct))
        node = Node(
            "sliding_window", [self.node], Schema(fields), PartitionInfo(),
            size=int(size), cols=cols,
        )
        return Query(self.ctx, node)

    # -- escape hatches ------------------------------------------------------
    def apply(
        self,
        fn: Callable,
        schema: Optional[Schema] = None,
        cap_factor: float = 1.0,
        with_index: bool = False,
    ) -> "Query":
        """Per-partition user function over a ColumnBatch (reference
        Apply/ApplyPerPartition; with_index = ApplyWithPartitionIndex)."""
        node = Node(
            "apply", [self.node], schema or self.schema, PartitionInfo(),
            fn=fn, cap_factor=cap_factor, with_index=with_index,
        )
        return Query(self.ctx, node)

    def apply_host(
        self,
        fn: Callable,
        schema: Optional[Schema] = None,
    ) -> "Query":
        """Per-partition HOST callback: fn(cols: dict[str, np.ndarray],
        partition_index) -> dict of equal-length arrays — the arbitrary
        user-code escape hatch (reference Apply runs arbitrary .NET
        lambdas; jittable fns should use ``apply``).  Each job costs a
        device->host->device round-trip: the documented perf cliff
        (SURVEY 7.3).

        The fn sees *physical* columns: STRING columns arrive as their
        encoded hash/prefix word columns (``s#h0``..``s#r1``), and a
        STRING output column must be produced the same way.  Output is
        validated against ``schema`` (names + dtypes) and cast."""
        node = Node(
            "apply_host", [self.node], schema or self.schema,
            PartitionInfo(), fn=fn,
        )
        return Query(self.ctx, node)

    def fork(self, fn: Callable, out_schemas: Sequence[Schema]) -> Tuple["Query", ...]:
        """Multi-output per-partition function (reference Fork,
        ``DryadLinqQueryable.cs:3717``): fn(batch) -> tuple of batches."""
        fork_node = Node(
            "fork", [self.node], self.schema, PartitionInfo(),
            fn=fn, out_schemas=list(out_schemas),
        )
        outs = []
        for i, s in enumerate(out_schemas):
            branch = Node(
                "fork_branch", [fork_node], s, PartitionInfo(), index=i
            )
            outs.append(Query(self.ctx, branch))
        return tuple(outs)

    def do_while(
        self,
        body: Callable[["Query"], "Query"],
        cond: Callable[["Query"], "Query"],
        max_iter: int = 100,
        device: bool = False,
    ) -> "Query":
        """Iterate body until cond yields False (reference DoWhile,
        ``DryadLinqQueryable.cs:1281``). ``cond`` maps the current
        dataset to a 1-row bool query (e.g. via count_as_query + select).

        ``device=True`` compiles the WHOLE loop as one on-device
        ``lax.while_loop`` (no host round-trip per iteration) when body
        and cond each lower to a single fused stage and the body
        preserves batch structure; otherwise it falls back to the
        driver loop (a ``do_while_device_fallback`` event is logged)."""
        node = Node(
            "do_while", [self.node], self.schema, PartitionInfo(),
            body=body, cond=cond, max_iter=max_iter, device=device,
        )
        return Query(self.ctx, node)

    # -- scalar aggregates ---------------------------------------------------
    def _aggregate_node(self, aggs: List[Tuple[str, Optional[str], str]]) -> Node:
        fields = []
        for op, col, out in aggs:
            ct = self.schema.field(col).ctype if col else ColumnType.INT32
            fields.append((out, _agg_type(op, col, ct)))
        return Node(
            "aggregate", [self.node], Schema(fields), PartitionInfo(), aggs=aggs
        )

    def aggregate_as_query(self, aggs: Dict[str, Tuple[str, Optional[str]]]) -> "Query":
        lst = [(op, col, out) for out, (op, col) in aggs.items()]
        return Query(self.ctx, self._aggregate_node(lst))

    def count_as_query(self) -> "Query":
        return self.aggregate_as_query({"count": ("count", None)})

    def _scalar(self, op: str, col: Optional[str]):
        # min/max/mean/any/all on an empty table would otherwise surface
        # the reduction's dtype sentinel; count alongside guards it.
        q = self.aggregate_as_query({"v": (op, col), "n": ("count", None)})
        table = q.collect()
        if op not in ("count", "sum") and int(table["n"][0]) == 0:
            return None
        return table["v"][0].item()

    def count(self) -> int:
        return int(self._scalar("count", None))

    def sum_(self, col: str):
        return self._scalar("sum", col)

    def min_(self, col: str):
        return self._scalar("min", col)

    def max_(self, col: str):
        return self._scalar("max", col)

    def mean(self, col: str) -> float:
        return float(self._scalar("mean", col))

    def any_(self, col: str) -> bool:
        return bool(self._scalar("any", col))

    def all_(self, col: str) -> bool:
        return bool(self._scalar("all", col))

    # -- materialization -----------------------------------------------------
    def explain(self, analyze: bool = False) -> str:
        """Pretty-print the logical plan and fused stage graph
        (``DryadLinqQueryExplain.cs`` analog).  ``analyze=True``
        EXECUTES the query first and appends the runtime-diagnosis
        panel — phase attribution plus any pathologies the online
        engine (``obs.diagnose``) caught during the run."""
        from dryad_tpu.obs import critpath, tracectx
        from dryad_tpu.tools.explain import explain, explain_diagnoses

        text = explain(self)
        if analyze:
            # mint (or adopt) a trace context so the run's events are
            # qid-stamped, then fold them into the critical-path panel
            tctx = tracectx.current() or tracectx.mint()
            with tracectx.activate(tctx):
                self.collect()
            text += "\n\n" + explain_diagnoses(self.ctx)
            bd = critpath.fold_query(self.ctx.events.events(), tctx.qid)
            if bd is not None and bd.phases:
                text += "\n\n-- critical path --\n" + bd.format()
        return text

    def collect(self) -> Dict[str, np.ndarray]:
        """Execute and fetch host logical columns (reference
        Submit+enumerate path, ``DryadLinqQuery.cs:608``): the job of
        one output (``DryadContext.collect_many``)."""
        return self.ctx.run_to_host(self)

    def collect_stream(self):
        """Execute an out-of-core (``from_stream``) plan and yield
        host tables one bounded piece at a time — the result-side
        counterpart of chunked ingest, for outputs larger than host
        memory (reference: enumerating a query streams the output
        table, ``DryadLinqQuery.cs:608-647``).  Plans without a stream
        input yield their whole result once."""
        from dryad_tpu.exec.outofcore import (
            StreamExecutor,
            has_stream_input,
        )

        if not has_stream_input(self.ctx, self.node):
            yield self.collect()
            return
        if self.ctx.local_debug:
            raise RuntimeError(
                "from_stream inputs are not supported in local_debug mode"
            )
        from dryad_tpu.obs import tracectx

        # one trace context covers the whole streamed run: the chunk
        # pipeline captures it at construction, so producer/consumer
        # spans across every yielded piece share one qid
        with tracectx.activate(self.ctx._trace_ctx()):
            _schema, tables = StreamExecutor(self.ctx).run_stream(self.node)
            yield from tables

    def __iter__(self):
        """Enumerating a query triggers execution and yields row dicts
        (reference TableEnumerator, ``DryadLinqQuery.cs:608-647``:
        foreach on a query submits the job and streams the output)."""
        table = self.collect()
        names = list(table.keys())
        n = len(table[names[0]]) if names else 0
        for i in range(n):
            yield {c: table[c][i] for c in names}

    def submit(self) -> "JobHandle":
        return self.ctx.submit(self)

    def to_store(self, path: str) -> "JobHandle":
        """Execute and persist as a partitioned store (reference ToStore,
        ``DryadLinqQueryable.cs:3909``)."""
        return self.ctx.to_store(self, path)

    def cache(self) -> "Query":
        """Execute now and return a query over the DEVICE-RESIDENT
        result: downstream queries branch from the materialized batch
        instead of recomputing this pipeline (the reference's temp-table
        materialization — ``ToStoreInternal`` isTemp,
        ``DryadLinqQueryable.cs:3948`` — kept in HBM instead of DFS).
        The cached table carries this query's partition claim, so a
        downstream consumer with matching keys elides its exchange.
        It does not survive ``rebuild_mesh`` (clear error on use);
        ``ctx.release(cached)`` drops the HBM pin explicitly."""
        if self.ctx.local_debug:
            out = self.ctx.run_to_host(self)
            q = self.ctx.from_arrays(out, schema=self.schema)
            # mark so release() honors the documented contract in the
            # debug interpreter too (there is no HBM pin to drop)
            q.node.params["cached"] = True
            return q
        batch = self.ctx._execute_device(self)
        return self.ctx._from_device_batch(
            batch, self.schema, partition=self.node.partition
        )


class JobHandle:
    """Completed-job handle (reference SubmitAndWait returns job info)."""

    def __init__(self, table: Dict[str, np.ndarray], path: Optional[str] = None):
        self.table = table
        self.path = path

    def result(self) -> Dict[str, np.ndarray]:
        return self.table
