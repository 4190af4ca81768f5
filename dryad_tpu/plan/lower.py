"""Lowering: logical node DAG -> fused stage graph.

The analog of the reference's three-phase physical planning
(``DryadLinqQueryGen.cs``): Phase-1 operator translation happens as the
API builds logical nodes; this module is Phase-2 *pipelining* — fusing
maximal operator chains into one stage, the SuperNode of
``DryadLinqQueryGen.cs:406-456`` — and Phase-3 cleanup: Tee boundaries
at multi-consumer nodes, combiner (partial-aggregation) insertion before
shuffles (the ``DrDynamicAggregateManager`` tree analog), and shuffle
elision when partition metadata already matches (AssumePartition logic).

A Stage executes as ONE ``shard_map``-ped XLA program; exchanges are
``all_to_all`` *ops inside the stage*, not channel edges between
processes — the central TPU-first inversion of the reference design.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, List, Optional, Sequence, Tuple

from dryad_tpu.columnar.schema import Schema
from dryad_tpu.plan import keys as K
from dryad_tpu.plan.nodes import Node, PartitionInfo, consumers, walk

_stage_ids = itertools.count()


@dataclasses.dataclass
class StageOp:
    kind: str
    params: Dict[str, Any]

    def __repr__(self) -> str:
        return f"{self.kind}({', '.join(sorted(self.params))})"


@dataclasses.dataclass
class Stage:
    """A fused per-partition pipeline compiled as one SPMD program.

    ``input_refs``: (producer_stage_id, out_index) pairs, or
    ("plan_input", input_node_id) for plan inputs bound at execution.
    Ops manipulate numbered slots; slot i starts as input i; outputs are
    the slots named in ``out_slots``.
    """

    id: int
    name: str
    input_refs: List[Tuple[Any, int]]
    ops: List[StageOp] = dataclasses.field(default_factory=list)
    out_slots: List[int] = dataclasses.field(default_factory=lambda: [0])
    # growth: output capacity multiplier relative to base input capacity
    growth: float = 1.0
    # slots taken beyond the inputs' (``take_slots``)
    taken: int = 0

    def take_slots(self, n: int) -> List[int]:
        """``n`` slots that no input of the stage and no earlier taker
        holds (an op works in its input's slot; a ``fork`` needs one an
        output)."""
        first = len(self.input_refs) + self.taken
        self.taken += n
        return list(range(first, first + n))


@dataclasses.dataclass
class StageGraph:
    stages: List[Stage]
    # node id -> (stage id, out_index) for roots the caller asked for
    outputs: Dict[int, Tuple[int, int]]
    # plan-input node id -> Node (for binding host data)
    inputs: Dict[int, Node]


def tail_width(rows, config, P) -> Optional[int]:
    """ceil(rows / tail_rows_per_partition) when ``rows`` is at or
    below the tail threshold; None = full width.  A result at or above
    the mesh width ``P`` (when known) is no reduction at all, and
    returning it would needlessly mark the node reduced (forcing joins
    to re-exchange a correctly co-partitioned side).  ONE sizing policy
    for both the static estimator and the runtime observed-volume
    adapter (``exec.executor``)."""
    limit = getattr(config, "tail_fanout_rows", 4096)
    if not limit or rows is None or rows > limit:
        return None
    per = max(1, getattr(config, "tail_rows_per_partition", 512))
    nparts = max(1, -(-rows // per))
    if P is not None and nparts >= P:
        return None
    return nparts


class _Builder:
    def __init__(self, config, dictionary=None, P: Optional[int] = None) -> None:
        self.config = config
        self.dictionary = dictionary
        # mesh width when the caller knows it (fan-out decisions)
        self.P = P
        self.stages: List[Stage] = []
        self.open: Dict[int, Stage] = {}  # stage id -> stage (not yet closed)
        # node id -> ("open", stage, slot) | ("closed", stage_id, out_idx)
        self.cursor: Dict[int, Tuple] = {}
        self.plan_inputs: Dict[int, Node] = {}
        # node id -> static upper bound on GLOBAL row count (None =
        # unbounded); feeds stage-level fan-out adaptation
        self.est: Dict[int, Optional[int]] = {}
        # node ids whose hash claim was produced by a fan-REDUCED
        # exchange (mod P_stage < P): still key-colocated, so group_by
        # elision stays safe, but a join must NOT treat it as
        # co-partitioned with a full-width side
        self.reduced: set = set()
        # (node id, col) -> static vocab walk result; the gate and the
        # emission run back-to-back, and do_while re-lowers per
        # iteration — don't redo the O(V) union walk each time
        self._vocab_cache: Dict[Tuple[int, str], Any] = {}

    def _str_vocab(self, node: Node, col: str):
        key = (node.id, col)
        if key not in self._vocab_cache:
            from dryad_tpu.api.query import static_str_vocab

            self._vocab_cache[key] = static_str_vocab(node, col)
        return self._vocab_cache[key]

    # -- static row estimates (DrDynamicRangeDistributor.cpp:54-110:
    # consumer fan-out from observed data size; here from the plan's
    # statically-bounded row counts) --------------------------------------
    def _estimate_node(self, node: Node) -> Optional[int]:
        ins = [self.est.get(i.id) for i in node.inputs]
        k = node.kind
        if k == "aggregate":
            return 1
        if k in ("take", "tail"):
            return int(node.params["n"])
        if k == "topk":
            return int(node.params["n"])
        if k == "group_by":
            if node.params.get("dense"):
                return int(node.params["dense"])
            if node.params.get("auto_dense") and self.dictionary is not None:
                return len(self.dictionary)
            return ins[0]  # groups <= input rows
        if k == "distinct":
            return ins[0]
        if k == "concat":
            return sum(ins) if all(e is not None for e in ins) else None
        if k == "zip":
            known = [e for e in ins if e is not None]
            return min(known) if known else None
        if k in (
            "select", "where", "project", "with_rank", "take_while",
            "skip_while", "skip", "reverse", "default_if_empty",
            "order_by", "hash_partition", "range_partition",
            "assume_partition", "tee", "fork_branch", "cache",
        ):
            return ins[0] if ins else None
        if k == "join" and node.params.get("join_kind") in (
            "count", "semi", "anti"
        ):
            # per-left-row output shapes: at most the left's rows
            # (left-outer and inner joins expand — unbounded)
            return ins[0]
        return None

    def _tail_nparts(self, src: Node) -> Optional[int]:
        """Masked-partition fan-out for the consumer exchange when the
        source is statically tiny; None = full width (see
        :func:`tail_width` — shared with the runtime observed-volume
        adapter)."""
        return tail_width(self.est.get(src.id), self.config, self.P)

    # -- stage bookkeeping -------------------------------------------------
    def _new_stage(self, name: str, input_refs: List[Tuple[Any, int]]) -> Stage:
        s = Stage(next(_stage_ids), name, input_refs)
        self.stages.append(s)
        self.open[s.id] = s
        return s

    def _close(self, stage: Stage, out_slots: Optional[List[int]] = None) -> None:
        if out_slots is not None:
            stage.out_slots = out_slots
        self.open.pop(stage.id, None)

    def _materialize(self, node: Node) -> Tuple[int, int]:
        """Ensure node's value is a closed stage output; return ref."""
        kind, *rest = self.cursor[node.id]
        if kind == "closed":
            return rest[0], rest[1]
        stage, slot = rest
        self._close(stage, [slot])
        self.cursor[node.id] = ("closed", stage.id, 0)
        return stage.id, 0

    def _continue_or_start(
        self, node: Node, n_consumers: int
    ) -> Tuple[Stage, int]:
        """Get an open stage positioned at node's single input value."""
        (src,) = node.inputs
        kind, *rest = self.cursor[src.id]
        if kind == "open" and n_consumers == 1:
            stage, slot = rest
            self._tag(stage, node.kind)
            return stage, slot
        ref = self._materialize(src)
        stage = self._new_stage(node.kind, [ref])
        return stage, 0

    @staticmethod
    def _tag(stage: Stage, kind: str) -> None:
        """Record a fused node kind in the stage name ('input+group_by')."""
        if kind not in stage.name.split("+"):
            stage.name = f"{stage.name}+{kind}"

    # -- node lowering -----------------------------------------------------
    def lower_node(self, node: Node, fanout: Dict[int, int]) -> None:
        self.est[node.id] = self._estimate_node(node)
        # reduced-ness is sticky down single-input chains: any claim
        # derived from fan-reduced data keeps its mod-P_stage layout
        # until something re-exchanges full-width
        if node.inputs and node.inputs[0].id in self.reduced:
            self.reduced.add(node.id)
        n_cons = fanout.get(node.id, 1)
        k = node.kind

        if k == "input":
            self.plan_inputs[node.id] = node
            stage = self._new_stage("input", [("plan_input", node.id)])
            self.cursor[node.id] = ("open", stage, 0)

        elif k in (
            "select", "where", "select_many", "apply", "take",
            "skip", "tail", "take_while", "skip_while", "reverse",
            "default_if_empty", "with_rank",
        ):
            stage, slot = self._continue_or_start(node, fanout.get(node.inputs[0].id, 1))
            if k == "select":
                stage.ops.append(StageOp("select", dict(slot=slot, fn=node.params["fn"])))
            elif k == "where":
                stage.ops.append(StageOp("where", dict(slot=slot, fn=node.params["fn"])))
            elif k == "select_many":
                stage.ops.append(
                    StageOp(
                        "select_many",
                        dict(slot=slot, fn=node.params["fn"], factor=node.params["factor"]),
                    )
                )
                stage.growth *= node.params["factor"]
            elif k == "apply":
                stage.ops.append(
                    StageOp(
                        "apply",
                        dict(
                            slot=slot,
                            fn=node.params["fn"],
                            with_index=node.params.get("with_index", False),
                            cap_factor=node.params.get("cap_factor", 1.0),
                        ),
                    )
                )
                stage.growth *= node.params.get("cap_factor", 1.0)
            elif k == "with_rank":
                stage.ops.append(
                    StageOp("with_rank", dict(slot=slot, out=node.params["out"]))
                )
            elif k in ("take", "skip", "tail"):
                # Global rank is partition-major, so take() after order_by
                # yields the first n in sort order; on unordered input it
                # is the first n in engine (== ingestion) order.
                stage.ops.append(
                    StageOp(k, dict(slot=slot, n=node.params["n"]))
                )
            elif k in ("take_while", "skip_while"):
                stage.ops.append(
                    StageOp(k, dict(slot=slot, fn=node.params["fn"]))
                )
            elif k == "reverse":
                stage.ops.append(StageOp("reverse", dict(slot=slot)))
            elif k == "default_if_empty":
                stage.ops.append(
                    StageOp(
                        "default_if_empty",
                        dict(slot=slot, defaults=node.params["defaults"]),
                    )
                )
            self.cursor[node.id] = ("open", stage, slot)

        elif k == "topk":
            stage, slot = self._continue_or_start(
                node, fanout.get(node.inputs[0].id, 1)
            )
            in_schema = node.inputs[0].schema
            operands_fn = K.ordering_operands(in_schema, node.params["keys"])
            stage.ops.append(
                StageOp(
                    "topk",
                    dict(slot=slot, operands_fn=operands_fn,
                         n=int(node.params["n"])),
                )
            )
            # topk SHRINKS the batch capacity; close the stage so any
            # consumer's capacity bookkeeping starts from the new size.
            self.cursor[node.id] = ("open", stage, slot)
            self._materialize(node)

        elif k == "assume_partition":
            # Metadata-only: value identical to input.
            self.cursor[node.id] = self.cursor[node.inputs[0].id]

        elif k in ("hash_partition", "group_by", "distinct"):
            self._lower_keyed(node, fanout)

        elif k in ("order_by", "range_partition"):
            self._lower_ranged(node, fanout)

        elif k == "join":
            self._lower_join(node)

        elif k == "zip":
            lref = self._materialize(node.inputs[0])
            rref = self._materialize(node.inputs[1])
            stage = self._new_stage("zip", [lref, rref])
            stage.ops.append(
                StageOp(
                    "zip",
                    dict(left_slot=0, right_slot=1, suffix=node.params["suffix"]),
                )
            )
            self.cursor[node.id] = ("open", stage, 0)

        elif k == "sliding_window":
            stage, slot = self._continue_or_start(node, fanout.get(node.inputs[0].id, 1))
            stage.ops.append(
                StageOp(
                    "sliding_window",
                    dict(slot=slot, size=node.params["size"], cols=node.params["cols"]),
                )
            )
            self.cursor[node.id] = ("open", stage, slot)

        elif k == "concat":
            refs = [self._materialize(i) for i in node.inputs]
            stage = self._new_stage("concat", refs)
            stage.ops.append(
                StageOp("concat", dict(slots=list(range(len(refs))), out_slot=0))
            )
            stage.growth = float(len(refs))
            self.cursor[node.id] = ("open", stage, 0)

        elif k == "aggregate":
            stage, slot = self._continue_or_start(node, fanout.get(node.inputs[0].id, 1))
            aggs = self._phys_aggs(node.inputs[0].schema, node.params["aggs"])
            stage.ops.append(StageOp("scalar_agg", dict(slot=slot, aggs=aggs)))
            self.cursor[node.id] = ("open", stage, slot)

        elif k == "fork":
            stage, slot = self._continue_or_start(node, fanout.get(node.inputs[0].id, 1))
            out_slots = stage.take_slots(len(node.params["out_schemas"]))
            stage.ops.append(
                StageOp(
                    "fork",
                    dict(
                        slot=slot, fn=node.params["fn"], out_slots=out_slots,
                        # what each output must look like, checked as
                        # the fork is traced: physical column -> dtype
                        out_dtypes=tuple(
                            tuple(
                                (n, dt.name)
                                for n, dt in sorted(sch.device_dtypes().items())
                            )
                            for sch in node.params["out_schemas"]
                        ),
                    ),
                )
            )
            self._close(stage, out_slots)
            self.cursor[node.id] = ("closed", stage.id, -1)  # branches index it

        elif k == "fork_branch":
            fork_node = node.inputs[0]
            _, stage_id, _ = self.cursor[fork_node.id]
            self.cursor[node.id] = ("closed", stage_id, node.params["index"])

        elif k == "tee":
            ref = self._materialize(node.inputs[0])
            self.cursor[node.id] = ("closed", ref[0], ref[1])

        elif k == "apply_host":
            # Host-callback stage: driver-evaluated (device->host->device),
            # the arbitrary-user-code escape hatch.
            ref = self._materialize(node.inputs[0])
            stage = self._new_stage("apply_host", [ref])
            stage.ops.append(
                StageOp(
                    "apply_host",
                    dict(fn=node.params["fn"], schema=node.schema),
                )
            )
            self._close(stage, [0])
            self.cursor[node.id] = ("closed", stage.id, 0)

        elif k == "do_while":
            # Driver-loop node: body/cond are plan-producing callables the
            # executor re-lowers per iteration (reference GM evaluates
            # DoWhile subplans per iteration, DryadLinqQueryNode.cs:4555).
            ref = self._materialize(node.inputs[0])
            stage = self._new_stage("do_while", [ref])
            stage.ops.append(
                StageOp(
                    "do_while",
                    dict(
                        body=node.params["body"],
                        cond=node.params["cond"],
                        max_iter=node.params.get("max_iter", 100),
                        device=node.params.get("device", False),
                        schema=node.schema,
                    ),
                )
            )
            self._close(stage, [0])
            self.cursor[node.id] = ("closed", stage.id, 0)

        else:
            raise NotImplementedError(f"lowering for node kind {k!r}")

        # Multi-consumer (Tee analog): close so consumers share one value.
        if n_cons > 1 and self.cursor[node.id][0] == "open":
            self._materialize(node)

    # -- keyed (hash) ops --------------------------------------------------
    def _emit_auto_dense(self, node: Node, stage, slot, key: str, aggs) -> None:
        """Shared emission for auto-dense STRING rewrites (group_by and
        vocabulary distinct): string_code -> dense bucket reduce with
        decode -> project to the node's schema.  When the key column's
        per-ingest vocabulary is statically known, the coding tables
        shrink to THAT subset — a context that ingested an unrelated
        huge vocabulary elsewhere no longer inflates K for this query."""
        from dryad_tpu.ops.stringcode import build_tables, build_tables_subset

        vocab = self._str_vocab(node.inputs[0], key)
        if vocab is not None and len(vocab) < len(self.dictionary):
            code_t, dec_t = build_tables_subset(self.dictionary, vocab)
        else:
            code_t, dec_t = build_tables(self.dictionary)
        # Runtime-operand tables: the bucket domain is the table's
        # shape-palette tier (pow2 >= K), not K itself — K is a
        # per-widen value whose baking would put the vocabulary size
        # back into the trace the operand split just removed.  Codes in
        # [K, padded) never occur (misses map to padded exactly), so
        # the extra buckets stay empty and drop at the validity mask.
        runtime = bool(
            getattr(self.config, "stringcode_runtime_tables", True)
        )
        num_buckets = (
            code_t.num_codes_padded if runtime else code_t.num_codes
        )
        stage.ops.append(StageOp(
            "string_code",
            dict(slot=slot, h0=f"{key}#h0", h1=f"{key}#h1",
                 out="#code", table=code_t),
        ))
        stage.ops.append(StageOp(
            "group_reduce_dense",
            dict(slot=slot, key="#code", aggs=aggs,
                 num_buckets=num_buckets, decode=dec_t,
                 out_key=key),
        ))
        want = K.group_carry_cols(node.schema, node.schema.names)
        stage.ops.append(StageOp("project", dict(slot=slot, cols=want)))
        self.cursor[node.id] = ("open", stage, slot)

    def _auto_dense_ok(self, node: Node, in_schema: Schema, keys) -> bool:
        """Gate for the auto-dense STRING group_by rewrite: one STRING
        key, dense-supported aggs over plain numeric columns, and a
        bounded context dictionary to code against."""
        # Eligibility is decided at node-creation time (Query
        # _auto_dense_eligible, which also drops the partition claim —
        # the rewrite's output is code-range partitioned, matching no
        # claimable scheme); here only the dictionary gate re-checks,
        # because the vocabulary may have grown between build and
        # lowering.  A late fallback to the sort path stays correct
        # precisely because the node claims nothing.
        if not node.params.get("auto_dense"):
            return False
        if self.dictionary is None or len(self.dictionary) == 0:
            return False
        limit = getattr(self.config, "auto_dense_limit", 1 << 17)
        vocab = self._str_vocab(node.inputs[0], keys[0])
        bound = len(vocab) if vocab is not None else len(self.dictionary)
        return 0 < bound <= limit

    def _phys_aggs(self, schema: Schema, aggs) -> List:
        from dryad_tpu.ops.segmented import AggSpec

        out = []
        from dryad_tpu.columnar.schema import ColumnType, DecimalType

        for op, col, name in aggs:
            if col is not None:
                f = schema.field(col)
                if isinstance(f.ctype, DecimalType) and not f.ctype.wide:
                    # a narrow DECIMAL: min / max / first are the int32
                    # column's; a sum (and a mean's) is carried in 64
                    # bits from the sign-extended word, so money adds
                    # up exactly where a column of it fits 32 bits
                    if op == "sum":
                        out.append(AggSpec("sum64", col, name))
                        continue
                    if op == "mean":
                        out.append(AggSpec("mean64", col, name, f.ctype.scale))
                        continue
                elif f.ctype.storage is ColumnType.INT64 and op in ("sum", "min", "max"):
                    # exact 64-bit arithmetic over the split (#h0, #h1)
                    # word pair (carry-propagating add / signed-lex
                    # compare, ops/wide.py; the reference's numeric
                    # aggregate surface is DryadLinqQueryGen.cs:3439ff)
                    out.append(AggSpec(f"{op}64", f"{col}#h0", name))
                    continue
                elif f.ctype.storage is ColumnType.INT64 and op == "mean":
                    # Average over long: exact sum64 + count partials,
                    # f32 divide at finalize (a DECIMAL's by its scale
                    # too: the mean is in units)
                    out.append(AggSpec(
                        "mean64", f"{col}#h0", name,
                        getattr(f.ctype, "scale", 0),
                    ))
                    continue
                if f.ctype is ColumnType.FLOAT64:
                    if op in ("min", "max"):
                        # the stored words are the order-preserving
                        # signed-int64 image, so int64 signed-lex
                        # min/max apply unchanged (columnar/schema.py)
                        out.append(AggSpec(f"{op}64", f"{col}#h0", name))
                        continue
                    if op in ("sum", "mean"):
                        raise ValueError(
                            f"aggregate {op!r} unsupported on float64 "
                            f"column {col!r}: no f64 arithmetic on "
                            f"device — cast to float32 for approximate "
                            f"sums"
                        )
                if f.ctype.is_split:
                    if op != "first":
                        raise ValueError(
                            f"aggregate {op!r} unsupported on {f.ctype.value} "
                            f"column {col!r}"
                        )
                    # 'first' on a split column: one AggSpec per device
                    # word, producing the output field's word columns.
                    for dev in f.device_names:
                        word = dev.split("#", 1)[1]
                        out.append(AggSpec("first", dev, f"{name}#{word}"))
                    continue
            out.append(AggSpec(op, col, name))
        return out

    def _needs_hash_exchange(self, node: Node, keys: Sequence[str]) -> bool:
        """Equal-key COLOCATION elision for keyed ops (group_by /
        distinct / hash_partition): a matching hash claim colocates, and
        so does a STRICT (non-spread) range claim whose partition keys
        are a subset of the group keys — the partition function then
        depends only on the group key, so equal groups cannot straddle
        (the dense bucket path's key-ordered output rides this)."""
        src = node.inputs[0]
        p = src.partition
        if p.scheme == "hash" and tuple(p.keys) == tuple(keys):
            return False
        if (
            p.scheme == "range"
            and not p.spread
            and p.keys
            and set(p.keys) <= set(keys)
        ):
            return False
        return True

    def _lower_keyed(self, node: Node, fanout: Dict[int, int]) -> None:
        stage, slot = self._continue_or_start(node, fanout.get(node.inputs[0].id, 1))
        in_schema = node.inputs[0].schema
        keys = node.params["keys"]
        eq_cols = K.equality_cols(in_schema, keys)
        carry_cols = K.group_carry_cols(in_schema, keys)
        need_exchange = self._needs_hash_exchange(node, keys)

        # Stage-level fan-out adaptation: a statically-tiny input
        # concentrates onto fewer partitions (masked tail).
        nparts = self._tail_nparts(node.inputs[0])

        if node.kind == "hash_partition":
            if need_exchange:
                if nparts:
                    self.reduced.add(node.id)
                stage.ops.append(StageOp(
                    "exchange_hash",
                    dict(slot=slot, keys=eq_cols, nparts=nparts),
                ))
                stage.ops.append(StageOp(
                    "resize",
                    dict(slot=slot, factor=stage.growth, nparts=nparts),
                ))
            self.cursor[node.id] = ("open", stage, slot)
            return

        if node.kind == "distinct" and self._auto_dense_ok(node, in_schema, keys):
            # vocabulary distinct: bucket count>0 + decode, no shuffle
            from dryad_tpu.ops.segmented import AggSpec

            self._emit_auto_dense(
                node, stage, slot, keys[0], [AggSpec("count", None, "#c")]
            )
            return

        if node.kind == "distinct":
            if need_exchange:
                if nparts:
                    self.reduced.add(node.id)
                stage.ops.append(StageOp("distinct", dict(slot=slot, keys=eq_cols)))
                stage.ops.append(StageOp(
                    "exchange_hash",
                    dict(slot=slot, keys=eq_cols, nparts=nparts,
                         tree=dict(keys=eq_cols, distinct=True)),
                ))
                stage.ops.append(StageOp(
                    "resize",
                    dict(slot=slot, factor=stage.growth, nparts=nparts),
                ))
            stage.ops.append(StageOp("distinct", dict(slot=slot, keys=eq_cols)))
            self.cursor[node.id] = ("open", stage, slot)
            return

        # dense-key fast path: MXU bucket reduce + psum_scatter, no shuffle
        # (see ops/pallas_bucket.py; plan-level analog of swapping the
        # reference's aggregation tree for one collective).
        if node.kind == "group_by" and node.params.get("dense"):
            aggs = self._phys_aggs(in_schema, node.params["aggs"])
            stage.ops.append(
                StageOp(
                    "group_reduce_dense",
                    dict(
                        slot=slot,
                        key=carry_cols[0],
                        aggs=aggs,
                        num_buckets=int(node.params["dense"]),
                        guard=bool(node.params.get("guard_range")),
                    ),
                )
            )
            want = K.group_carry_cols(node.schema, node.schema.names)
            stage.ops.append(StageOp("project", dict(slot=slot, cols=want)))
            self.cursor[node.id] = ("open", stage, slot)
            return

        # auto-dense STRING fast path: a plain group_by over one STRING
        # key whose domain is the (bounded) context dictionary maps
        # rows to dense codes on device and reduces on the MXU with no
        # shuffle (ops/stringcode.py); codes decode back to the string
        # physical words per partition.  The reference pays a full hash
        # repartition for this query shape (DryadLinqQueryNode.cs:3581).
        if node.kind == "group_by" and self._auto_dense_ok(node, in_schema, keys):
            aggs = self._phys_aggs(in_schema, node.params["aggs"])
            self._emit_auto_dense(node, stage, slot, keys[0], aggs)
            return

        # group_by with builtin aggs or a Decomposable
        decomposable = node.params.get("decomposable")
        if decomposable is not None:
            stage.ops.append(
                StageOp(
                    "seed",
                    dict(slot=slot, fn=decomposable.seed, state_cols=decomposable.state_cols),
                )
            )
            keep = carry_cols + list(decomposable.state_cols)
            stage.ops.append(StageOp("project", dict(slot=slot, cols=keep)))
            stage.ops.append(
                StageOp(
                    "group_combine",
                    dict(
                        slot=slot,
                        keys=carry_cols,
                        state_cols=decomposable.state_cols,
                        merge=decomposable.merge,
                    ),
                )
            )
            if need_exchange:
                if nparts:
                    self.reduced.add(node.id)
                stage.ops.append(StageOp(
                    "exchange_hash",
                    dict(slot=slot, keys=eq_cols, nparts=nparts,
                         tree=dict(keys=carry_cols,
                                   state_cols=decomposable.state_cols,
                                   merge=decomposable.merge)),
                ))
                stage.ops.append(StageOp(
                    "resize",
                    dict(slot=slot, factor=stage.growth, nparts=nparts),
                ))
                stage.ops.append(
                    StageOp(
                        "group_combine",
                        dict(
                            slot=slot,
                            keys=carry_cols,
                            state_cols=decomposable.state_cols,
                            merge=decomposable.merge,
                        ),
                    )
                )
            if decomposable.finalize is not None:
                stage.ops.append(
                    StageOp("select", dict(slot=slot, fn=decomposable.finalize))
                )
                want = K.group_carry_cols(node.schema, node.schema.names)
                stage.ops.append(StageOp("project", dict(slot=slot, cols=want)))
        else:
            aggs = self._phys_aggs(in_schema, node.params["aggs"])
            partial, final, means = _decompose_aggs(aggs)
            from dryad_tpu.ops.segmented import AggSpec

            salt = node.params.get("salt")
            if salt and need_exchange:
                # Skew path (DrDynamicDistributor analog): spread each
                # key over `salt` destinations — partial-reduce on
                # (key, salt), exchange on (key, salt), re-reduce, then
                # collapse with the normal key-only exchange below.
                salted = carry_cols + ["#salt"]
                stage.ops.append(
                    StageOp("select", dict(slot=slot, fn=_AddSalt(int(salt))))
                )
                stage.ops.append(
                    StageOp("group_reduce", dict(slot=slot, keys=salted, aggs=partial))
                )
                stage.ops.append(StageOp(
                    "exchange_hash",
                    dict(slot=slot, keys=eq_cols + ["#salt"],
                         tree=dict(keys=salted, aggs=final)),
                ))
                stage.ops.append(StageOp("resize", dict(slot=slot, factor=stage.growth)))
                stage.ops.append(
                    StageOp("group_reduce", dict(slot=slot, keys=salted, aggs=final))
                )
            else:
                stage.ops.append(
                    StageOp("group_reduce", dict(slot=slot, keys=carry_cols, aggs=partial))
                )
            if need_exchange:
                if nparts:
                    self.reduced.add(node.id)
                stage.ops.append(StageOp(
                    "exchange_hash",
                    dict(slot=slot, keys=eq_cols, nparts=nparts,
                         tree=dict(keys=carry_cols, aggs=final)),
                ))
                stage.ops.append(StageOp(
                    "resize",
                    dict(slot=slot, factor=stage.growth, nparts=nparts),
                ))
                stage.ops.append(
                    StageOp("group_reduce", dict(slot=slot, keys=carry_cols, aggs=final))
                )
            if means:
                stage.ops.append(StageOp(
                    "select", dict(slot=slot, fn=_FinalizeMeans(means))
                ))
            want = K.group_carry_cols(node.schema, node.schema.names)
            stage.ops.append(StageOp("project", dict(slot=slot, cols=want)))
        self.cursor[node.id] = ("open", stage, slot)

    # -- range ops ---------------------------------------------------------
    def _lower_ranged(self, node: Node, fanout: Dict[int, int]) -> None:
        stage, slot = self._continue_or_start(node, fanout.get(node.inputs[0].id, 1))
        in_schema = node.inputs[0].schema
        keys: List[Tuple[str, bool]] = [
            (kk, bool(d)) for kk, d in node.params["keys"]
        ]
        operands_fn = K.ordering_operands(in_schema, keys)
        src_p = node.inputs[0].partition
        # Exchange elision requires matching *direction* too: ascending
        # and descending ranges are different partitionings.  Bucketing
        # uses the primary operand only and equal primaries colocate, so
        # a matching primary (name, desc) suffices.
        # A spread input (skew-proof order_by) keeps global ORDER but
        # not equal-key colocation, so neither a range_partition (which
        # promises colocation) nor an order_by with different secondary
        # keys (whose local re-sort could not fix a straddling run) may
        # elide its exchange over it.
        spread_ok = (
            node.kind == "order_by" and src_p.ordered_by == tuple(keys)
        )
        already_ranged = (
            src_p.scheme == "range"
            and len(src_p.range_by) > 0
            and src_p.range_by[0] == keys[0]
            and (not src_p.spread or spread_ok)
        )
        if not already_ranged:
            # order_by only needs global ORDER, so its exchange spreads
            # equal keys across partitions (skew-proof, kernels.py
            # _k_exchange_range); range_partition promises equal-key
            # COLOCATION and keeps strict splitters.  The ops are the
            # same at every width (P may be unknown here): on a mesh of
            # one partition the exchange and its resize trace nothing
            # (kernels._elided) and an order_by is its local_sort alone.
            nparts = self._tail_nparts(node.inputs[0])
            if nparts:
                self.reduced.add(node.id)
            stage.ops.append(
                StageOp(
                    "exchange_range",
                    dict(
                        slot=slot, operands_fn=operands_fn,
                        spread=node.kind == "order_by",
                        rate=self.config.sample_rate,
                        nparts=nparts,
                    ),
                )
            )
            stage.ops.append(StageOp(
                "resize", dict(slot=slot, factor=stage.growth, nparts=nparts)
            ))
        if node.kind == "order_by":
            stage.ops.append(
                StageOp("local_sort", dict(slot=slot, operands_fn=operands_fn))
            )
        self.cursor[node.id] = ("open", stage, slot)

    # -- join ---------------------------------------------------------------
    def _lower_join(self, node: Node) -> None:
        left, right = node.inputs
        lref = self._materialize(left)
        rref = self._materialize(right)
        stage = self._new_stage("join", [lref, rref])
        lkeys = K.equality_cols(left.schema, node.params["left_keys"])
        rkeys = K.equality_cols(right.schema, node.params["right_keys"])
        strategy = node.params.get("strategy", "shuffle")
        need_l = self._needs_hash_exchange_for(left, node.params["left_keys"])
        need_r = self._needs_hash_exchange_for(right, node.params["right_keys"])
        strat_params = {}
        if strategy == "shuffle":
            # Static co-partitioning: exchanges are their own stage ops.
            if need_l:
                stage.ops.append(StageOp("exchange_hash", dict(slot=0, keys=lkeys)))
                stage.ops.append(StageOp("resize", dict(slot=0, factor=1.0)))
            if need_r:
                stage.ops.append(StageOp("exchange_hash", dict(slot=1, keys=rkeys)))
                stage.ops.append(StageOp("resize", dict(slot=1, factor=1.0)))
        else:
            # broadcast / auto: the kernel decides at trace time from the
            # right side's static capacity (DrDynamicBroadcastManager
            # analog) and either all_gathers the right side or performs
            # the deferred co-partitioning exchanges itself.
            strat_params = dict(
                strategy=strategy,
                need_left_exchange=need_l,
                need_right_exchange=need_r,
                broadcast_limit=self.config.broadcast_limit,
                # statically-bounded right-side ROW count (None =
                # unbounded): lets the auto broadcast decision use
                # observed-data-size bounds instead of raw capacity
                # (DynamicManager.cs:51 decides from actual size)
                est_right=self.est.get(right.id),
            )
        jk = node.params.get("join_kind", "inner")
        if jk == "count":
            stage.ops.append(
                StageOp(
                    "group_join_count",
                    dict(
                        left_slot=0,
                        right_slot=1,
                        left_keys=lkeys,
                        right_keys=rkeys,
                        out=node.params["out"],
                        expansion=node.params.get("expansion", 1.0),
                        **strat_params,
                    ),
                )
            )
        elif jk == "ranked":
            order = node.params.get("order")
            operands_fn = (
                K.ordering_operands(right.schema, list(order)) if order else None
            )
            stage.ops.append(
                StageOp(
                    "join_ranked",
                    dict(
                        left_slot=0,
                        right_slot=1,
                        left_keys=lkeys,
                        right_keys=rkeys,
                        rank_out=node.params["rank_out"],
                        operands_fn=operands_fn,
                        expansion=node.params.get("expansion", 1.0),
                        suffix=node.params.get("suffix", "_r"),
                        rank_limit=node.params.get("rank_limit"),
                        rank_limit_max_boost=2 ** self.config.max_shuffle_retries,
                        **strat_params,
                    ),
                )
            )
            stage.growth = max(1.0, node.params.get("expansion", 1.0))
        elif jk in ("inner", "left"):
            stage.ops.append(
                StageOp(
                    "join",
                    dict(
                        left_slot=0,
                        right_slot=1,
                        left_keys=lkeys,
                        right_keys=rkeys,
                        expansion=node.params.get("expansion", 1.0),
                        suffix=node.params.get("suffix", "_r"),
                        outer=(jk == "left"),
                        right_defaults=node.params.get("right_defaults"),
                        **strat_params,
                    ),
                )
            )
            stage.growth = max(1.0, node.params.get("expansion", 1.0)) + (
                1.0 if jk == "left" else 0.0
            )
        else:
            stage.ops.append(
                StageOp(
                    "semi",
                    dict(
                        left_slot=0,
                        right_slot=1,
                        left_keys=lkeys,
                        right_keys=rkeys,
                        negate=(jk == "anti"),
                        expansion=node.params.get("expansion", 1.0),
                        **strat_params,
                    ),
                )
            )
        self.cursor[node.id] = ("open", stage, 0)

    def _needs_hash_exchange_for(self, src: Node, keys: Sequence[str]) -> bool:
        # A fan-REDUCED hash layout (mod P_stage < P) is key-colocated
        # but NOT co-partitioned with a full-width side — a join must
        # re-exchange it (group_by elision over it stays safe and is
        # handled by _needs_hash_exchange).
        if src.id in self.reduced:
            return True
        p = src.partition
        return not (p.scheme == "hash" and tuple(p.keys) == tuple(keys))


def _decompose_aggs(aggs):
    """Builtin combiner decomposition: local partial + post-shuffle final.

    The Seed/Accumulate/RecursiveAccumulate split for builtin aggregates
    (reference ``DryadLinqDecomposition.cs:34``): count becomes local
    count + final sum; mean becomes (sum, count) partials + final divide.

    A mean carries nothing another aggregate already carries: its count
    is the query's ``count`` where there is one (else one count serves
    every mean), and a ``mean64``'s sum is the ``sum64`` of the same
    column where the query asks for that too.  What a fold moves a slot
    is its channels (``agg_state_words`` on the stage's ``dispatch``
    span), so TPC-H Q1's three averages beside its four sums and its
    count cost one more 64-bit channel, not three and three counts.
    Returns ``(partial, final, means)``: ``means`` is what
    :class:`_FinalizeMeans` reads, ``(out, sum channel, count channel,
    scale)`` with ``scale`` None for a sum of one word.
    """
    from dryad_tpu.ops.segmented import AggSpec

    partial, final = [], []
    made = {}  # (op, col) -> the channel that carries it

    def carry(op, col, out):
        made.setdefault((op, col), out)
        partial.append(AggSpec(op, col, out))
        if op == "count":
            final.append(AggSpec("sum", out, out))
        elif op in ("sum64", "min64", "max64"):
            # partial writes out#h0/out#h1; final re-reduces that pair
            final.append(AggSpec(op, f"{out}#h0", out))
        else:
            final.append(AggSpec(op, out, out))

    for a in aggs:
        if a.op in ("sum", "count", "min", "max", "first", "any", "all",
                    "sum64", "min64", "max64"):
            carry(a.op, a.col, a.out)
        elif a.op not in ("mean", "mean64"):
            raise ValueError(f"unknown agg op {a.op!r}")
    means = []
    for a in aggs:
        if a.op not in ("mean", "mean64"):
            continue
        if ("count", None) not in made:
            carry("count", None, f"{a.out}#c")
        op = "sum64" if a.op == "mean64" else "sum"
        if (op, a.col) not in made:
            carry(op, a.col, f"{a.out}#s")
        means.append((
            a.out, made[(op, a.col)], made[("count", None)],
            a.scale if a.op == "mean64" else None,
        ))
    return partial, final, tuple(means)


class _AddSalt:
    """Row fn appending the #salt spread column; VALUE-equal so
    re-lowering doesn't bust the compiled-stage cache."""

    def __init__(self, salt: int):
        self.salt = salt

    def __eq__(self, other) -> bool:
        return type(other) is _AddSalt and other.salt == self.salt

    def __hash__(self) -> int:
        return hash(("_AddSalt", self.salt))

    def __call__(self, cols):
        import jax.numpy as jnp

        n = next(iter(cols.values())).shape[0]
        out = dict(cols)
        out["#salt"] = jnp.arange(n, dtype=jnp.int32) % jnp.int32(self.salt)
        return out


class _FinalizeMeans:
    """Post-shuffle mean finalize (sum / count -> mean; a 64-bit sum
    decodes its word pair to f32 first and a DECIMAL's divides its
    scale away); VALUE-equal so re-lowering doesn't bust the
    compiled-stage cache.  ``means``: :func:`_decompose_aggs`'s.  The
    channels stay in the batch (another output may be one of them); the
    ``project`` that follows keeps the schema's columns."""

    def __init__(self, means):
        self.means = tuple(means)

    def __eq__(self, other) -> bool:
        return type(other) is _FinalizeMeans and other.means == self.means

    def __hash__(self) -> int:
        return hash(("_FinalizeMeans", self.means))

    def __call__(self, cols):
        import jax.numpy as jnp

        from dryad_tpu.ops.segmented import pair_mean

        out = dict(cols)
        for name, s, c, scale in self.means:
            if scale is None:
                count = jnp.maximum(cols[c].astype(jnp.float32), 1.0)
                out[name] = cols[s].astype(jnp.float32) / count
            else:
                out[name] = pair_mean(
                    cols[f"{s}#h0"], cols[f"{s}#h1"], cols[c], scale
                )
        return out


def _rewrite_topk(roots: Sequence[Node], limit: int) -> List[Node]:
    """Plan rewrite (the ``SimpleRewriter.cs`` Phase-1 analog):
    ``take(n)`` over a sole-consumer ``order_by`` becomes one fused
    ``topk`` node — per-partition top-n + an ``all_gather`` of the P
    heads + a final local sort, instead of a full range exchange of the
    whole dataset.  Applied only for n <= ``limit`` (the gathered head
    array is P*n rows on every partition)."""
    fanout = consumers(roots)
    memo: Dict[int, Node] = {}

    def rb(node: Node) -> Node:
        if node.id in memo:
            return memo[node.id]
        new_inputs = [rb(i) for i in node.inputs]
        src = node.inputs[0] if node.inputs else None
        if (
            node.kind == "take"
            and src is not None
            and src.kind == "order_by"
            and fanout.get(src.id, 1) == 1
            and 0 < node.params["n"] <= limit
        ):
            ob = new_inputs[0]
            ks = [(kk, bool(d)) for kk, d in ob.params["keys"]]
            nn = Node(
                "topk", [ob.inputs[0]], node.schema,
                PartitionInfo.ranged(ks, ks, spread=True),
                keys=ks, n=node.params["n"],
            )
        elif all(ni is oi for ni, oi in zip(new_inputs, node.inputs)):
            nn = node
        else:
            nn = Node(
                node.kind, new_inputs, node.schema, node.partition,
                **node.params,
            )
        memo[node.id] = nn
        return nn

    return [rb(r) for r in roots]


def lower(
    roots: Sequence[Node], config, dictionary=None, P: Optional[int] = None
) -> StageGraph:
    """Lower a logical DAG to a stage graph (Phase 2+3).

    ``dictionary``: the context StringDictionary, enabling the
    auto-dense STRING group_by rewrite (codes against its entries).
    ``P``: mesh partition count when known — lets the fan-out
    adaptation skip no-op reductions at or above the mesh width."""
    b = _Builder(config, dictionary, P)
    rewritten = _rewrite_topk(roots, getattr(config, "topk_limit", 1024))
    fanout = consumers(rewritten)
    for node in walk(rewritten):
        b.lower_node(node, fanout)
    # outputs stay keyed by the CALLER's root ids (rewrites rebuild
    # nodes, but callers look up query.node.id)
    outputs: Dict[int, Tuple[int, int]] = {}
    for orig, r in zip(roots, rewritten):
        outputs[orig.id] = b._materialize(r)
    return StageGraph(b.stages, outputs, b.plan_inputs)
