"""Whole-DAG SPMD fusion: one compiled program per multi-stage plan.

Phase-2 lowering (``plan/lower.py``) already fuses maximal *operator
chains* into stages, but the executor still dispatches every stage as
its own compiled program with the driver mediating each boundary — one
compile key, one dispatch latency, and one control round-trip per
stage.  The reference Dryad pays a process +
channel boundary between every stage pair (N*M file/HTTP channels per
exchange, ``channelinterface.h``); our intra-stage shuffles are already
on-device ``all_to_all`` ops (``ops/shuffle.py``), so the remaining
lever is the *inter-stage* boundary.

This pass stitches a maximal run of consecutive device-eligible stages
— including their hash/range exchanges — into a single
:class:`FusedStage` whose body chains the per-stage kernels inside ONE
``shard_map`` region (``exec.kernels.build_fused_fn`` /
``parallel.stage.compile_fused``), compiled once and dispatched once.
Intermediates stay in HBM for the whole region; exchanges at the seams
ride the same mesh collectives as intra-stage exchanges (hybrid-mesh
plans keep the ICI-hop -> combine -> one-DCN-hop tree decomposition of
PAPERS.md arxiv 2112.01075 through the per-member tree kernels).

Fusion eligibility (a seam BREAKS, with a recorded
``fuse_break_reason``, when any rule fails):

- every op in the run must be a device kernel from :data:`FUSABLE_OPS`
  (``apply_host`` / ``do_while`` stages are driver-evaluated host
  boundaries — ``host_boundary:*``);
- a stage shaped for observed-volume width adaptation (all ops
  width-insensitive, a full-width exchange, statically-unbounded
  non-plan inputs, and a shrinking producer) stays UNFUSED so the
  executor's runtime re-widthing (``DrDynamicRangeDistributor.cpp:54``
  semantics) still applies — fusing it would pin the region to the
  static width (``width_adapt:*``).

Overflow contract: any member's bucket-overflow flag retries the WHOLE
region at the next palette capacity — the same bounded-palette shape
contract as the single-stage path, so a fused plan stays byte-identical
to the staged baseline (the ``plan_fuse=False`` differential).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from dryad_tpu.plan.lower import Stage, StageGraph, _stage_ids

# Stage-op kinds the fuser admits into a fused region.  Every entry
# MUST have a registered device kernel (``exec.kernels._KERNELS``) —
# the AST lint ``tests/test_fuse_lint.py`` enforces the subset relation
# in both directions, so a new device kernel extends fusion coverage
# (or is consciously excluded here) instead of silently rotting.
FUSABLE_OPS = frozenset({
    "select", "where", "project", "seed", "select_many", "apply",
    "exchange_hash", "exchange_range", "resize",
    "group_reduce", "group_reduce_dense", "string_code",
    "group_combine", "distinct", "local_sort", "topk",
    "join", "semi", "concat", "take", "with_rank", "skip", "tail",
    "take_while", "skip_while", "reverse", "default_if_empty",
    "scalar_agg", "fork", "group_join_count", "join_ranked", "zip",
    "sliding_window",
})

# Driver-evaluated stages: hard host boundaries no region may cross.
DRIVER_OPS = frozenset({"do_while", "apply_host"})

# Op kinds proven width-insensitive — the observed-volume width
# adapter may re-dispatch a stage of only these at a reduced fan
# (``exec.executor`` consumes this set; ONE definition for the pass's
# adapt-seam rule and the executor's runtime gate).
ADAPT_OK_OPS = frozenset({
    "select", "where", "project", "exchange_hash", "exchange_range",
    "resize", "group_reduce", "group_reduce_dense", "local_sort",
    "join", "scalar_agg", "string_code",
})

# Aggregation-shaped ops that shrink data by orders of magnitude — the
# producers whose observed output makes width adaptation worth a sync.
SHRINKING_OPS = frozenset({
    "group_reduce", "group_reduce_dense", "distinct", "scalar_agg",
    "topk",
})


class FusedStage:
    """A run of stages compiled and dispatched as ONE SPMD program.

    Duck-types the :class:`~dryad_tpu.plan.lower.Stage` surface the
    executor consumes (``id``/``name``/``input_refs``/``ops``/
    ``out_slots``/``growth``) plus the region structure:

    - ``members``: the fused stages, in topological (list) order;
    - ``wiring``: per member, one entry per member input ref —
      ``("ext", j)`` binds the region's external input ``j``,
      ``("mem", mi, oi)`` binds output ``oi`` of ``members[mi]``;
    - ``exports``: ``(member_index, out_index)`` pairs, in region
      output order — the member outputs consumed outside the region
      (or by the plan's roots).

    ``ops`` chains the member ops so structural scans (overflow
    capability, miss guards, operand enumeration, fault-name tokens)
    see the whole region; member-local slot numbers overlap, so any
    *identity* derivation (compile keys, checkpoint fingerprints) must
    also fold ``wiring``/``exports``/member boundaries — see
    ``fingerprint_extra`` and the executor's fused ``_stage_key``.
    """

    def __init__(
        self,
        members: List[Stage],
        input_refs: List[Tuple[Any, int]],
        wiring: List[Tuple[Tuple, ...]],
        exports: List[Tuple[int, int]],
    ):
        self.id = next(_stage_ids)
        # "+"-token name so fault injection (exec.faults token match),
        # stage statistics, and metric labels keep working per op kind
        seen: Dict[str, None] = {}
        for m in members:
            for tok in m.name.split("+"):
                seen.setdefault(tok)
        self.name = "+".join(seen)
        self.members = members
        self.input_refs = input_refs
        self.wiring = wiring
        self.exports = exports
        self.out_slots = list(range(len(exports)))
        self.growth = max((m.growth for m in members), default=1.0)
        self.ops = [op for m in members for op in m.ops]

    @property
    def fingerprint_extra(self) -> str:
        """Region structure for the checkpoint identity: chained op
        params alone cannot distinguish two regions that partition the
        same op sequence differently or wire members differently."""
        return (
            f"fused:members={[(len(m.ops), tuple(m.out_slots)) for m in self.members]!r}"
            f":wiring={self.wiring!r}:exports={self.exports!r}"
        )

    def __repr__(self) -> str:
        return (
            f"FusedStage(id={self.id}, members="
            f"{[m.id for m in self.members]}, exports={self.exports})"
        )


@dataclasses.dataclass
class FuseReport:
    """What fused and why seams broke — the explain/debug surface."""

    enabled: bool
    # one entry per dispatch unit, in dispatch order:
    # {"id", "members": [stage ids], "names": [...], "fused": bool,
    #  "reason": Optional[str]}  (reason set on unfused singletons)
    regions: List[Dict[str, Any]]
    # {"after": stage id, "before": stage id, "reason": str} per
    # consecutive-stage boundary that did NOT fuse
    breaks: List[Dict[str, Any]]
    n_stages: int
    n_dispatch_units: int


def _ineligible_reason(stage: Stage) -> Optional[str]:
    """None when every op is fusable; else the seam-break reason."""
    for op in stage.ops:
        if op.kind in DRIVER_OPS:
            return f"host_boundary:{op.kind}"
        if op.kind not in FUSABLE_OPS:
            return f"unsupported_op:{op.kind}"
    return None


def _is_shrinker(stage: Stage) -> bool:
    return any(op.kind in SHRINKING_OPS for op in stage.ops)


def _adaptable_shape(stage: Stage) -> bool:
    """Mirror of the executor's ``_adaptable``: all ops
    width-insensitive and at least one full-width exchange."""
    return all(op.kind in ADAPT_OK_OPS for op in stage.ops) and any(
        op.kind in ("exchange_hash", "exchange_range")
        and not op.params.get("nparts")
        for op in stage.ops
    )


def _adapt_candidate(
    stage: Stage, by_id: Dict[int, Stage], config, single_axis: bool
) -> bool:
    """True when the staged executor could re-dispatch ``stage`` at an
    observed-volume-reduced width: fusing it into any region would pin
    it to the static full width, so the pass leaves it alone (seam
    reason ``width_adapt``)."""
    if not single_axis or not getattr(config, "tail_fanout_rows", 0):
        return False
    if not _adaptable_shape(stage):
        return False
    producers = []
    for ref, _idx in stage.input_refs:
        if ref == "plan_input":
            return False  # static bindings: lowering already decided
        p = by_id.get(ref)
        if p is None:
            return False
        producers.append(p)
    return any(_is_shrinker(p) for p in producers)


def fuse(
    graph: StageGraph, config, single_axis: bool = True
) -> Tuple[StageGraph, FuseReport]:
    """Group maximal runs of consecutive device-eligible stages into
    :class:`FusedStage` regions and rewire the graph.

    Stages appear in ``graph.stages`` in topological order (lowering
    materializes producers before consumers), so ANY contiguous run is
    a valid region: every external input is produced before the region
    dispatches and every external consumer runs after it.

    Returns the (possibly) rewired graph plus a :class:`FuseReport`;
    with fewer than two fusable neighbors the graph passes through
    untouched.
    """
    by_id = {s.id: s for s in graph.stages}
    # classify: None = fusable; a string = unfused singleton + reason
    cls: Dict[int, Optional[str]] = {}
    for s in graph.stages:
        reason = _ineligible_reason(s)
        if reason is None and _adapt_candidate(s, by_id, config, single_axis):
            reason = "width_adapt:observed-volume adaptation opportunity"
        cls[s.id] = reason

    # group consecutive unclassified stages into runs
    runs: List[List[Stage]] = []
    cur: List[Stage] = []
    for s in graph.stages:
        if cls[s.id] is None:
            cur.append(s)
        else:
            if cur:
                runs.append(cur)
                cur = []
            runs.append([s])
    if cur:
        runs.append(cur)

    breaks: List[Dict[str, Any]] = []
    for a, b in zip(graph.stages, graph.stages[1:]):
        if cls[a.id] is None and cls[b.id] is None:
            continue  # same run — fused together (or lone pair edge)
        breaks.append({
            "after": a.id,
            "before": b.id,
            "reason": cls[b.id] or cls[a.id] or "single_stage",
        })

    # (producer sid, out idx) pairs consumed by the plan roots
    root_refs = set(graph.outputs.values())

    new_stages: List[Any] = []
    regions: List[Dict[str, Any]] = []
    # (old sid, out idx) -> (new sid, new out idx) for fused members
    remap: Dict[Tuple[int, int], Tuple[int, int]] = {}

    def _remap_ref(ref, idx):
        if ref == "plan_input":
            return (ref, idx)
        return remap.get((ref, idx), (ref, idx))

    for run in runs:
        if len(run) < 2 or cls[run[0].id] is not None:
            for s in run:
                if any((r, i) in remap for r, i in s.input_refs if r != "plan_input"):
                    s = Stage(
                        s.id, s.name,
                        [_remap_ref(r, i) for r, i in s.input_refs],
                        ops=s.ops, out_slots=s.out_slots, growth=s.growth,
                    )
                new_stages.append(s)
                regions.append({
                    "id": s.id, "members": [s.id], "names": [s.name],
                    "fused": False, "reason": cls[s.id],
                })
            continue

        member_pos = {m.id: i for i, m in enumerate(run)}
        member_set = set(member_pos)
        ext_refs: List[Tuple[Any, int]] = []
        ext_index: Dict[Tuple[Any, int], int] = {}
        wiring: List[Tuple[Tuple, ...]] = []
        for m in run:
            w: List[Tuple] = []
            for ref, idx in m.input_refs:
                if ref != "plan_input" and ref in member_set:
                    w.append(("mem", member_pos[ref], idx))
                    continue
                key = _remap_ref(ref, idx)
                if key not in ext_index:
                    ext_index[key] = len(ext_refs)
                    ext_refs.append(key)
                w.append(("ext", ext_index[key]))
            wiring.append(tuple(w))

        consumed_outside = set()
        for s in graph.stages:
            if s.id in member_set:
                continue
            for ref, idx in s.input_refs:
                if ref != "plan_input" and ref in member_set:
                    consumed_outside.add((ref, idx))
        exports: List[Tuple[int, int]] = []
        for mi, m in enumerate(run):
            for oi in range(len(m.out_slots)):
                if (m.id, oi) in consumed_outside or (m.id, oi) in root_refs:
                    exports.append((mi, oi))
        if not exports:  # defensive: a dead-tail region still yields
            exports = [
                (len(run) - 1, oi)
                for oi in range(len(run[-1].out_slots))
            ]

        fused = FusedStage(run, ext_refs, wiring, exports)
        for pos, (mi, oi) in enumerate(exports):
            remap[(run[mi].id, oi)] = (fused.id, pos)
        new_stages.append(fused)
        regions.append({
            "id": fused.id, "members": [m.id for m in run],
            "names": [m.name for m in run], "fused": True, "reason": None,
        })

    outputs = {
        nid: _remap_ref(ref, idx) for nid, (ref, idx) in graph.outputs.items()
    }
    report = FuseReport(
        enabled=True, regions=regions, breaks=breaks,
        n_stages=len(graph.stages), n_dispatch_units=len(new_stages),
    )
    return StageGraph(new_stages, outputs, graph.inputs), report
