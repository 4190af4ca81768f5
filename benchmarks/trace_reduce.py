"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time as the union of operation intervals,
idle share, time per operation, each job's lead / device / tail
seconds (the harness's ``bench:fresh`` and ``bench:requery``
annotations around ``Query.collect()`` against the operations that
began inside them), and idle gaps summed by where in a job they fell.

The reduction works on a plain :class:`Trace` (lists of tuples), so
its arithmetic is checked without a chip; :func:`load` is the one
place that knows how the profiler lays a TPU trace out.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench:"
WINDOW_ANNOTATION = "bench:window"
BETWEEN_JOBS = "bench:between_jobs"
COLLECTIVES = (
    "all-to-all", "all-reduce", "all-gather", "reduce-scatter",
    "collective-permute", "collective-broadcast",
)

Op = Tuple[str, float, float]  # (label, start_s, end_s)


@dataclasses.dataclass
class Trace:
    """``ops``: chip id -> operations on that chip; ``annotations``:
    the host's ``bench:*`` spans; all times in seconds on one clock."""

    ops: Dict[int, List[Op]]
    annotations: List[Op]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_label(name: str, stats: dict) -> str:
    """``fusion.4 custom fusion gather f32[8388608]``: the instruction's
    own name (the text before `` = `` in the HLO line the profiler names
    the event by), what the profiler's metadata says it is
    (``hlo_category``), the jax primitive it came from (the last part
    of ``tf_op``) and its shape without layouts."""
    short = name.split(" = ", 1)[0].lstrip("%")
    primitive = str(stats.get("tf_op", "")).rstrip(":").rsplit("/", 1)[-1]
    shape = re.sub(r"\{[^}]*\}", "", str(stats.get("shape_with_layout", "")))
    parts = (short, str(stats.get("hlo_category", "")), primitive, shape[:48])
    return " ".join(p for p in parts if p)


def load(path: str) -> Trace:
    """Read device operations (every ``/device:TPU:<n>`` plane's
    ``XLA Ops`` line: what the TensorCore ran, not the asynchronous
    copies beside it) and the host's ``bench:*`` annotations."""
    import xplane

    ops: Dict[int, List[Op]] = {}
    annotations: List[Op] = []
    for plane in xplane.read(path):
        if plane["name"].startswith(DEVICE_PLANE):
            chip = int(plane["name"][len(DEVICE_PLANE):].split()[0])
            for line in plane["lines"]:
                if line["name"] == OPS_LINE:
                    ops.setdefault(chip, []).extend(
                        (op_label(name, stats), start, end)
                        for name, start, end, stats in line["events"])
        elif plane["name"] == HOST_PLANE:
            annotations.extend(
                (name, start, end)
                for line in plane["lines"]
                for name, start, end, _ in line["events"]
                if name.startswith(ANNOTATION_PREFIX))
    return Trace(ops, annotations)


# -- interval arithmetic -----------------------------------------------------

def union(intervals) -> List[Tuple[float, float]]:
    """Disjoint, sorted union of ``(start, end)`` intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def complement(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The gaps of a disjoint sorted ``busy`` inside ``[lo, hi]``."""
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def self_times(ops: List[Op]) -> Dict[str, float]:
    """Seconds per label with nested operations taken out of their
    parents (a ``while`` spans its body's operations): every instant
    of a chip's line is charged to the innermost operation."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [label, end, child_seconds, start]

    def close(entry):
        label, end, inner, start = entry
        out[label] = out.get(label, 0.0) + max(0.0, (end - start) - inner)
        if stack:
            stack[-1][2] += end - start

    for label, start, end in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and start >= stack[-1][1]:
            close(stack.pop())
        if stack:
            end = min(end, stack[-1][1])
        stack.append([label, end, 0.0, start])
    while stack:
        close(stack.pop())
    return out


def job_phases(ops: List[Op], start: float, end: float):
    """One job span against one chip's operations: ``(lead, device,
    tail)`` seconds -- the span's start to its first operation, that
    operation's start to its last operation's end, and from there to
    the span's end.  An operation belongs to the span that holds its
    midpoint: in a trace the device's clock sits a few milliseconds
    off the host's, so when jobs run back to back the edge of one
    job's program falls inside its neighbour's span, and a time read
    here is good to those few milliseconds.  ``None`` when the span
    holds no operation."""
    mine = [(s, e) for _, s, e in ops if start <= (s + e) / 2 < end]
    if not mine:
        return None
    first = max(start, min(s for s, _ in mine))
    last = min(end, max(e for _, e in mine))
    return first - start, last - first, end - last


def is_job(annotation: str) -> bool:
    """``bench:fresh``, ``bench:requery``, or a later job kind: one
    colon.  The window, the time between jobs and deeper names
    (``bench:fresh:...``) are not jobs."""
    return (annotation.count(":") == 1
            and annotation not in (WINDOW_ANNOTATION, BETWEEN_JOBS))


def is_collective(label: str) -> bool:
    """By the instruction's name or its category (``all-to-all.3``,
    ``all-reduce-start``, a fusion whose category names a collective)."""
    return any(word.startswith(COLLECTIVES) for word in label.split())


def is_gather(label: str) -> bool:
    """An operation that came from jax's ``gather`` primitive."""
    return "gather" in label.split()


def reduce(trace: Trace) -> dict:
    """The summary the metric readers take:

    ``window_s``      length of the ``bench:window`` annotation
    ``chips``         chips with at least one operation in the window
    ``busy_s``        union of operation intervals, mean over chips
    ``idle_share``    1 - busy_s / window_s
    ``op_s``          label -> self seconds, mean over chips
    ``busy_in``       job annotation (``bench:fresh``, ``bench:requery``)
                      -> busy seconds, one entry per instance
    ``phases``        job annotation -> ``(lead, device, tail)`` seconds,
                      one entry per instance that ran an operation
                      (:func:`job_phases`), mean over chips
    ``gap_s``         where the device idled: ``<job annotation>:lead``
                      (before the job's first operation), ``:between_ops``,
                      ``:tail`` (after its last), and ``bench:between_jobs``
                      for idle time of the window outside every job
    """
    windows = [a for a in trace.annotations if a[0] == WINDOW_ANNOTATION]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_ANNOTATION} annotation")
    _, lo, hi = windows[0]
    chips = sorted(c for c, ops in trace.ops.items()
                   if clip([(s, e) for _, s, e in ops], lo, hi))
    if not chips:
        raise ValueError("no operation ran on a device inside the window")
    n = len(chips)
    jobs: Dict[str, List[Tuple[float, float]]] = {}
    for name, s0, e0 in sorted(trace.annotations, key=lambda a: a[1]):
        if is_job(name) and e0 > lo and s0 < hi:
            jobs.setdefault(name, []).append((max(s0, lo), min(e0, hi)))
    busy_s = 0.0
    op_s: Dict[str, float] = {}
    gap_s: Dict[str, float] = {}
    busy_in = {name: [0.0] * len(spans) for name, spans in jobs.items()}
    phase_sums = {name: [None] * len(spans) for name, spans in jobs.items()}

    def idle(name: str, gaps, s: float, e: float) -> float:
        sec = length(clip(gaps, s, e))
        if sec > 0:
            gap_s[name] = gap_s.get(name, 0.0) + sec / n
        return sec

    for chip in chips:
        ops = [(label, max(s, lo), min(e, hi)) for label, s, e in trace.ops[chip]
               if min(e, hi) > max(s, lo)]
        busy = union((s, e) for _, s, e in ops)
        busy_s += length(busy) / n
        for label, sec in self_times(ops).items():
            op_s[label] = op_s.get(label, 0.0) + sec / n
        gaps = complement(busy, lo, hi)
        in_jobs = 0.0
        for name, spans in jobs.items():
            for i, (s0, e0) in enumerate(spans):
                busy_in[name][i] += length(clip(busy, s0, e0)) / n
                phases = job_phases(ops, s0, e0)
                if phases is None:
                    in_jobs += idle(f"{name}:lead", gaps, s0, e0)
                    continue
                first, last = s0 + phases[0], s0 + phases[0] + phases[1]
                in_jobs += idle(f"{name}:lead", gaps, s0, first)
                in_jobs += idle(f"{name}:between_ops", gaps, first, last)
                in_jobs += idle(f"{name}:tail", gaps, last, e0)
                acc = phase_sums[name][i] or (0.0, 0.0, 0.0, 0)
                phase_sums[name][i] = tuple(
                    a + b for a, b in zip(acc, phases + (1,)))
        rest = length(gaps) - in_jobs
        if rest > 1e-9:
            gap_s[BETWEEN_JOBS] = gap_s.get(BETWEEN_JOBS, 0.0) + rest / n
    window_s = hi - lo
    return {
        "window_s": window_s, "chips": n, "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "op_s": op_s, "gap_s": gap_s, "busy_in": busy_in,
        "phases": {name: [tuple(v / acc[3] for v in acc[:3])
                          for acc in sums if acc]
                   for name, sums in phase_sums.items()},
    }


def top(table: Dict[str, float], n: int = 10) -> List[list]:
    """The ``n`` largest entries as ``[name, seconds]`` pairs."""
    return [[k, v] for k, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]
