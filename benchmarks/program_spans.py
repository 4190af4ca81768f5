"""The program's own spans and operator scopes, read from the traced
run's xplane (see TRACING.md).

Since PR 24 every ``Tracer.span`` of the program is also a
``TraceAnnotation`` named ``dryad:<phase>:<name>`` on the profiler's
host plane, with ``span_id``, ``parent_id``, ``qid`` and its numeric
fields (``bytes``, ``rows``, ``capacity`` ...) as stats, and every
device operation a kernel produced carries ``dryad.<operator>`` in its
``tf_op`` path.  This file reduces both to what the per-layer metric
readers take:

- the host spans, each placed in the ``bench:fresh`` / ``bench:requery``
  span that contains it (host clock against host clock), with self
  time = duration minus the children's by ``parent_id``;
- device busy seconds by scope (self times as ``trace_reduce`` counts
  them, so a ``while`` is not counted over its body);
- the window's device-idle seconds by the innermost program span open
  at each instant, and what no span covers.

A program without the spans or the scopes (the parent of PR 24, or a
program that came back from a compilation cache written before the
scopes: the cache's key leaves names out) gives ``None`` there, never 0.

The reduction works on the plain planes ``xplane.read`` returns, so its
arithmetic is checked on hand-built planes without a chip.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import statistics
from typing import Dict, List, Optional, Tuple

import trace_reduce as TR

SPAN_PREFIX = "dryad:"
SCOPE_PREFIX = "dryad."
UNSCOPED = "(no scope)"
UNNAMED = "(no span)"


@dataclasses.dataclass
class Span:
    """One ``dryad:<phase>:<name>`` event of the host plane."""

    name: str
    start: float
    end: float
    stats: dict
    job: Optional[Tuple[str, int]] = None  # ("bench:fresh", 2)
    self_s: float = 0.0

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Summary:
    spans: List[Span]  # inside the window, by start
    jobs: Dict[str, List[Tuple[float, float]]]  # kind -> spans of the window
    busy_s: float  # mean over chips
    scope_s: Optional[Dict[str, float]]  # scope path -> self seconds
    idle_s: float  # mean over chips
    idle_by_span: Optional[Dict[str, float]]  # span name -> idle seconds

    def of_job(self, kind: str) -> List[List[Span]]:
        """The spans of every job of one kind, a list a job."""
        out: List[List[Span]] = [[] for _ in self.jobs.get(kind, [])]
        for span in self.spans:
            if span.job is not None and span.job[0] == kind:
                out[span.job[1]].append(span)
        return out


# -- from planes to spans and operations --------------------------------------

def host_events(planes) -> Tuple[List[Span], List[TR.Op]]:
    """The host plane's ``dryad:*`` events and its ``bench:*`` ones."""
    spans, bench = [], []
    for plane in planes:
        if plane["name"] != TR.HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, end, stats in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    spans.append(Span(name, start, end, stats))
                elif name.startswith(TR.ANNOTATION_PREFIX):
                    bench.append((name, start, end))
    return sorted(spans, key=lambda s: (s.start, -s.end)), bench


def scope_of(tf_op: str) -> str:
    """``jit(dryad_stage)/shard_map/dryad.exchange_hash/dryad.exchange.layout/sort:``
    -> ``dryad.exchange_hash/dryad.exchange.layout``: the parts of an
    operation's name path that the program's scopes put there."""
    parts = [p for p in str(tf_op).rstrip(":").split("/")
             if p.startswith(SCOPE_PREFIX)]
    return "/".join(parts) if parts else UNSCOPED


def device_ops(planes) -> Dict[int, List[TR.Op]]:
    """Chip -> its ``XLA Ops`` line, each operation labelled by its
    scope path (:func:`scope_of`)."""
    ops: Dict[int, List[TR.Op]] = {}
    for plane in planes:
        if not plane["name"].startswith(TR.DEVICE_PLANE):
            continue
        chip = int(plane["name"][len(TR.DEVICE_PLANE):].split()[0])
        for line in plane["lines"]:
            if line["name"] == TR.OPS_LINE:
                ops.setdefault(chip, []).extend(
                    (scope_of(stats.get("tf_op", "")), start, end)
                    for _, start, end, stats in line["events"])
    return ops


# -- the reduction --------------------------------------------------------------

def place(spans: List[Span], jobs: Dict[str, List[Tuple[float, float]]]) -> None:
    """Each span into the job whose interval contains it."""
    for span in spans:
        for kind, intervals in jobs.items():
            for i, (lo, hi) in enumerate(intervals):
                if lo <= span.start and span.end <= hi:
                    span.job = (kind, i)


def self_seconds(spans: List[Span]) -> None:
    """``self_s`` = a span's duration minus its children's, by
    ``parent_id``."""
    by_id = {s.stats.get("span_id"): s for s in spans}
    for span in spans:
        span.self_s = span.seconds
    for span in spans:
        parent = by_id.get(span.stats.get("parent_id"))
        if parent is not None and parent is not span:
            parent.self_s -= span.seconds


def leaf_intervals(spans: List[Span]) -> List[Tuple[float, float, str]]:
    """``(start, end, name)`` of every stretch in which a span is open
    with no child of its own open: the program's innermost span at each
    instant.  Children by ``parent_id``; a span with no parent in the
    list is a root."""
    ids = {s.stats.get("span_id") for s in spans}
    children: Dict[object, List[Span]] = {}
    for span in spans:
        parent = span.stats.get("parent_id")
        if parent in ids and parent != span.stats.get("span_id"):
            children.setdefault(parent, []).append(span)
    out = []
    for span in spans:
        covered = TR.union(
            (c.start, c.end) for c in children.get(span.stats.get("span_id"), []))
        for lo, hi in TR.complement(TR.clip(covered, span.start, span.end),
                                    span.start, span.end):
            out.append((lo, hi, span.name))
    return out


def reduce(planes) -> Summary:
    """Everything the readers take, from one traced window."""
    spans, bench = host_events(planes)
    windows = [a for a in bench if a[0] == TR.WINDOW_ANNOTATION]
    if not windows:
        raise ValueError(f"the trace holds no {TR.WINDOW_ANNOTATION} annotation")
    _, lo, hi = windows[0]
    jobs: Dict[str, List[Tuple[float, float]]] = {}
    for name, s0, e0 in sorted(bench, key=lambda a: a[1]):
        if TR.is_job(name) and s0 >= lo and e0 <= hi:
            jobs.setdefault(name, []).append((s0, e0))
    spans = [s for s in spans if s.start >= lo and s.end <= hi]
    place(spans, jobs)
    self_seconds(spans)

    ops = {chip: [(label, max(s, lo), min(e, hi)) for label, s, e in chip_ops
                  if min(e, hi) > max(s, lo)]
           for chip, chip_ops in device_ops(planes).items()}
    ops = {chip: chip_ops for chip, chip_ops in ops.items() if chip_ops}
    n = max(1, len(ops))
    scope_s: Dict[str, float] = {}
    busy_s = idle_s = 0.0
    idle_by: Dict[str, float] = {}
    leaves = leaf_intervals(spans)
    for chip_ops in ops.values():
        for label, sec in TR.self_times(chip_ops).items():
            scope_s[label] = scope_s.get(label, 0.0) + sec / n
        busy = TR.union((s, e) for _, s, e in chip_ops)
        busy_s += TR.length(busy) / n
        gaps = TR.complement(busy, lo, hi)
        idle_s += TR.length(gaps) / n
        for s, e, name in leaves:
            sec = TR.length(TR.clip(gaps, s, e))
            if sec > 0:
                idle_by[name] = idle_by.get(name, 0.0) + sec / n
    if not ops:  # no device plane: the whole window, charged to nothing
        idle_s = hi - lo
    scoped = any(label != UNSCOPED for label in scope_s)
    idle_by[UNNAMED] = max(0.0, idle_s - sum(idle_by.values()))
    return Summary(
        spans=spans, jobs=jobs, busy_s=busy_s,
        scope_s=scope_s if scoped else None,
        idle_s=idle_s, idle_by_span=idle_by if spans and ops else None,
    )


# -- what the metric files call ---------------------------------------------------

def under(summary: Summary, prefix: str) -> Optional[float]:
    """Percent of device busy time in operations with a scope that
    starts with ``prefix`` anywhere in their path; ``None`` when no
    operation of the trace carries a scope at all."""
    if summary is None or summary.scope_s is None or summary.busy_s <= 0:
        return None
    sec = sum(s for label, s in summary.scope_s.items()
              if any(part.startswith(prefix) for part in label.split("/")))
    return 100.0 * sec / summary.busy_s


def median_over_jobs(summary: Summary, kind: str, value) -> Optional[float]:
    """Median over the window's jobs of one kind of ``value(spans of
    the job)``; jobs for which it gives ``None`` are left out, and
    ``None`` comes back when none is left."""
    if summary is None:
        return None
    got = [v for v in (value(job) for job in summary.of_job(kind) if job)
           if v is not None]
    return statistics.median(got) if got else None


def named(job: List[Span], *names: str) -> List[Span]:
    """The job's spans called ``dryad:<phase>:<name>`` for one of
    ``names``; a name that ends in ``*`` is a prefix."""
    exact = {n for n in names if not n.endswith("*")}
    prefixes = tuple(n[:-1] for n in names if n.endswith("*"))
    return [s for s in job
            if s.name in exact or (prefixes and s.name.startswith(prefixes))]


def total(spans: List[Span], stat: str) -> float:
    return float(sum(s.stats.get(stat, 0) for s in spans))


def seconds_in(job: List[Span], *names: str) -> Optional[float]:
    """Summed seconds of the job's spans of these names (as
    :func:`named` takes them); ``None`` when the job has none."""
    mine = named(job, *names)
    return sum(s.seconds for s in mine) if mine else None


def capacity_over_rows(job: List[Span]) -> Optional[float]:
    """Slots fetched a row kept, off the job's ``decode`` spans."""
    decoded = named(job, "dryad:decode:decode")
    rows = total(decoded, "rows")
    return total(decoded, "capacity") / rows if rows else None


@functools.lru_cache(maxsize=4)
def _of_trace(trace_dir: str) -> Optional[Summary]:
    import xplane

    try:
        path = TR.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    summary = reduce(xplane.read(path))
    report(summary)
    return summary


def of(cell, metric_file: str) -> Optional[Summary]:
    """The summary of the cell's traced run, found the way ``run.py``
    wrote it: ``<root>/.bench_out/trace-<cell.name>``, ``<root>`` being
    two directories above ``benchmarks/metrics/<metric>.py``.  Read
    once a process; the first read prints the ``[bench] spans``,
    ``scopes`` and ``idle_by_span`` lines.  ``None`` without a trace."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(metric_file))))
    return _of_trace(os.path.join(root, ".bench_out", f"trace-{cell.name}"))


def report(summary: Summary) -> None:
    """Three kinds of ``[bench]`` line, before the result line: the
    median self seconds of every span name a job kind; the share of
    busy per scope, top 10; idle seconds per innermost span."""
    for kind in summary.jobs:
        per_job: Dict[str, List[float]] = {}
        for job in summary.of_job(kind):
            sums: Dict[str, float] = {}
            for span in job:
                sums[span.name] = sums.get(span.name, 0.0) + span.self_s
            for name, sec in sums.items():
                per_job.setdefault(name, []).append(sec)
        body = " ".join(f"{name}={statistics.median(v):.6f}"
                        for name, v in sorted(per_job.items()))
        ratio = median_over_jobs(summary, kind, capacity_over_rows)
        tail = "" if ratio is None else f" capacity_over_rows={ratio:.4f}"
        print(f"[bench] spans kind={kind} jobs={len(summary.jobs[kind])} "
              f"{body or 'none'}{tail}", flush=True)
    if summary.scope_s is None:
        print("[bench] scopes none", flush=True)
    else:
        body = " ".join(
            f"{label}={100.0 * sec / summary.busy_s:.3f}%"
            for label, sec in TR.top(summary.scope_s))
        print(f"[bench] scopes {body}", flush=True)
    if summary.idle_by_span is None:
        print(f"[bench] idle_by_span none idle_s={summary.idle_s:.6f}", flush=True)
    else:
        body = " ".join(f"{name}={sec:.6f}"
                        for name, sec in TR.top(summary.idle_by_span, 16))
        print(f"[bench] idle_by_span idle_s={summary.idle_s:.6f} {body}",
              flush=True)
