"""What the host passes say of themselves, read from the traced run's
program spans (``program_spans.of``).

Since PR 34 the span of a host pass that writes a table's worth of host
memory is opened with ``account=True`` and carries, beside its seconds,
``user_s`` / ``sys_s`` (CPU seconds of the whole process between its
open and its close) and ``bytes_out``: the bytes of the arrays it made
(``fetch_copy`` says the same under ``bytes``).  All inclusive of the
span's children, so a sum over spans takes the *outermost* ones that
state the number (``pack`` lies inside the schema ``encode``,
``unpack`` inside ``decode``).  What a span learns before it closes now
rides its annotation, so the xplane holds these.

This file is the arithmetic the ``metrics/*.py`` readers of those stats
share, on the plain ``program_spans.Span`` lists of one job, so it is
checked on hand-built planes.  Every function gives ``None``, never 0,
where the job's spans lack the stat or the span (the parent of PR 34,
whose passes state no ``bytes_out`` and keep no account;
``collect_self_s`` alone reads spans the parent has too).

The first read of a trace also prints, before the result line, one
``[bench] host_pass`` line a job kind a pass that states its bytes:
medians over the kind's jobs of the pass's seconds, CPU seconds, bytes
and GB/s.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional

import program_spans as PS

INGEST = "dryad:ingest:*"
ENCODE = "dryad:ingest:encode"
TOKENIZE = "dryad:ingest:tokenize"
FETCH_COPY = "dryad:readback:fetch_copy"
DECODE = "dryad:decode:decode"
COLLECT = "dryad:other:collect"
SAMPLE = "dryad:other:resource_sample"


def accounted(span: PS.Span) -> bool:
    """The span was opened with ``account=True``: it carries the CPU
    seconds that the account alone adds."""
    return "user_s" in span.stats


def bytes_made(span: PS.Span) -> Optional[float]:
    """The host bytes an accounted pass wrote: its ``bytes_out``, or a
    ``fetch_copy``'s ``bytes``; ``None`` for a span that states none or
    keeps no account (the parent's ``fetch_copy`` states ``bytes``)."""
    if not accounted(span):
        return None
    if span.name == FETCH_COPY:
        return span.stats.get("bytes")
    return span.stats.get("bytes_out")


def outermost(job: List[PS.Span], spans: List[PS.Span]) -> List[PS.Span]:
    """Those of ``spans`` with no ancestor (by ``parent_id``, among the
    job's spans) in ``spans``: a sum over them counts no byte twice."""
    by_id = {s.stats.get("span_id"): s for s in job}
    mine = {id(s) for s in spans}

    def nested(span):
        seen = set()
        parent = by_id.get(span.stats.get("parent_id"))
        while parent is not None and id(parent) not in seen:
            if id(parent) in mine:
                return True
            seen.add(id(parent))
            parent = by_id.get(parent.stats.get("parent_id"))
        return False

    return [s for s in spans if not nested(s)]


def writers(job: List[PS.Span], *names: str) -> List[PS.Span]:
    """The outermost spans of these names that state the bytes they
    wrote."""
    return outermost(job, [s for s in PS.named(job, *names)
                           if bytes_made(s) is not None])


def pad_encodes(job: List[PS.Span]) -> List[PS.Span]:
    """The ``encode`` spans that lay the table out as ``P * capacity``
    slots (they carry ``capacity``); the schema pass carries none."""
    return [s for s in PS.named(job, ENCODE)
            if "capacity" in s.stats and bytes_made(s) is not None]


def rows_ingested(job: List[PS.Span]) -> float:
    """Rows the job laid out for the device: the pad ``encode``s'
    ``rows``; where a job has none, the ``tokenize`` spans'."""
    return (PS.total(pad_encodes(job), "rows")
            or PS.total(PS.named(job, TOKENIZE), "rows"))


def bytes_a_row(spans: List[PS.Span], rows: float) -> Optional[float]:
    return sum(bytes_made(s) for s in spans) / rows if spans and rows else None


def bytes_per_s(spans: List[PS.Span]) -> Optional[float]:
    seconds = sum(s.seconds for s in spans)
    return sum(bytes_made(s) for s in spans) / seconds if seconds > 0 else None


# -- a job's numbers, as the metric files name them ---------------------------

def ingest_host_bytes_a_row(job):
    return bytes_a_row(writers(job, INGEST), rows_ingested(job))


def encode_pad_s(job):
    pads = pad_encodes(job)
    return sum(s.seconds for s in pads) if pads else None


def egress(job) -> List[PS.Span]:
    """A job's ``fetch_copy`` and ``decode`` spans, when both state
    their bytes."""
    copied, decoded = writers(job, FETCH_COPY), writers(job, DECODE)
    return copied + decoded if copied and decoded else []


def egress_host_bytes_a_row(job):
    return bytes_a_row(egress(job), PS.total(PS.named(job, DECODE), "rows"))


def fetch_copy_bytes_per_s(job):
    return bytes_per_s(writers(job, FETCH_COPY))


def decode_bytes_per_s(job):
    return bytes_per_s(writers(job, DECODE))


def collect_self_s(job):
    roots = PS.named(job, COLLECT)
    return sum(s.self_s for s in roots) if roots else None


def sample_s_a_pair(summary: Optional[PS.Summary]) -> Optional[float]:
    """Seconds of the window's jobs inside ``resource_sample`` spans,
    over the window's pairs."""
    if summary is None:
        return None
    samples = [s for s in PS.named(summary.spans, SAMPLE) if s.job is not None]
    pairs = len(summary.jobs.get("bench:fresh", []))
    return sum(s.seconds for s in samples) / pairs if samples and pairs else None


# -- the trace, and its lines ---------------------------------------------------

def of(cell, metric_file: str) -> Optional[PS.Summary]:
    """``program_spans.of``; the first read of a trace also prints its
    ``[bench] host_pass`` lines."""
    summary = PS.of(cell, metric_file)
    if summary is not None and not getattr(summary, "host_pass_said", False):
        summary.host_pass_said = True  # once a trace, as PS.of reports once
        report(summary)
    return summary


def median_over_jobs(cell, metric_file: str, kind: str, value):
    return PS.median_over_jobs(of(cell, metric_file), kind, value)


def label(span: PS.Span) -> str:
    """A pass's name on its line; the two ``encode`` passes apart."""
    if span.name == ENCODE:
        return ENCODE + (".pad" if "capacity" in span.stats else ".schema")
    return span.name


def passes(job: List[PS.Span]) -> Dict[str, Dict[str, float]]:
    """Label -> the job's sums over the spans of that label that state
    their bytes (each inclusive of its children)."""
    out: Dict[str, Dict[str, float]] = {}
    for span in job:
        made = bytes_made(span)
        if made is None:
            continue
        row = out.setdefault(label(span), dict.fromkeys(
            ("s", "user_s", "sys_s", "bytes_out"), 0.0))
        row["s"] += span.seconds
        row["bytes_out"] += made
        for stat in ("user_s", "sys_s"):
            row[stat] += span.stats[stat]
    return out


def report(summary: PS.Summary) -> None:
    for kind in summary.jobs:
        per_pass: Dict[str, List[Dict[str, float]]] = {}
        for job in summary.of_job(kind):
            for name, row in passes(job).items():
                per_pass.setdefault(name, []).append(row)
        for name, rows in sorted(per_pass.items()):
            med = {k: statistics.median(r[k] for r in rows) for k in rows[0]}
            rate = statistics.median(
                r["bytes_out"] / r["s"] / 1e9 if r["s"] > 0 else 0.0 for r in rows)
            print(f"[bench] host_pass kind={kind} span={name} jobs={len(rows)} "
                  f"s={med['s']:.6f} user_s={med['user_s']:.6f} "
                  f"sys_s={med['sys_s']:.6f} bytes_out={int(med['bytes_out'])} "
                  f"GB_s={rate:.3f}", flush=True)
