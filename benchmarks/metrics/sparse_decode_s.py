"""Median, over the window's requeries, of the seconds a job spent in
``dryad:decode:decode`` spans that took the mask path: those whose
``fetched`` exceeds their ``rows`` by more than a fifth (a trimmed
answer lies within a fifth of its rows, by ``trim_tiers``).  0.0 where
every answer of the job was a slice a shard; ``None`` over a program
whose ``decode`` states no ``fetched``.

The read also prints one ``[bench] output`` line a job kind an
output (the spans' ``output``, PR 38): medians over the kind's jobs of
the output's ``fetch_copy`` and ``decode`` seconds, its rows, the slots
fetched and the bytes copied."""

import statistics

import program_spans as PS

DECODE = "dryad:decode:decode"
FETCH_COPY = "dryad:readback:fetch_copy"
SPARSE = 1.2  # fetched / rows over which a decode walked a mask


def sparse_s(job):
    decoded = [s for s in PS.named(job, DECODE) if "fetched" in s.stats]
    if not decoded:
        return None
    return sum(s.seconds for s in decoded
               if s.stats["fetched"] > SPARSE * s.stats.get("rows", 0))


def by_output(job):
    """output -> this job's numbers for it; empty where no span says
    which output it served."""
    out = {}
    for span in PS.named(job, DECODE, FETCH_COPY):
        if "output" not in span.stats:
            continue
        row = out.setdefault(int(span.stats["output"]), dict.fromkeys(
            ("fetch_copy_s", "decode_s", "rows", "fetched", "bytes"), 0.0))
        if span.name == DECODE:
            row["decode_s"] += span.seconds
            row["rows"] += span.stats.get("rows", 0)
            row["fetched"] += span.stats.get("fetched", 0)
        else:
            row["fetch_copy_s"] += span.seconds
            row["bytes"] += span.stats.get("bytes", 0)
    return out


def report(summary: PS.Summary) -> None:
    for kind in summary.jobs:
        per_output = {}
        for job in summary.of_job(kind):
            for output, row in by_output(job).items():
                per_output.setdefault(output, []).append(row)
        for output, rows in sorted(per_output.items()):
            med = {k: statistics.median(r[k] for r in rows) for k in rows[0]}
            print(f"[bench] output kind={kind} output={output} jobs={len(rows)} "
                  f"fetch_copy_s={med['fetch_copy_s']:.6f} "
                  f"decode_s={med['decode_s']:.6f} rows={int(med['rows'])} "
                  f"fetched={int(med['fetched'])} bytes={int(med['bytes'])}",
                  flush=True)


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    summary = PS.of(cell, __file__)
    if summary is None:
        return None
    report(summary)
    return PS.median_over_jobs(summary, "bench:requery", sparse_s)
