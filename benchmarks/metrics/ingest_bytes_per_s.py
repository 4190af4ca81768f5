"""Median, over the window's fresh jobs, of the bytes the job's
``dryad:ingest:h2d`` spans carry over the seconds from the start of the
job's first ``dryad:ingest:*`` span to the end of its last ``h2d``: the
rate at which a host table becomes device arrays, layout included.
``device_put`` returns once the copies are enqueued, so what of a copy
outlasts its span is not in the denominator (TRACING.md)."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None

    def rate(job):
        h2d = PS.named(job, "dryad:ingest:h2d")
        ingest = PS.named(job, "dryad:ingest:*")
        if not h2d:
            return None
        seconds = max(s.end for s in h2d) - min(s.start for s in ingest)
        return PS.total(h2d, "bytes") / seconds if seconds > 0 else None

    return PS.median_over_jobs(PS.of(cell, __file__), "bench:fresh", rate)
