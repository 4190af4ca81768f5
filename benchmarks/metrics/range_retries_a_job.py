"""Median, over the window's requeries, of the job's
``dryad:dispatch:*`` spans with ``boost`` > 1: a range partition whose
bucket overflowed ran the whole stage again at twice the room, under
splitters from twice the sample.  0 where every job's first dispatch
held; ``None`` where a job has no dispatch span."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None

    def retries(job):
        dispatched = PS.named(job, "dryad:dispatch:*")
        if not dispatched:
            return None
        return float(sum(s.stats.get("boost", 1) > 1 for s in dispatched))

    return PS.median_over_jobs(PS.of(cell, __file__), "bench:requery", retries)
