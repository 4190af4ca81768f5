"""Median, over the window's requeries, of the number of
``dryad:dispatch:*`` spans in the job: its stage programs plus every
overflow retry (a retry is one more dispatch, at ``boost`` > 1).  A
join whose pair buffer never overflows and whose plan fuses into one
stage reads 1."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.median_over_jobs(
        PS.of(cell, __file__), "bench:requery",
        lambda job: float(len(PS.named(job, "dryad:dispatch:*"))) or None)
