"""Median, over the window's requeries, of the slots a job copied back
over the rows it handed out: the sum of ``fetched`` over the sum of
``rows`` of the job's ``dryad:decode:decode`` spans, every output
counted.  1.0 = nothing but rows travelled; a trimmed answer reads up
to 1.19 (the next rung of ``trim_tiers``); a filtered batch whose valid
rows lie scattered over its capacity is copied whole and reads capacity
over rows.  ``None`` over a program whose ``decode`` states no
``fetched`` (before PR 31)."""

import program_spans as PS

DECODE = "dryad:decode:decode"


def slots_a_row(job):
    decoded = [s for s in PS.named(job, DECODE) if "fetched" in s.stats]
    rows = PS.total(decoded, "rows")
    return PS.total(decoded, "fetched") / rows if rows else None


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.median_over_jobs(PS.of(cell, __file__), "bench:requery", slots_a_row)
