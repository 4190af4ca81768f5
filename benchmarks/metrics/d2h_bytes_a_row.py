"""Median, over the window's requeries, of the bytes the job's
``dryad:readback:fetch_copy`` spans brought back over the rows its
``dryad:decode:decode`` kept: what a row of the answer costs on the way
back, padding and validity mask included."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None

    def bytes_a_row(job):
        rows = PS.total(PS.named(job, "dryad:decode:decode"), "rows")
        copied = PS.named(job, "dryad:readback:fetch_copy")
        return PS.total(copied, "bytes") / rows if rows and copied else None

    return PS.median_over_jobs(PS.of(cell, __file__), "bench:requery", bytes_a_row)
