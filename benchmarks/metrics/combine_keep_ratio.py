"""Median, over the window's requeries, of the rows the combiner
before the hash exchange left over the rows it was handed, summed over
the chips: ``combine_rows_out`` / ``combine_rows_in`` of the job's
``dryad:readback:drain`` span (counted on the device, read back with
the overflow flag).  What of a table crosses the ICI: 1.0 where
every row's key is its own on its chip; the more rows share a key on
a chip, the lower.  ``None`` where the span lacks the counts (the
parent of PR 41; a job on one chip, which exchanges nothing)."""

import program_spans as PS
import exchange_observed as XO


def read(trace, spans, counters, cell):
    if trace is None:
        return None

    def kept(job):
        seen = XO.last_drain(job)
        if seen is None or not seen["combine_rows_in"]:
            return None
        return seen["combine_rows_out"] / seen["combine_rows_in"]

    return PS.median_over_jobs(PS.of(cell, __file__), "bench:requery", kept)
