"""Median, over the window's requeries, of the rows the fullest chip
received in the hash exchange times the chips over the rows sent:
``recv_rows_max`` x chips / ``combine_rows_out`` of the job's
``dryad:readback:drain`` span (what was sent is what was received
unless a bucket overflowed, and then the job ran again and its last
drain is the one read).  1.0 is even; at ``shuffle_slack`` (2.0) a
bucket overflows.  ``None`` where the span lacks the counts (the
parent of PR 41; one chip)."""

import program_spans as PS
import exchange_observed as XO


def read(trace, spans, counters, cell):
    if trace is None:
        return None

    def balance(job):
        seen = XO.last_drain(job)
        if seen is None or not seen["combine_rows_out"]:
            return None
        return seen["recv_rows_max"] * cell.chips / seen["combine_rows_out"]

    return PS.median_over_jobs(PS.of(cell, __file__), "bench:requery", balance)
