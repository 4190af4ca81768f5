"""Median, over the window's requeries, of the time from the job's
first device operation to the end of its last: the stage programs of
one query over a resident table, with whatever the host leaves between
them.  From the trace."""

from spans import DEVICE, median_phase


def read(trace, spans, counters, cell):
    return median_phase(trace, "bench:requery", DEVICE)
