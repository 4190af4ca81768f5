"""Median, over the window's requeries, of the time from the job's
last device operation to the return of ``Query.collect()``: D2H and
decode of the answer.  From the trace, good to the few milliseconds its
two clocks differ by, so listed only for cells whose answer is of size."""

from spans import TAIL, median_phase


def read(trace, spans, counters, cell):
    return median_phase(trace, "bench:requery", TAIL)
