"""Median, over the window's fresh jobs, of the time from the job's
start (``Query.collect()`` of a new host table; for WordCount the read
and tokenizing too) to its first device operation: the host binds,
plans and moves the table in while the device has nothing to run.
From the trace, good to the few milliseconds its two clocks differ by."""

from spans import LEAD, median_phase


def read(trace, spans, counters, cell):
    return median_phase(trace, "bench:fresh", LEAD)
