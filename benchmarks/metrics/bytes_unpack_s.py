"""Median, over the window's requeries, of the seconds in
``dryad:decode:unpack``: the host passes that turn the fetched uint32
words of the answer's BYTES columns back into ``[rows, width]`` uint8,
one span a column inside ``dryad:decode:decode``.  ``None`` where the
job has no such span."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.median_over_jobs(
        PS.of(cell, __file__), "bench:requery",
        lambda job: PS.seconds_in(job, "dryad:decode:unpack"))
