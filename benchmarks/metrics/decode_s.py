"""Median, over the window's requeries, of the seconds in
``dryad:decode:decode``: the fetched physical columns, ``capacity``
slots of them, cut to the valid rows and made the user's table."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.median_over_jobs(
        PS.of(cell, __file__), "bench:requery",
        lambda job: PS.seconds_in(job, "dryad:decode:decode"))
