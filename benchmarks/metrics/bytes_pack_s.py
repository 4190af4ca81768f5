"""Median, over the window's fresh jobs, of the seconds in
``dryad:ingest:pack``: the host passes that turn the table's BYTES
columns (``[rows, width]`` uint8) into big-endian uint32 words, one
span a column inside ``dryad:ingest:encode``.  ``None`` where the job
has no such span (a program without the column type, a table without
such a column)."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.median_over_jobs(
        PS.of(cell, __file__), "bench:fresh",
        lambda job: PS.seconds_in(job, "dryad:ingest:pack"))
