"""Median, over the window's fresh jobs, of the seconds the host spent
laying the table out before any copy: the program's spans
``dryad:ingest:tokenize`` (file read + native tokenizer),
``dryad:ingest:vocab`` (``np.unique``) and ``dryad:ingest:encode``
(schema encode, pad to P x capacity), summed a job."""

import program_spans as PS

NAMES = ("dryad:ingest:tokenize", "dryad:ingest:vocab", "dryad:ingest:encode")


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.median_over_jobs(PS.of(cell, __file__), "bench:fresh",
                               lambda job: PS.seconds_in(job, *NAMES))
