"""Median, over the window's fresh jobs, of ``dryad:other:collect``'s
seconds less its children's: what of a job no span of the program
names (the event log's own work after each span closes, since PR 34
named the frees: ``drop``, ``release``).  ``None`` where a job has no
such span; the parent of PR 34 has it, and reads there what the frees at
its functions' returns took besides."""

import host_pass as HP


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return HP.median_over_jobs(cell, __file__, "bench:fresh", HP.collect_self_s)
