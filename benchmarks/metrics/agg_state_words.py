"""Median, over the window's requeries, of ``agg_state_words`` of the
job's ``dryad:dispatch:*`` spans (the largest, where a job has several):
the 4-byte words of scan state the widest builtin-aggregate fold of the
stage carries a slot (two a 64-bit channel, one any other; a ``count``
carries none: ``ops/segmented.py::fold_stats``).  What rides every pass
of the fold: a change that shares or splits channels, or that widens a
sum, moves it.  ``None`` where no dispatch span states it (a program
before PR 49; a stage without a builtin-aggregate group-by)."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None

    def of(job):
        said = [s.stats["agg_state_words"]
                for s in PS.named(job, "dryad:dispatch:*")
                if "agg_state_words" in s.stats]
        return float(max(said)) if said else None

    return PS.median_over_jobs(PS.of(cell, __file__), "bench:requery", of)
