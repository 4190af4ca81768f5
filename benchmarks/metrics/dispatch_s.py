"""Median, over the window's requeries, of the seconds from the start
of ``dryad:other:collect`` to the end of the job's last
``dryad:dispatch:*`` span: lower, fuse, the cache lookups and the
launch, the host's work before it can only wait."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None

    def dispatch_s(job):
        roots = PS.named(job, "dryad:other:collect")
        launched = PS.named(job, "dryad:dispatch:*")
        if not roots or not launched:
            return None
        return max(s.end for s in launched) - min(s.start for s in roots)

    return PS.median_over_jobs(PS.of(cell, __file__), "bench:requery", dispatch_s)
