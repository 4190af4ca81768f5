"""Median, over the window's fresh jobs, of the share of the table's
layout that was written into host memory the context had used before:
``warm_bytes`` over ``bytes_out``, summed over the job's
``dryad:ingest:encode`` spans that carry ``capacity`` (one a table
since PR 36), in per cent.  100 = every byte went into a staging
buffer kept from an earlier job; 0 = every buffer was new (a first
job, or a pool whose buffers were all in flight or given up).  ``None``
where the span states no ``warm_bytes`` (the parent of PR 36)."""

import host_pass as HP


def share(job):
    pads = [s for s in HP.pad_encodes(job) if "warm_bytes" in s.stats]
    staged = sum(s.stats["bytes_out"] for s in pads)
    return 100.0 * sum(s.stats["warm_bytes"] for s in pads) / staged if staged else None


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return HP.median_over_jobs(cell, __file__, "bench:fresh", share)
