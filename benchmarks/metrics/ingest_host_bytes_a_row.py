"""Median, over the window's fresh jobs, of the host bytes the ingest
passes wrote a row ingested: the sum of ``bytes_out`` over the job's
outermost ``dryad:ingest:*`` spans that state it (the schema ``encode``
with its ``pack``s inside, the pad ``encode``; ``tokenize`` and
``vocab`` for text) over the pad ``encode``s' ``rows`` (``tokenize``'s
where a job has no pad).  One pass over an 8-byte row with its validity
byte would read 9.  ``None`` where no span states ``bytes_out`` (the
parent of PR 34)."""

import host_pass as HP


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return HP.median_over_jobs(cell, __file__, "bench:fresh", HP.ingest_host_bytes_a_row)
