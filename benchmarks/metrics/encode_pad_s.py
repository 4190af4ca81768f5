"""Median, over the window's fresh jobs, of the seconds in the
``dryad:ingest:encode`` spans that carry ``capacity``: the copy of the
physical columns into ``P * capacity`` slots with ``valid``.  The
schema pass (the other span of the name) is ``ingest_encode_s`` less
this.  ``None`` where the span does not state ``bytes_out`` (the parent
of PR 34)."""

import host_pass as HP


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return HP.median_over_jobs(cell, __file__, "bench:fresh", HP.encode_pad_s)
