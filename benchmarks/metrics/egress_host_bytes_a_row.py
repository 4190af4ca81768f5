"""Median, over the window's requeries, of the host bytes the way back
wrote a row of the answer: ``dryad:readback:fetch_copy``'s ``bytes``
plus ``dryad:decode:decode``'s ``bytes_out`` (the user's table) over
``decode``'s ``rows``.  Handing out the fetched arrays would read
``d2h_bytes_a_row`` alone.  ``None`` where ``decode`` states no
``bytes_out`` (the parent of PR 34)."""

import host_pass as HP


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return HP.median_over_jobs(cell, __file__, "bench:requery", HP.egress_host_bytes_a_row)
