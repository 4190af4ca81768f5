"""Median, over the window's requeries, of ``dryad:decode:decode``'s
``bytes_out`` (the user's table) over its seconds, ``unpack`` inside
it.  ``None`` where the span states no ``bytes_out`` (the parent of PR
34)."""

import host_pass as HP


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return HP.median_over_jobs(cell, __file__, "bench:requery", HP.decode_bytes_per_s)
