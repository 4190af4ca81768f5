"""Median, over the window's requeries, of
``dryad:readback:fetch_copy``'s ``bytes`` over its seconds: the rate of
the copy back as the host sees it.  Beside the span's ``user_s`` +
``sys_s`` (the ``[bench] host_pass`` line) it says whether a core was
busy for those seconds or the thread waited.  ``None`` where the span
does not account for itself (the parent of PR 34)."""

import host_pass as HP


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return HP.median_over_jobs(cell, __file__, "bench:requery", HP.fetch_copy_bytes_per_s)
