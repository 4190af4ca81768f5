"""Median, over the window's requeries, of the job's ``stage_overflow``
events: the ``overflows`` its last ``dryad:readback:drain`` span states
(the drains of the job that saw the flag set, its own counted): an
exchange whose bucket overflowed ran the whole stage again at twice
the room.  0 where every first dispatch held.  ``None`` where the span
lacks the count (the parent of PR 41; one chip)."""

import program_spans as PS
import exchange_observed as XO


def read(trace, spans, counters, cell):
    if trace is None:
        return None

    def retries(job):
        seen = XO.last_drain(job)
        return None if seen is None else float(seen["overflows"])

    return PS.median_over_jobs(PS.of(cell, __file__), "bench:requery", retries)
