"""Median, over the window's requeries, of ``recv_fill_max`` of the
job's last ``dryad:readback:drain`` span: over the job's exchanges, the
largest of fullest chip's rows / the capacity a chip holds them in
after the ``resize``.  How near the default slack stands to a second
run of the stage: past 1.0 the ``resize`` overflows and the whole stage
runs again at twice every shape.  ``None`` where the span lacks the
field (a program before PR 45; one chip)."""

import join_observed as JO


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return JO.median_of_requeries(
        cell, __file__, lambda seen: float(seen["recv_fill_max"]),
        "recv_fill_max")
