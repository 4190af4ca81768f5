"""Median, over the window's requeries, of ``recv_balance_max`` of the
job's last ``dryad:readback:drain`` span: over the job's exchanges
(the join's two: the probe side's, then the build side's), the largest
of fullest chip's rows x chips / rows sent.  1.0 is even; the skewed
probe side sets it, where the sum over both exchanges (``recv_balance``)
would be flattened by the even build side.  At P = 4 and the default
slack an exchange overflows at 2.0.  ``None`` where the span lacks the
field (a program before PR 45; one chip)."""

import join_observed as JO


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return JO.median_of_requeries(
        cell, __file__, lambda seen: float(seen["recv_balance_max"]),
        "recv_balance_max")
