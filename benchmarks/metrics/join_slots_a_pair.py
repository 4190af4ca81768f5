"""Median, over the window's requeries, of pair slots a candidate
pair: ``join_slots`` (``out_capacity``, a chip) x chips / ``join_pairs``
(summed over the chips) of the job's last ``dryad:readback:drain``
span.  What the per-slot gathers, the most expensive thing in a join,
pay for a live pair: they run over every slot, filled or not.  2.0
where a foreign-key join sizes its buffer from the capacity after the
exchange (``shuffle_slack`` shards) at ``expansion`` 1.0.  ``None``
where the span lacks the counts (a program before PR 45)."""

import join_observed as JO


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return JO.median_of_requeries(
        cell, __file__,
        lambda seen: (seen["join_slots"] * cell.chips / seen["join_pairs"]
                      if seen["join_pairs"] else None),
        "join_slots", "join_pairs")
