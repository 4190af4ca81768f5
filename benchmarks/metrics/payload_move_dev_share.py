"""Percent of device busy time in operations under
``dryad.sort.payload``: the columns of a sorted batch moved apart from
the sort, gathered by the row index that the sort carried.  ``None``,
never 0, where no operation carries the scope: the payload rode its
sorts as extra operands (or the program is older than the scope)."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.under(PS.of(cell, __file__), "dryad.sort.payload") or None
