"""Percent of device busy time in operations under
``dryad.join.materialize``: the gathers that bring every output column
to its pair slot (left columns by ``li``, right columns by ``ri``).
XLA computes a gather shared with ``dryad.join.exact`` (a key column by
``li``) once and names it by one of the two, so the split between them
is the compiler's.  A share of 0 means no operation carries the scope
(the parent of PR 26, or a program cached before it): nothing to read."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.under(PS.of(cell, __file__), "dryad.join.materialize") or None
