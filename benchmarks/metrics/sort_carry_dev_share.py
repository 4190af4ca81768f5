"""Percent of device busy time in operations under ``dryad.sort.carry``
anywhere in their path: every stable sort that moves a batch's columns
(the exchange layout's, ``resize``'s compaction, ``local_sort``, the
splitters' sample), with the columns riding it or, under
``dryad.sort.payload`` inside it, gathered by the carried row index.
``None``, never 0, where no operation carries the scope."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.under(PS.of(cell, __file__), "dryad.sort.carry") or None
