"""Percent of device busy time in operations under
``dryad.join.copartition``: the placement of a ``shuffle`` join, both
sides' hash exchange (layout sort, ``all_to_all``) and ``resize``
(``exec/kernels.py::_co_partition_for_join``), apart from the join
proper.  ``None``, never 0, where no operation carries the scope (a
broadcast join; a program before PR 45)."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.under(PS.of(cell, __file__), "dryad.join.copartition") or None
