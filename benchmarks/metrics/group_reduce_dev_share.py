"""Percent of device busy time in operations under
``dryad.group_reduce``: the builtin-aggregate group-by
(``ops/segmented.py::group_reduce``), every run of it in a job (on one
chip two: before the elided exchange and after it): the rows sorted by
key with their columns carried (``dryad.group_reduce.layout``) and the
fold (``dryad.group_reduce.fold``: the segmented scan, whose 64-bit
channels add with carry, and the compaction of the run-end rows).
``None``, never 0, where no operation carries the scope."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.under(PS.of(cell, __file__), "dryad.group_reduce") or None
