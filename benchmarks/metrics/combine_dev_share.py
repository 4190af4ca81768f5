"""Percent of device busy time in operations under
``dryad.group_combine``: the user-defined combiner
(``ops/segmented.py::group_combine``), both runs of a job: the rows
sorted by key with the state carried (``dryad.group_combine.layout``),
the segmented scan that traces the user's ``merge``
(``dryad.group_combine.scan``), the scatters that set one row a key
(``dryad.group_combine.emit``).  ``None``, never 0, where no operation
carries the scope."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.under(PS.of(cell, __file__), "dryad.group_combine") or None
