"""Percent of device busy time in operations under
``dryad.sort.splitters``: the shard sorted for its sample
(``dryad.sort.carry`` inside it), the sample gathered over the mesh,
the P - 1 splitters elected.  ``None``, never 0, where no operation
carries the scope."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.under(PS.of(cell, __file__), "dryad.sort.splitters") or None
