"""Percent of device busy time in operations under
``dryad.join.probe``: the right side sorted by key hash
(``dryad.sort.carry`` inside it) and the two binary searches of every
left row's hash in it.  A share of 0 means no operation carries the
scope (the parent of PR 26, or a program cached before it): nothing to
read."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.under(PS.of(cell, __file__), "dryad.join.probe") or None
