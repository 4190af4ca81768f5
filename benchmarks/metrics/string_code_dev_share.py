"""Percent of device busy time in operations under
``dryad.string_code``: the hash-to-code probe loop of the dense STRING
route."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.under(PS.of(cell, __file__), "dryad.string_code")
