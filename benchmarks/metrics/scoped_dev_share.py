"""Percent of device busy time in operations that carry any ``dryad.``
scope: how much of the device's time the program's scopes name."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.under(PS.of(cell, __file__), PS.SCOPE_PREFIX)
