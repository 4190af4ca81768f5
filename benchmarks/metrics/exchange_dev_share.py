"""Percent of device busy time in operations under an exchange's
scopes (``dryad.exchange_hash`` / ``dryad.exchange_range`` and, inside
them, ``dryad.exchange.layout`` and ``dryad.exchange.collective``):
what a repartition costs the device, layout and collective together."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.under(PS.of(cell, __file__), "dryad.exchange")
