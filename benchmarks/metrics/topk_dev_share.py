"""Percent of device busy time in operations under ``dryad.topk``: the
fused ``order_by`` + ``take``, a carried sort over the slots it is
given (``dryad.sort.carry`` inside it).  A share of 0 means no
operation carries the scope: nothing to read."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.under(PS.of(cell, __file__), "dryad.topk") or None
