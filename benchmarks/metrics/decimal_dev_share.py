"""Percent of device busy time in operations under ``dryad.decimal``:
the exact wide arithmetic of a ``select`` / ``where`` over DECIMAL
columns (``ops/wide.py``: the 32 x 32 -> 64 and 64 x 32 -> 64
multiplies in 16-bit limbs, the carried adds, the compares).  ``None``,
never 0, where no operation carries the scope: a program before PR 49,
or a fusion that XLA named after another of its operations."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.under(PS.of(cell, __file__), "dryad.decimal") or None
