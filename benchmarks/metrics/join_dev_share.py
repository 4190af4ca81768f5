"""Percent of device busy time in operations under a join kernel's
scope (``dryad.join`` and, inside it, ``dryad.join.probe``,
``dryad.join.expand_pairs``, ``dryad.join.materialize`` and
``dryad.join.exact``): what the join costs the device, all of it.
A share of 0 means no operation carries the scope (no join in the plan,
or a program cached before the scopes): nothing to read."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.under(PS.of(cell, __file__), "dryad.join") or None
