"""Percent of the window's device-idle seconds that fall inside a
program span (``dryad:*``), each instant charged to the innermost span
open: how much of the idle time the program's spans explain."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    summary = PS.of(cell, __file__)
    if summary is None or summary.idle_by_span is None or summary.idle_s <= 0:
        return None
    return 100.0 * (1.0 - summary.idle_by_span[PS.UNNAMED] / summary.idle_s)
