"""Percent of the traced window in which no operation ran on the
device: 1 - (union of device operation intervals) / window, mean over
the cell's chips."""


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return 100.0 * trace["idle_share"]
