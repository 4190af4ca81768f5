"""Percent of device busy time in XLA ``gather`` fusions, from the
trace."""

from spans import share_of_busy
from trace_reduce import is_gather


def read(trace, spans, counters, cell):
    return share_of_busy(trace, is_gather)
