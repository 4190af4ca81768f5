"""Percent of device busy time in collective operations (all-to-all,
all-reduce, reduce-scatter, ...), from the trace."""

from spans import share_of_busy
from trace_reduce import is_collective


def read(trace, spans, counters, cell):
    return share_of_busy(trace, is_collective)
