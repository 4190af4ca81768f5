"""Seconds a pair (a fresh job and its requery) of the window inside
``dryad:other:resource_sample``: the telemetry sample that the first
event after a second of quiet triggers on the job's own thread
(``obs/telemetry.py::ResourceMonitor``: ``memory_stats()`` of every
device and the shared probes).  ``None`` where the trace holds no such
span (the parent of PR 34, which sampled under no span)."""

import host_pass as HP


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return HP.sample_s_a_pair(HP.of(cell, __file__))
