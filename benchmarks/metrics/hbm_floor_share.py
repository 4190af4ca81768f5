"""Percent of a requery's device busy time that the HBM floor explains:
the bytes the query must read and write once (the job file's
``min_bytes`` of its shapes) over the chips' peak HBM bandwidth
(``peaks.json``, by ``device_kind``), over the median device busy time
inside a ``bench:requery`` span."""

import statistics


def read(trace, spans, counters, cell):
    if trace is None or not trace["busy_in"].get("bench:requery"):
        return None
    floor_s = cell.job.min_bytes(cell.params) / (
        cell.chips * cell.peaks["hbm_bytes_per_s"])
    busy_s = statistics.median(trace["busy_in"]["bench:requery"])
    return 100.0 * floor_s / busy_s if busy_s > 0 else None
