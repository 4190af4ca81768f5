"""Helpers the trace readers share."""

import statistics

LEAD, DEVICE, TAIL = 0, 1, 2  # the parts of trace_reduce.job_phases


def median_phase(trace, job: str, part: int):
    """Median seconds of one part (``LEAD`` / ``DEVICE`` / ``TAIL``)
    over the window's jobs of one kind (``bench:fresh`` /
    ``bench:requery``); None without a trace or without such a job."""
    if trace is None or not trace["phases"].get(job):
        return None
    return statistics.median(p[part] for p in trace["phases"][job])


def share_of_busy(trace, pick):
    """Percent of device busy time in operations that ``pick(label)``
    accepts; None without a trace."""
    if trace is None or not trace["op_s"]:
        return None
    total = sum(trace["op_s"].values())
    return 100.0 * sum(s for label, s in trace["op_s"].items() if pick(label)) / total
