"""The combiner's scans' share of their roofline, percent: the least
bytes the scans of the window's dispatches move on a chip (the job
file's ``scan_bytes`` of its shapes: the flag and the state words of
every slot scanned, read once and written once, both scans of a job;
padded slots are scanned like rows) over the seconds a chip spent in
operations under ``dryad.group_combine.scan`` in the window, over the
chip's HBM peak (``peaks.json``).  A scan in levels reads and writes
every level, so this reads well under 100%; over 100% is a wrong
count.  ``None`` where no operation carries the scope (the parent of
PR 41) and for a job file that states no ``scan_bytes``."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None or not hasattr(cell.job, "scan_bytes"):
        return None
    summary = PS.of(cell, __file__)
    share = PS.under(summary, "dryad.group_combine.scan")
    if not share:
        return None
    seconds = share / 100.0 * summary.busy_s  # mean over chips, the window
    dispatches = len(PS.named(summary.spans, "dryad:dispatch:*"))
    moved = dispatches * cell.job.scan_bytes(cell.params)
    print(f"[bench] combine_scan dispatches={dispatches} "
          f"bytes_a_dispatch_a_chip={cell.job.scan_bytes(cell.params)} "
          f"scan_s={seconds:.6f}", flush=True)
    return 100.0 * moved / seconds / cell.peaks["hbm_bytes_per_s"]
