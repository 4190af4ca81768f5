"""The builtin-aggregate folds' share of their roofline, percent: the
least bytes the folds of the window's dispatches move (the job file's
``fold_bytes`` of its shapes: the flag and the state words of every
slot, read once and written once, every fold of a job; padded slots are
folded like rows) over the seconds the chip spent in operations under
``dryad.group_reduce.fold`` in the window (the segmented scan AND the
compaction that places the run-end rows), over the chip's HBM peak
(``peaks.json``).  A scan in log2 n passes reads and writes the state
every pass, and the compaction moves it log2 n times more, so this
reads far under 100%; over 100% is a wrong count.  ``None`` where no
operation carries the scope and for a job file that states no
``fold_bytes``."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None or not hasattr(cell.job, "fold_bytes"):
        return None
    summary = PS.of(cell, __file__)
    share = PS.under(summary, "dryad.group_reduce.fold")
    if not share:
        return None
    seconds = share / 100.0 * summary.busy_s  # mean over chips, the window
    dispatches = len(PS.named(summary.spans, "dryad:dispatch:*"))
    a_dispatch = cell.job.fold_bytes(cell.params)
    print(f"[bench] agg_fold dispatches={dispatches} "
          f"bytes_a_dispatch={a_dispatch} fold_s={seconds:.6f}", flush=True)
    return 100.0 * dispatches * a_dispatch / cell.chips / seconds / (
        cell.peaks["hbm_bytes_per_s"])
