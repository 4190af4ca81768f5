"""What the co-partitioned join of a job said of itself, on the
``dryad:readback:drain`` span that read the overflow flag back (PR 46):
of the job's exchanges, the worst single one (``recv_balance_max``:
fullest chip's rows x chips / rows sent; ``recv_fill_max``: fullest
chip's rows / the capacity its ``resize`` leaves) and not their sum,
which a second input's even exchange would flatten; of its join
kernels, ``join_pairs`` (candidate pairs in the pair buffers, summed
over the chips), ``join_pairs_max`` (the fullest chip's) and
``join_slots`` (``out_capacity``, a chip).  For
``metrics/probe_side_balance.py``, ``exchange_fill_max.py`` and
``join_slots_a_pair.py``; the job's retries are
``metrics/exchange_retries_a_job.py``'s, which reads ``overflows`` off
the same span.  And a job's device seconds under a scope CHIP BY CHIP
(``metrics/materialize_chip_spread.py``, ``join_by_chip.py``): a job
takes its slowest chip's, which a mean over the chips hides."""

import os
import statistics

import program_spans as PS
import trace_reduce as TR


def median_of_requeries(cell, metric_file, value, *stats):
    """Median over the window's requeries of ``value(stats)`` of the
    job's last ``drain`` span that states every one of ``stats`` (a
    retry's drain comes after the one that overflowed); ``None`` where
    none does: a program older than the fields, or one chip, which
    exchanges nothing."""

    def of(job):
        stated = [s.stats for s in PS.named(job, "dryad:readback:drain")
                  if all(name in s.stats for name in stats)]
        return value(stated[-1]) if stated else None

    return PS.median_over_jobs(PS.of(cell, metric_file), "bench:requery", of)


def planes_of(cell, metric_file):
    """The planes of the cell's traced run, found as ``PS.of`` finds its
    summary; ``None`` without a trace."""
    import xplane

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(metric_file))))
    try:
        return xplane.read(TR.find_xplane(
            os.path.join(root, ".bench_out", f"trace-{cell.name}")))
    except FileNotFoundError:
        return None


def annotated(planes, prefix):
    """name -> the sorted intervals of the host plane's ``bench:*``
    annotations whose name starts with ``prefix``."""
    out = {}
    for name, start, end in PS.host_events(planes)[1]:
        if name.startswith(prefix):
            out.setdefault(name, []).append((start, end))
    return {name: sorted(ivals) for name, ivals in sorted(out.items())}


def scope_seconds_by_chip(planes, intervals):
    """chip -> one ``{scope path: self seconds}`` an interval, of the
    device operations inside it (``TR.self_times``' arithmetic), and
    under ``None`` the chip's busy seconds there."""
    out = {}
    for chip, ops in sorted(PS.device_ops(planes).items()):
        for lo, hi in intervals:
            mine = [(label, max(s, lo), min(e, hi)) for label, s, e in ops
                    if min(e, hi) > max(s, lo)]
            seconds = dict(TR.self_times(mine))
            seconds[None] = TR.length(TR.union((s, e) for _, s, e in mine))
            out.setdefault(chip, []).append(seconds)
    return out


def chip_spread(planes, scope, kind="bench:requery"):
    """Median over the window's jobs of ``kind`` of the slowest chip's
    seconds in operations under ``scope`` over the fastest's; ``None``
    where no operation of a job carries the scope, or on one chip."""
    window = annotated(planes, TR.WINDOW_ANNOTATION).get(TR.WINDOW_ANNOTATION)
    if not window:
        return None
    lo, hi = window[0]
    jobs = [(s, e) for s, e in annotated(planes, kind).get(kind, [])
            if s >= lo and e <= hi]
    by_chip = scope_seconds_by_chip(planes, jobs)
    if len(by_chip) < 2:
        return None
    ratios = []
    for i in range(len(jobs)):
        each = [sum(sec for label, sec in seconds[i].items()
                    if label is not None and scope in label.split("/"))
                for seconds in by_chip.values()]
        if min(each) > 0:
            ratios.append(max(each) / min(each))
    return statistics.median(ratios) if ratios else None
