"""Median, over the window's requeries, of the answers a job handed
back: the ``outputs`` of its ``dryad:other:collect`` spans, summed.  A
multi-output job (``DryadContext.collect_many``) is ONE ``collect`` that
says 3 here; the same three answers collected one by one would be three
roots of 1 inside a harness span and still sum to 3, so read it beside
the ``dispatch`` spans a job (``dispatches_a_job``, which lists its
cells; ``[bench] spans`` here).  ``None`` over a program whose
``collect`` states no ``outputs`` (the parent of PR 38)."""

import program_spans as PS

COLLECT = "dryad:other:collect"


def outputs(job):
    stated = [s for s in PS.named(job, COLLECT) if "outputs" in s.stats]
    return PS.total(stated, "outputs") if stated else None


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    return PS.median_over_jobs(PS.of(cell, __file__), "bench:requery", outputs)
