"""Median, over the window's requeries, of the slowest chip's seconds
in operations under ``dryad.join.materialize`` (the two stacked gathers
over the pair slots) over the fastest chip's.  Every chip gathers the
same number of slots from tables of the same shape, so a ratio over 1
is what the ADDRESSES cost: which rows the live slots repeat and what
the dead ones read (``ops/join.py::_slot_owners``).  A job takes its
slowest chip's seconds; the others wait for it in the ``psum``.  1.20
where every dead slot read ONE row (PR 45's logs).  ``None`` where no
operation carries the scope, or on one chip."""

import join_observed as JO


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    planes = JO.planes_of(cell, __file__)
    if planes is None:
        return None
    return JO.chip_spread(planes, "dryad.join.materialize")
