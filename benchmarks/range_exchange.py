"""What a range exchange across chips moves, reckoned from the cell's
shapes (for ``metrics/collective_ici_share.py``).

The program lays every chip's rows out in ``chips`` buckets of
``rows a chip x slack / chips`` slots and ships all but its own in one
``all_to_all``: padded slots travel like rows.  A slot is the row's
columns and its validity byte.  The program says the same of itself in
the ``xchg_ici_bytes`` stat of its ``dryad:dispatch:*`` spans
(``plan/xchgplan.py::flat_accounting``); this file takes nothing from
the program, so the two can be set against each other."""

import math

SLACK = 2.0  # DryadConfig().shuffle_slack, the configuration's default
SLOT_BYTES = 4 + 4 + 1  # int32 key, f32 payload, validity


def bucket_rows(rows_a_chip: int, chips: int) -> int:
    """Slots of one (source, destination) bucket: ``SLACK`` times an
    even share, at least 8, never more than the source holds."""
    return min(rows_a_chip, max(8, math.ceil(rows_a_chip * SLACK / chips)))


def ici_bytes_a_dispatch(rows: int, chips: int) -> int:
    """Bytes one chip puts on the ICI in one dispatch of ``order_by``
    over ``rows`` rows sharded evenly over ``chips``: every bucket but
    its own."""
    return (chips - 1) * bucket_rows(rows // chips, chips) * SLOT_BYTES


def roofline_share(ici_bytes: float, seconds: float, ici_bits_per_s: float) -> float:
    """Percent of the chip's ICI peak that ``ici_bytes`` in ``seconds``
    comes to."""
    return 100.0 * ici_bytes / seconds / (ici_bits_per_s / 8.0)
