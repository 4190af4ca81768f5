"""The exchange collective's share of its roofline, percent: the bytes
a chip put on the ICI in the window's dispatches (the
``xchg_ici_bytes`` stat of its ``dryad:dispatch:*`` spans, padded
bucket slots included: they travel) over the seconds a chip spent in
operations under ``dryad.exchange.collective`` in the window, over the
chip's ICI peak (``peaks.json``, ``ici_bits_per_s`` / 8).  The first
read prints the bytes a dispatch beside what
``range_exchange.ici_bytes_a_dispatch`` reckons from the cell's shapes:
a reading over 100% is a wrong count.  ``None`` where the spans lack
the stat (the parent of PR 30), where nothing crossed the ICI (P = 1)
and where no operation carries the scope."""

import program_spans as PS
import range_exchange as RX


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    summary = PS.of(cell, __file__)
    share = PS.under(summary, "dryad.exchange.collective")
    if not share:
        return None
    seconds = share / 100.0 * summary.busy_s  # mean over chips, the window
    counted = [s.stats["xchg_ici_bytes"]
               for s in PS.named(summary.spans, "dryad:dispatch:*")
               if "xchg_ici_bytes" in s.stats]
    if not sum(counted):
        return None
    reckoned = RX.ici_bytes_a_dispatch(cell.job.input_rows(cell.params), cell.chips)
    print(f"[bench] ici dispatches={len(counted)} bytes_a_dispatch={max(counted)} "
          f"reckoned_from_shapes={reckoned} collective_s={seconds:.6f}", flush=True)
    return RX.roofline_share(sum(counted), seconds, cell.peaks["ici_bits_per_s"])
