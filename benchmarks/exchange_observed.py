"""What the exchanges of a job saw, as the program says it on the
``dryad:readback:drain`` span that read the overflow flag back (PR 41):
``combine_rows_in`` (rows the combiners before the exchanges were
handed, summed over the chips), ``combine_rows_out`` (rows they left:
the rows sent), ``recv_rows_max`` (the rows the fullest chip received),
``boost``, ``overflows`` (drains of the job so far that saw the flag
set).  For ``metrics/combine_keep_ratio.py``, ``recv_balance.py`` and
``exchange_retries_a_job.py``."""

import program_spans as PS

STATS = ("combine_rows_in", "combine_rows_out", "recv_rows_max", "overflows")


def last_drain(job):
    """The stats of the job's last ``drain`` span that states them all,
    or ``None``: a retry's drain comes after the one that overflowed."""
    stated = [s.stats for s in PS.named(job, "dryad:readback:drain")
              if all(name in s.stats for name in STATS)]
    return stated[-1] if stated else None
