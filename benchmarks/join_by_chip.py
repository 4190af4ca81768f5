#!/usr/bin/env python3
"""The cell ``join-hash-4c`` by chip and by table: where a job's
seconds differ.

A job there takes its slowest chip's seconds, and the chips can differ
only under ``dryad.join.materialize`` (the two stacked gathers over the
pair slots): every other scope costs the same on every chip and table
to four digits, because its sorts run over fixed capacities, while a
gather costs by its ADDRESSES as well as by its shape.  The window
cannot show that (its metrics are medians over the pool's tables and
means over the chips); this does.  What it showed (``PERF.md`` section
6, PR 45 and PR 46): with the hot keys placed by the seed and every
dead pair slot reading ONE row, the last that owns a slot,
``materialize`` read 1.106 - 1.332 s by chip and table and the window's
median was a property of the seed's tables (9% between tables); with
the alphabet the configuration's and a dead slot reading the row of its
own number (PR 46) the ``li`` gather costs 0.712 s on every chip and
table, ``materialize`` 1.17 - 1.21 s (the slowest chip 1.02 - 1.04 of
the fastest), and what is left, 1.5% between tables, is the ``ri``
gather, whose addresses are the join itself.  One process on the
four chips: the ``pool`` tables of one seed, each bound once,
``--reps`` requeries of each under one trace, then

  ``[by_chip] table=<i> rep=<r> requery_s=...``
  ``[by_chip] table=<i> pairs=<candidate pairs a chip> recv_rows=<both exchanges'>``
  ``[by_chip] chip=<c> table=<i> busy=... <scope>=<seconds a job> ...``
  ``[by_chip] chip=<c> table=<i> materialize_ops_ms=<each operation under
  the scope, in order, of the last rep>``
  ``[by_chip] table=<i> materialize_chip_spread=<slowest chip / fastest, median over the reps>``

A change that makes the gathers cost by their shape reads the same
``materialize`` on every chip and table.  TPU only, as ``run.py`` is.

  python3 benchmarks/join_by_chip.py --seed 4600002004
"""

import argparse
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import join_observed as JO  # noqa: E402
import program_spans as PS  # noqa: E402
import run as R  # noqa: E402
import trace_reduce as TR  # noqa: E402
import xplane  # noqa: E402

CELL = "join-hash-4c"
SCOPE = "dryad.join.materialize"


def by_chip(planes):
    """The lines that say, a chip and a table, the busy seconds a job,
    the seconds under each scope, and the last rep's operations under
    ``SCOPE`` one by one; then a table's spread over the chips."""
    tables = JO.annotated(planes, "bench:table")
    ops = PS.device_ops(planes)
    for name, ivals in tables.items():
        table = name[len("bench:table"):]
        for chip, jobs in JO.scope_seconds_by_chip(planes, ivals).items():
            total = {}
            for seconds in jobs:
                for label, sec in seconds.items():
                    total[label] = total.get(label, 0.0) + sec / len(ivals)
            busy = total.pop(None)
            body = " ".join(f"{label}={sec:.4f}" for label, sec in
                            sorted(total.items(), key=lambda kv: -kv[1])[:10])
            yield f"[by_chip] chip={chip} table={table} busy={busy:.4f} {body}"
            lo, hi = ivals[-1]
            each = [f"{(e - s) * 1e3:.2f}"
                    for label, s, e in sorted(ops[chip], key=lambda op: op[1])
                    if SCOPE in label and s >= lo and e <= hi]
            yield (f"[by_chip] chip={chip} table={table} "
                   f"materialize_ops_ms={','.join(each)}")
    for name in tables:
        spread = JO.chip_spread(planes, SCOPE, kind=name)
        if spread is not None:
            yield (f"[by_chip] table={name[len('bench:table'):]} "
                   f"materialize_chip_spread={spread:.4f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args(argv)
    cell = R.load_cell(CELL)
    R.require_chips(cell.chips)
    import jax

    from dryad_tpu import DryadContext
    from dryad_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ctx = DryadContext(num_partitions_=cell.chips)
    queries = [cell.job.bind(ctx, table, cell.params)
               for table in R.make_pool(cell, args.seed, None)]
    for q in queries:
        q.collect()  # ingest, compile
    trace_dir = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    # ``JO.chip_spread`` takes its jobs inside the window's annotation
    with jax.profiler.TraceAnnotation(TR.WINDOW_ANNOTATION):
        for rep in range(args.reps):
            for i, q in enumerate(queries):
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(f"bench:table{i}"):
                    q.collect()
                print(f"[by_chip] table={i} rep={rep} "
                      f"requery_s={time.perf_counter() - t0:.4f}", flush=True)
    jax.profiler.stop_trace()
    events = ctx.events.events()
    held = [e for e in events if e["kind"] == "join_observed"][-len(queries):]
    seen = [e for e in events if e["kind"] == "exchange_observed"][-len(queries):]
    for i, (pairs, exchanged) in enumerate(zip(held, seen)):
        print(f"[by_chip] table={i} pairs={pairs['pairs']} "
              f"recv_rows={exchanged['recv_rows']}", flush=True)
    for line in by_chip(xplane.read(TR.find_xplane(trace_dir))):
        print(line, flush=True)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
