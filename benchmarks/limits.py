#!/usr/bin/env python3
"""Read what the limits of ``correct`` are set from, in one process:

    python benchmarks/limits.py --workload <name> --seeds 1,2,3 [--control-only]

For every seed: one table from the seed, one pair through the timed
path (``Query.collect()`` on the cell's chips), and each number the
job's ``compare`` yields, beside its limit; then the *control* — the
reference put in the program's place, computed in the precision below
the one the configuration states (``control`` in the job file) — through
the same ``compare``.  A limit has to lie above the program's largest
and below the control's smallest (PERF.md gives the readings).  The
benchmark's own runs do not run this.
"""

import argparse
import sys
import tempfile

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-only", action="store_true",
                    help="NumPy only: no chip is needed")
    args = ap.parse_args(argv)
    import numpy as np

    cell = run.load_cell(args.workload)
    job, params = cell.job, cell.params
    ctx = None
    if not args.control_only:
        try:
            run.require_chips(cell.chips)
        except run.NoChips as err:
            print(f"benchmarks/limits.py: {err}.  No fallback.", file=sys.stderr)
            return 1
        from dryad_tpu import DryadContext
        from dryad_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()

        ctx = DryadContext(num_partitions_=cell.chips)
    bad = 0
    with tempfile.TemporaryDirectory(prefix="dryad_limits_") as workdir:
        for seed in (int(s) for s in args.seeds.split(",")):
            table = job.make_table(np.random.default_rng([seed, 0]), params,
                                   workdir, 0)
            sides = {}
            if ctx is not None:
                query = job.bind(ctx, table, params)
                sides["program.fresh"] = query.collect()
                sides["program.requery"] = query.collect()
            sides["control"] = job.control(table, params)
            for side, answer in sides.items():
                checks = job.compare(table, answer, params)
                passed = all(v <= lim for v, lim in checks.values())
                bad += passed == (side == "control")
                for name, (value, limit) in sorted(checks.items()):
                    run.say("limit", workload=cell.name, seed=seed, side=side,
                            number=name, value=value, limit=limit)
                run.say("verdict", workload=cell.name, seed=seed, side=side,
                        correct=int(passed))
    run.say("limits", workload=cell.name,
            wrong_verdicts=bad)  # a program side that fails, a control that passes
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
