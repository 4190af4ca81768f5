"""ETL with independent vertex tasks + speculative duplication.

The Dryad execution model the reference is named for: a partition-local
plan runs as independent, re-executable vertices over an N-process
local cluster (``LinqToDryad/LocalJobSubmission.cs:97-147``), with the
speculative-duplication machinery live: one worker is given an injected
stall, the duration model flags the outlier
(``DrStageStatistics.cpp:93``), the task duplicates to the fast worker
and the first completion wins (``DrVertex.cpp:444`` RequestDuplicate).

Run:
    JAX_PLATFORMS=cpu python samples/etl_speculation.py

Prints the per-vertex drill-down (tools.jobview) showing the
duplication story and the compressed assembly stats.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The CPU-mesh demo path: pin the virtual mesh before the first backend
# query.  Without JAX_PLATFORMS=cpu the sample runs on jax's default
# devices (the chip).
if os.environ.get("JAX_PLATFORMS", "") == "cpu":
    from dryad_tpu.parallel.mesh import force_cpu_backend

    force_cpu_backend(2)

import numpy as np

from dryad_tpu import DryadContext
from dryad_tpu.cluster.localjob import LocalJobSubmission
from dryad_tpu.tools.jobview import build_vertex_jobs, render_vertex_job


def keep_paid(cols):
    # module-level: the plan ships to workers by pickle
    return cols["amount"] > 0


def main() -> None:
    rng = np.random.default_rng(0)
    n = 20_000
    tbl = {
        "user": rng.integers(0, 5_000, n).astype(np.int32),
        "amount": rng.normal(10.0, 30.0, n).astype(np.float32),
    }

    with LocalJobSubmission(num_workers=2, devices_per_worker=1) as sub:
        ctx = DryadContext(num_partitions_=1)
        q = ctx.from_arrays(tbl).where(keep_paid).project(["user", "amount"])

        # 8 vertex tasks over 2 workers: enough completions for the
        # duration model (MIN_SAMPLES=3) to flag the stalled outlier
        sub.submit_partitioned(q, nparts=8)  # warm worker caches
        # make worker 1 a straggler for its next vertex task
        sub.inject_delay(worker=1, seconds=6.0, count=1)
        out = sub.submit_partitioned(q, nparts=8)

        kept = int((tbl["amount"] > 0).sum())
        assert len(out["user"]) == kept
        print(f"kept {kept}/{n} rows\n")
        for vj in build_vertex_jobs(sub.events.events()):
            print(render_vertex_job(vj))
            print()


if __name__ == "__main__":
    main()
