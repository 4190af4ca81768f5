"""Interactive-analytics shape: materialize one aggregate with
``cache()`` (the reference's temp-table pattern,
``DryadLinqQueryable.cs:3948`` isTemp — kept in HBM, not DFS), then
branch several queries from it without recomputing; persist one branch
to a DFS-scheme store through the file-plane gateway.

The STRING group_by underneath rides the auto-dense MXU path
(dictionary codes, no shuffle — ``ops/stringcode.py``); ``explain``
shows the shuffle-free stage.

Run (CPU mesh):
    JAX_PLATFORMS=cpu python samples/analytics_cached.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The CPU-mesh demo path: pin the virtual mesh before the first backend
# query.  Without JAX_PLATFORMS=cpu the sample runs on jax's default
# devices (the chip).
if os.environ.get("JAX_PLATFORMS", "") == "cpu":
    from dryad_tpu.parallel.mesh import force_cpu_backend

    force_cpu_backend(8)

import numpy as np

from dryad_tpu import DryadContext
from dryad_tpu.tools.explain import explain


def main() -> None:
    rng = np.random.default_rng(0)
    n = 100_000
    users = np.array([f"user{int(i):04d}" for i in rng.integers(0, 2000, n)], object)
    spend = (rng.gamma(2.0, 10.0, n)).astype(np.float32)

    ctx = DryadContext()
    events = ctx.from_arrays({"user": users, "spend": spend})

    per_user = events.group_by(
        "user", {"total": ("sum", "spend"), "visits": ("count", None)}
    )
    print(explain(per_user))

    agg = per_user.cache()  # one execution, HBM-resident

    # three branches, zero recomputation of the aggregate
    top = agg.order_by([("total", True)]).take(5).collect()
    print("\ntop spenders:")
    for u, t, v in zip(top["user"], top["total"], top["visits"]):
        print(f"  {u}: {t:9.2f} over {int(v)} visits")

    whales = agg.where(lambda c: c["total"] > 500.0).count()
    # single-column distinct = the vocabulary query (dense path too)
    vocab = events.project(["user"]).distinct()
    print(f"\nusers over 500.0 total: {whales}")
    print(f"distinct users: {len(vocab.collect()['user'])}")

    # persist one branch through a DFS-scheme URI (a local ProcessService
    # stands in for the gateway; set DRYAD_TPU_DFS_GATEWAY in real use)
    import tempfile

    from dryad_tpu.cluster.service import ProcessService

    with ProcessService(tempfile.mkdtemp()) as svc:
        os.environ["DRYAD_TPU_DFS_GATEWAY"] = f"127.0.0.1:{svc.port}"
        agg.order_by([("total", True)]).to_store("hdfs://warehouse/per_user")
        back = (
            DryadContext()
            .from_store("hdfs://warehouse/per_user")
            .count()
        )
        print(f"rows persisted+reread via hdfs:// gateway: {back}")
        del os.environ["DRYAD_TPU_DFS_GATEWAY"]

    ctx.release(agg)


if __name__ == "__main__":
    main()
