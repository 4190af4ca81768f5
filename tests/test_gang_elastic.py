"""Mid-job gang elasticity: a gang member dying while an SPMD job runs
no longer fails the submission — the gang auto-shrinks to the
survivors and re-runs (the reference's mutable computer set,
``ClusterInterface/Interfaces.cs:336-343``, ``LocalScheduler.cs:88``)."""

import threading
import time

import numpy as np
import pytest

from dryad_tpu import DryadContext
from dryad_tpu.cluster.localjob import LocalJobSubmission


def _wordcount(ctx, words):
    return (
        ctx.from_arrays({"w": words})
        .group_by("w", {"c": ("count", None)})
    )


def test_gang_member_death_mid_job_auto_shrinks():
    rng = np.random.default_rng(3)
    vocab = np.array(["a", "bb", "ccc", "dddd"], object)
    words = vocab[rng.integers(0, 4, 600)]
    with LocalJobSubmission(num_workers=2, devices_per_worker=1) as sub:
        ctx = DryadContext(num_partitions_=2)
        out = sub.submit(_wordcount(ctx, words))
        assert int(np.sum(out["c"])) == 600  # healthy gang works

        # kill one member shortly after the next submission starts —
        # it lands mid-job (fresh plan => multi-second compile)
        def killer():
            time.sleep(0.4)
            sub._handles[1].kill()  # SIGKILL: decisive mid-job death

        t = threading.Thread(target=killer)
        t.start()
        tbl2 = {
            "k": rng.integers(0, 40, 2000).astype(np.int32),
            "v": rng.standard_normal(2000).astype(np.float32),
        }
        q2 = (
            ctx.from_arrays(tbl2)
            .group_by("k", {"s": ("sum", "v"), "n": ("count", None)})
            .order_by([("k", False)])
        )
        out2 = sub.submit(q2)
        t.join()
        if sub.n == 2:
            # rare under-load race: the job finished before the kill
            # landed mid-flight — the worker is dead NOW, so the next
            # submit exercises the death-at-submit-start recovery path
            out2 = sub.submit(q2)

        assert sub.n == 1, "gang did not shrink to the survivor"
        assert sorted(out2["k"].tolist()) == sorted(
            np.unique(tbl2["k"]).tolist()
        )
        ref = {
            int(k): int((tbl2["k"] == k).sum())
            for k in np.unique(tbl2["k"])
        }
        got = dict(zip(out2["k"].tolist(), out2["n"].tolist()))
        assert got == ref
        kinds = [e["kind"] for e in sub.events.events()]
        assert "gang_member_lost_mid_job" in kinds
        assert "gang_rebuild" in kinds

        # the reshaped gang keeps serving
        out3 = sub.submit(_wordcount(ctx, words))
        assert int(np.sum(out3["c"])) == 600


def test_auto_recover_off_raises():
    with LocalJobSubmission(num_workers=2, devices_per_worker=1) as sub:
        ctx = DryadContext(num_partitions_=2)
        words = np.array(["x", "y"] * 50, object)
        sub.submit(_wordcount(ctx, words))  # warm + prove healthy
        sub.launcher.stop(sub._handles[0])
        with pytest.raises((RuntimeError, TimeoutError)):
            sub.submit(_wordcount(ctx, words), auto_recover=False)
