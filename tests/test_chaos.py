"""Seeded chaos differential suite.

Fixed multi-stage pipelines run under an installed ``FaultPlan``
(probabilistic stage failures + injected stage delays drawn from one
seeded stream) and must still produce results bit-identical to the
NumPy oracle, within bounded attempt counts — recovery is not allowed
to change answers.  Plus the three directed scenarios the tentpole
calls out: silent checkpoint corruption (CRC-detected, recomputed),
a worker killed mid-vertex-job (re-execution on survivors), and a
deterministic always-failing stage (fails fast inside the retry budget
with the full attempt history attached).
"""

import threading
import time

import numpy as np
import pytest

from dryad_tpu import DryadConfig, DryadContext
from dryad_tpu.exec.failure import JobFailedError
from dryad_tpu.exec.faults import (
    FaultPlan,
    InjectedStageFailure,
    install_plan,
    set_fake_checkpoint_corruption,
    set_fake_stage_failure,
)
from tests.oracle import check

pytestmark = pytest.mark.chaos

SEEDS = [0, 1, 2]

# fast-retry config for chaos runs: the plan injects at most 2 failures
# per stage, comfortably inside the 4-attempt budget, and the backoff
# base keeps total injected wait time negligible
CHAOS_CONFIG = dict(
    max_stage_failures=4,
    retry_backoff_base=0.002,
    retry_backoff_max=0.02,
)


def _plan(seed: int) -> FaultPlan:
    return FaultPlan(
        seed=seed,
        stage_failure_prob=0.25,
        max_failures_per_stage=2,
        stage_delay_prob=0.2,
        stage_delay_seconds=0.005,
    )


def _data(n=800):
    rng = np.random.default_rng(42)
    return {
        "k": rng.integers(0, 13, n).astype(np.int32),
        "v": rng.standard_normal(n).astype(np.float32),
    }


def _pipeline_groupby_sort(ctx):
    """group_by (sum, count) -> order_by: two exchanges."""
    tbl = _data()
    q = (
        ctx.from_arrays(tbl)
        .group_by("k", {"s": ("sum", "v"), "n": ("count", None)})
        .order_by([("s", True)])
    )
    ks = np.unique(tbl["k"])
    expected = {
        "k": ks,
        "s": np.array(
            [tbl["v"][tbl["k"] == k].sum() for k in ks], np.float32
        ),
        "n": np.array([(tbl["k"] == k).sum() for k in ks], np.int64),
    }
    return q, expected


def _pipeline_join_agg(ctx):
    """hash join -> group_by: join exchange + aggregation exchange."""
    left = _data(600)
    rng = np.random.default_rng(7)
    right = {
        "k": np.arange(13, dtype=np.int32),
        "w": rng.standard_normal(13).astype(np.float32),
    }
    q = (
        ctx.from_arrays(left)
        .join(ctx.from_arrays(right), "k")
        .group_by("k", {"m": ("max", "w"), "n": ("count", None)})
    )
    ks = np.unique(left["k"])
    expected = {
        "k": ks,
        "m": np.array([right["w"][k] for k in ks], np.float32),
        "n": np.array([(left["k"] == k).sum() for k in ks], np.int64),
    }
    return q, expected


def _pos(c):
    return c["v"] > 0


def _pipeline_filter_topk(ctx):
    """where -> order_by -> take: filter + range exchange + head."""
    tbl = _data(500)
    q = (
        ctx.from_arrays(tbl)
        .where(_pos)
        .order_by([("v", True)])
        .take(20)
    )
    mask = tbl["v"] > 0
    order = np.argsort(-tbl["v"][mask], kind="stable")[:20]
    expected = {
        "k": tbl["k"][mask][order],
        "v": tbl["v"][mask][order],
    }
    return q, expected


PIPELINES = [
    _pipeline_groupby_sort,
    _pipeline_join_agg,
    _pipeline_filter_topk,
]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize(
    "pipeline", PIPELINES, ids=lambda f: f.__name__.removeprefix("_pipeline_")
)
def test_chaos_pipeline_matches_oracle(pipeline, seed, mesh8):
    ctx = DryadContext(num_partitions_=8, config=DryadConfig(**CHAOS_CONFIG))
    q, expected = pipeline(ctx)
    install_plan(_plan(seed))
    try:
        out = q.collect()
    finally:
        install_plan(None)
    check(out, expected)
    # bounded recovery: per-stage failures stay under the plan cap and
    # the budget; the job completed without a terminal failure
    kinds = [e["kind"] for e in ctx.events.events()]
    assert "job_failed" not in kinds
    assert "job_complete" in kinds
    per_stage = {}
    for e in ctx.events.filter("stage_failed"):
        per_stage[e["name"]] = per_stage.get(e["name"], 0) + 1
    assert all(n <= 2 for n in per_stage.values()), per_stage


def test_chaos_replay_is_deterministic(mesh8):
    """Same seed -> identical injected-failure schedule (the property
    that makes a chaos failure reproducible)."""

    def run():
        ctx = DryadContext(
            num_partitions_=8, config=DryadConfig(**CHAOS_CONFIG)
        )
        q, _ = _pipeline_groupby_sort(ctx)
        install_plan(_plan(1))
        try:
            q.collect()
        finally:
            install_plan(None)
        return [
            (e["name"], e["version"])
            for e in ctx.events.filter("stage_failed")
        ]

    assert run() == run()


def test_chaos_checkpoint_corruption_recomputes(mesh8, tmp_path):
    """Silent bit rot in a persisted checkpoint: the CRC catches it at
    load, the stage recomputes, and the answer matches the oracle."""
    cdir = str(tmp_path / "ckpt")
    cfg = DryadConfig(checkpoint_dir=cdir, **CHAOS_CONFIG)

    ctx1 = DryadContext(num_partitions_=8, config=cfg)
    q1, expected = _pipeline_groupby_sort(ctx1)
    set_fake_checkpoint_corruption(1)  # rot the first checkpoint saved
    out1 = q1.collect()
    check(out1, expected)  # in-HBM results are unaffected by the rot

    # a restarted driver resumes from the checkpoint store: the rotted
    # entry must fail its CRC and recompute, not serve garbage
    ctx2 = DryadContext(num_partitions_=8, config=cfg)
    q2, _ = _pipeline_groupby_sort(ctx2)
    out2 = q2.collect()
    check(out2, expected)
    kinds = [e["kind"] for e in ctx2.events.events()]
    assert "checkpoint_corrupt" in kinds, kinds
    assert "job_complete" in kinds


def _even(cols):
    return cols["k"] % 2 == 0


def test_chaos_worker_kill_reexecutes_on_survivor():
    """A worker killed while stalling on its vertex task: the driver
    reaps it, re-executes the task on the survivor, and the assembled
    result still matches the oracle exactly."""
    from dryad_tpu.cluster.localjob import LocalJobSubmission

    rng = np.random.default_rng(5)
    tbl = {
        "k": rng.integers(0, 100, 3000).astype(np.int32),
        "v": rng.standard_normal(3000).astype(np.float32),
    }
    with LocalJobSubmission(num_workers=2, devices_per_worker=1) as sub:
        ctx = DryadContext(num_partitions_=1)
        q = ctx.from_arrays(tbl).where(_even).project(["k", "v"])
        sub.submit_partitioned(q, nparts=4)  # warm both workers

        sub.inject_delay(worker=1, seconds=30.0, count=1)

        def killer():
            time.sleep(1.0)  # let the stalled task dispatch first
            sub._handles[1].kill()

        t = threading.Thread(target=killer)
        t.start()
        # speculation off: ONLY worker-death re-execution can finish
        # the stalled partition
        out = sub.submit_partitioned(q, nparts=4, speculation=False)
        t.join()
        mask = tbl["k"] % 2 == 0
        check(
            {"k": np.sort(out["k"]), "v": np.sort(out["v"])},
            {"k": np.sort(tbl["k"][mask]), "v": np.sort(tbl["v"][mask])},
        )
        kinds = [e["kind"] for e in sub.events.events()]
        assert "worker_dead" in kinds
        assert "vertex_retry" in kinds
        assert "vertex_job_complete" in kinds


def test_chaos_gang_kill_mid_collective_auto_recovers():
    """FaultPlan extended to GANG runs (ROADMAP open item): a seeded
    plan with ``worker_kill_prob`` installed on ONE gang member via the
    ``set_fault`` mailbox command kills that worker process inside its
    group_by stage — its peer is left stranded in the stage's
    collectives (mid-collective death) — and ``submit()``'s
    auto-recovery rebuilds the gang and still returns the oracle
    answer."""
    from dryad_tpu.cluster.localjob import LocalJobSubmission

    rng = np.random.default_rng(3)
    tbl = {
        "k": rng.integers(0, 13, 800).astype(np.int32),
        "v": rng.standard_normal(800).astype(np.float32),
    }
    with LocalJobSubmission(num_workers=2, devices_per_worker=1) as sub:
        ctx = DryadContext(num_partitions_=2)
        q = ctx.from_arrays(tbl).group_by(
            "k", {"s": ("sum", "v"), "n": ("count", None)}
        )
        sub.inject_fault(
            None,
            plan={"seed": 3, "worker_kill_prob": 1.0,
                  "max_worker_kills": 1, "stages": ["group_by"]},
            workers=[1],
        )
        out = sub.submit(q)
        ks = np.unique(tbl["k"])
        exp_s = np.array(
            [tbl["v"][tbl["k"] == kk].sum() for kk in ks], np.float32
        )
        assert sorted(out["k"].tolist()) == ks.tolist()
        order = np.argsort(out["k"])
        np.testing.assert_allclose(out["s"][order], exp_s, rtol=1e-4)
        kinds = [e["kind"] for e in sub.events.events()]
        assert "gang_member_lost_mid_job" in kinds
        assert "gang_rebuild" in kinds


def test_chaos_deterministic_stage_fails_fast_with_history(mesh8):
    """An always-failing stage (stable error) is classified
    deterministic on its second identical failure and fails the job
    INSIDE the retry budget, attempt history attached."""
    ctx = DryadContext(
        num_partitions_=8, config=DryadConfig(max_stage_failures=5)
    )
    set_fake_stage_failure("group_by", -1)  # every attempt, stable msg
    with pytest.raises(JobFailedError) as ei:
        ctx.from_arrays(_data(100)).group_by(
            "k", {"n": ("count", None)}
        ).collect()
    e = ei.value
    assert e.attempts, "no attempt history attached"
    assert len(e.attempts) == 2 <= 5  # failed fast, not at the budget
    assert e.attempts[0].kind == "transient"
    assert e.attempts[-1].kind == "deterministic"
    assert "attempt history" in str(e)
    assert "deterministic" in str(e)
    evs = ctx.events.filter("job_failed")
    assert evs and evs[-1]["failure_kind"] == "deterministic"


def test_chaos_budget_exhaustion_carries_history(mesh8):
    """Distinct transient failures burn the whole budget; the terminal
    error still carries every attempt."""
    ctx = DryadContext(
        num_partitions_=8,
        config=DryadConfig(max_stage_failures=3, retry_backoff_base=0.001),
    )
    set_fake_stage_failure("group_by", 99)  # varying msg: transient
    with pytest.raises(JobFailedError, match="failure budget") as ei:
        ctx.from_arrays(_data(100)).group_by(
            "k", {"n": ("count", None)}
        ).collect()
    assert len(ei.value.attempts) == 3
    assert all(a.kind == "transient" for a in ei.value.attempts)


# -- async dispatch window under chaos (exec/outofcore + exec/pipeline) ------


def test_chaos_async_dispatch_window_matches_serial(mesh8):
    """FaultPlan stage failures land while the dispatch window holds
    chunks in flight: the executor retries each injected failure inside
    its budget at dispatch time, the window drains cleanly (no
    collector deadlock, no terminal failure), and the committed stream
    stays byte-identical to the ``dispatch_depth=1`` serial driver."""
    from tests.test_fuzz_differential import _assert_byte_identical_rows

    rng = np.random.default_rng(6)
    chunks = [
        {
            "k": rng.integers(0, 13, 600).astype(np.int32),
            "v": rng.standard_normal(600).astype(np.float32),
        }
        for _ in range(4)
    ]

    def run(depth, fuse):
        ctx = DryadContext(
            num_partitions_=8,
            config=DryadConfig(
                stream_pipeline_depth=1, dispatch_depth=depth,
                chunk_fuse=fuse, stream_combine_rows=20,
                **CHAOS_CONFIG,
            ),
        )
        install_plan(_plan(2))
        try:
            out = (
                ctx.from_stream(
                    iter([{c: v.copy() for c, v in ch.items()}
                          for ch in chunks])
                )
                .group_by("k", {"s": ("sum", "v"), "n": ("count", None)})
                .collect()
            )
        finally:
            install_plan(None)
        return out, ctx

    on, ctx_on = run(3, 2)
    off, _ = run(1, 1)
    kinds = [e["kind"] for e in ctx_on.executor.events.events()]
    assert "dispatch_window" in kinds
    assert "stage_failed" in kinds, "the chaos plan should have fired"
    assert "job_failed" not in kinds
    _assert_byte_identical_rows(on, off, "async chaos vs serial")


def test_chaos_drain_site_retry_and_terminal_error_no_deadlock():
    """The window's drain-site contract, exercised directly: a fetch
    that dies with a transient injected fault is re-executed via the
    dispatcher's retry callback AT ITS COMMIT POSITION (submit order is
    preserved around it), a terminal ``JobFailedError`` propagates to
    the caller, and ``close()`` joins the collector in both cases."""
    from dryad_tpu.exec.outofcore import _AsyncDispatcher

    class _FakeCtx:
        # the dispatcher hands each query straight back as its fetch
        def run_to_host_async(self, fetch):
            return fetch

        def run_many_to_host_async(self, fetches):
            return list(fetches)

    def ok(i):
        return lambda: {"i": np.array([i])}

    def boom(exc):
        def fetch():
            raise exc

        return fetch

    retried = []

    def retry(tag):
        retried.append(tag)
        return {"i": np.array([tag])}

    got = []
    dsp = _AsyncDispatcher(_FakeCtx(), 3, 2, retry=retry)
    try:
        for i in range(7):
            dsp.submit(
                i,
                boom(InjectedStageFailure("mid-window")) if i == 3
                else ok(i),
            )
            # interleaved non-blocking commits, like the driver loop
            got.extend(dsp.ready())
        got.extend(dsp.drain())
    finally:
        dsp.close()
    # ready() committed a prefix, drain() the rest — together they must
    # cover 0..6 in submit order, with chunk 3 served by the retry
    assert retried == [3]
    assert [(tag, int(t["i"][0])) for tag, t in got] == [
        (i, i) for i in range(7)
    ]
    assert dsp.win.retries == 1

    dsp2 = _AsyncDispatcher(_FakeCtx(), 3, 1, retry=retry)
    try:
        dsp2.submit(0, boom(JobFailedError("retry budget burned")))
        with pytest.raises(JobFailedError):
            list(dsp2.drain())
    finally:
        dsp2.close()  # a poisoned window must still join cleanly
    assert retried == [3], "terminal failures must not re-dispatch"


# -- flight recorder forensics (obs.flightrec + tools.blackbox) --------------


def test_chaos_worker_kill_leaves_recoverable_blackbox_dumps():
    """The PR's crash-forensics contract: a seeded FaultPlan kill
    mid-collective takes the worker down via ``os._exit`` (no atexit,
    no unwinding) — yet every process leaves a ``blackbox-<pid>.json``
    under the shared job root, and ``tools.blackbox`` merges them into
    one clock-corrected timeline whose fatal window contains both the
    worker-side kill and the driver-side loss detection, in causal
    order."""
    import os

    from dryad_tpu.cluster.localjob import LocalJobSubmission
    from dryad_tpu.tools import blackbox

    rng = np.random.default_rng(3)
    tbl = {
        "k": rng.integers(0, 13, 800).astype(np.int32),
        "v": rng.standard_normal(800).astype(np.float32),
    }
    with LocalJobSubmission(num_workers=2, devices_per_worker=1) as sub:
        root = sub.root
        ctx = DryadContext(num_partitions_=2)
        q = ctx.from_arrays(tbl).group_by(
            "k", {"s": ("sum", "v"), "n": ("count", None)}
        )
        sub.submit(q)  # warm run: collects telemetry (clock offsets)
        sub.inject_fault(
            None,
            plan={"seed": 3, "worker_kill_prob": 1.0,
                  "max_worker_kills": 1, "stages": ["group_by"]},
            workers=[1],
        )
        sub.submit(q)  # kill + auto-recovery
        dump_dir = os.path.join(root, "blackbox")
        dumps = blackbox.load_dumps(dump_dir)
        roles = {d["role"] for d in dumps}
        # the killed worker dumped BEFORE os._exit, the driver dumped
        # on detecting the loss
        assert "driver" in roles and "worker-1" in roles, roles
        killed = [
            d for d in dumps
            if d["reason"].startswith("worker_killed:")
        ]
        assert killed and killed[0]["role"] == "worker-1"
        drv = [d for d in dumps if d["role"] == "driver"][0]
        assert drv["reason"].startswith("gang_member_lost:")
        # the warm run's telemetry drain left the offset table the
        # merge corrects with
        assert drv["info"].get("worker_offsets")
    # after shutdown the surviving workers dumped too (atexit)
    dumps = blackbox.load_dumps(os.path.join(root, "blackbox"))
    assert len(dumps) >= 3
    merged = blackbox.merge(dumps, window_s=30.0)
    kinds = [e["kind"] for e in merged["events"]]
    assert "worker_killed_injected" in kinds
    assert "gang_member_lost_mid_job" in kinds
    # causal order survives the merge: the injected kill precedes the
    # driver noticing the dead gang member
    assert kinds.index("worker_killed_injected") < kinds.index(
        "gang_member_lost_mid_job"
    )
    # every source is clock-tagged in the summary and the text render
    # names the fatal window
    text = blackbox.render(merged)
    assert "worker_killed" in text
    assert "clock_offset" in text


def test_chaos_straggler_diagnosed_and_parity_prelaunched():
    """The diagnosis->control loop: a 6s injected straggler on one
    coded vertex is (1) diagnosed online (``straggler`` rule, in-flight
    evidence) and (2) masked by parity pre-launched from PRIOR-job
    statistics — trigger ``straggler``, zero failures, and the answer
    assembled while the delayed vertex was still inside its delay.
    That last is read off the ORDER of the job's events, not off the
    machine's clock: k or more completions without the delayed
    vertex's, that vertex cancelled as the one task still running, then
    the reconstruction (one parity row used) and the job's completion."""
    from dryad_tpu.cluster.localjob import LocalJobSubmission

    DELAY = 6.0
    rng = np.random.default_rng(5)
    tbl = {
        "k": rng.integers(0, 20, 3000).astype(np.int32),
        "v": rng.integers(-100, 100, 3000).astype(np.int32),
    }
    ctx = DryadContext(num_partitions_=1)
    q = ctx.from_arrays(tbl).group_by(
        "k", {"c": ("count", None), "s": ("sum", "v")}
    )
    with LocalJobSubmission(num_workers=2, devices_per_worker=1) as sub:
        out0 = sub.submit_partitioned(q, nparts=2, coded=True)  # seeds stats
        assert sub.diagnosis.stats_for("coded").durations, (
            "warm run must feed the engine's coded duration model"
        )
        sub.inject_delay(worker=1, seconds=DELAY, count=1)
        out = sub.submit_partitioned(q, nparts=2, coded=True)
        for c in out0:
            assert out0[c].tobytes() == out[c].tobytes(), c
        evs = sub.events.events()
        # zero failures: this was pure pre-launch, not failure masking
        assert [e for e in evs if e["kind"] == "coded_task_failed"] == []
        launches = [e for e in evs if e["kind"] == "coded_launch"]
        assert launches and launches[-1]["trigger"] == "straggler"
        diags = [
            e for e in evs
            if e["kind"] == "diagnosis" and e["rule"] == "straggler"
        ]
        assert diags, "no online straggler diagnosis emitted"
        assert diags[-1]["evidence"]["in_flight"] is True
        # the diagnosis precedes the launch it drove
        assert evs.index(diags[-1]) < evs.index(launches[-1])
        # masked: the delayed job's own events, in the order they came
        job = evs[max(
            i for i, e in enumerate(evs) if e["kind"] == "coded_job_start"
        ):]
        assert evs.index(launches[-1]) >= evs.index(job[0])
        done = [e for e in job if e["kind"] == "coded_task_complete"]
        held = {0, 1} - {e["coded"] for e in done}
        assert len(held) == 1, f"straggler not masked: completions {done}"
        assert any(e["parity"] for e in done)
        (cancel,) = [e for e in job if e["kind"] == "coded_cancel"]
        (rebuilt,) = [e for e in job if e["kind"] == "coded_reconstruct"]
        (complete,) = [e for e in job if e["kind"] == "coded_job_complete"]
        # the delayed vertex was the one task still running when the
        # k-th completion came, and the answer did not wait for it
        assert cancel["canceled"] >= 1
        assert rebuilt["parity_used"] == 1 and held.isdisjoint(rebuilt["used"])
        assert (
            job.index(launches[-1]) < job.index(done[-1])
            < job.index(cancel) < job.index(rebuilt) < job.index(complete)
        )
        # and the engine retained it for explain/jobview
        assert "straggler" in [d["rule"] for d in sub.diagnosis.diagnoses()]


@pytest.mark.slow
def test_chaos_worker_killed_mid_level_minus1_merge():
    """A seeded FaultPlan kill inside the worker-side combine
    (``combineparts``, level -1 of the gang combine tree) must not
    cost correctness: the part files are durable on the job root, so
    the same submit falls back to flat assembly and still answers;
    after ``rebuild_gang`` a replay with the tree on is byte-identical
    to the flat oracle; and the killed worker left a recoverable
    blackbox dump naming the combineparts stage."""
    import os

    from dryad_tpu.cluster.localjob import LocalJobSubmission
    from dryad_tpu.tools import blackbox

    rng = np.random.default_rng(11)
    tbl = {
        "k": rng.integers(0, 32, 2000).astype(np.int32),
        "v": rng.integers(-500, 500, 2000).astype(np.int32),
    }

    def mkq(on):
        ctx = DryadContext(
            num_partitions_=1,
            config=DryadConfig(gang_combine_tree=on),
        )
        return ctx.from_arrays(tbl).group_by(
            "k", {"s": ("sum", "v"), "mn": ("min", "v")}
        )

    with LocalJobSubmission(num_workers=2, devices_per_worker=1) as sub:
        root = sub.root
        flat = sub.submit_partitioned(mkq(False), nparts=8, coded=False)
        sub.inject_fault(
            None,
            plan={"seed": 3, "worker_kill_prob": 1.0,
                  "max_worker_kills": 1, "stages": ["combineparts"]},
            workers=[1],
        )
        # level -1 is an optimization, never a durability dependency:
        # the kill lands mid-merge, the driver falls back to flat
        # assembly of the durable part files and still answers
        fallback = sub.submit_partitioned(mkq(True), nparts=8, coded=False)
        for c in flat:
            assert flat[c].tobytes() == fallback[c].tobytes(), c
        sub.rebuild_gang(2)
        n0 = len(sub.events.events())
        replay = sub.submit_partitioned(mkq(True), nparts=8, coded=False)
        for c in flat:
            assert flat[c].tobytes() == replay[c].tobytes(), c
        # the rebuilt gang runs the tree for real this time
        pre = [
            e for e in sub.events.events()[n0:]
            if e["kind"] == "gang_partial_combine"
        ]
        assert len(pre) == 2, pre
        dumps = blackbox.load_dumps(os.path.join(root, "blackbox"))
        killed = [
            d for d in dumps
            if d["reason"] == "worker_killed:combineparts"
        ]
        assert killed and killed[0]["role"] == "worker-1"
    merged = blackbox.merge(
        blackbox.load_dumps(os.path.join(root, "blackbox")), window_s=30.0
    )
    kinds = [e["kind"] for e in merged["events"]]
    assert "worker_killed_injected" in kinds


def test_chaos_gang_kill_preserves_query_trace_and_bytes():
    """End-to-end tracing under gang failure: a seeded kill takes one
    gang member mid-query, auto-recovery rebuilds the gang and re-runs
    — and the merged cross-process trace still yields ONE complete
    critical path for the retried query (worker spans shipped back on
    the telemetry channel carry the qid from the re-stamped mailbox
    envelopes), with results byte-identical to an undisturbed rerun."""
    from dryad_tpu.cluster.localjob import LocalJobSubmission
    from dryad_tpu.obs import critpath, tracectx

    rng = np.random.default_rng(7)
    tbl = {
        "k": rng.integers(0, 11, 600).astype(np.int32),
        "v": rng.standard_normal(600).astype(np.float32),
    }
    with LocalJobSubmission(num_workers=2, devices_per_worker=1) as sub:
        ctx = DryadContext(num_partitions_=2)
        q = ctx.from_arrays(tbl).group_by(
            "k", {"s": ("sum", "v"), "n": ("count", None)}
        )
        sub.inject_fault(
            None,
            plan={"seed": 7, "worker_kill_prob": 1.0,
                  "max_worker_kills": 1, "stages": ["group_by"]},
            workers=[1],
        )
        tctx = tracectx.mint(tenant="chaos")
        with tracectx.activate(tctx):
            out = sub.submit(q)
        kinds = [e["kind"] for e in sub.events.events()]
        assert "gang_member_lost_mid_job" in kinds
        assert "gang_rebuild" in kinds
        evs = sub.events.events()
        # worker spans from the RETRIED run shipped back qid-stamped
        wspans = [e for e in evs if e.get("kind") == "span"
                  and e.get("cat") == "worker"]
        assert wspans, "no worker spans in the merged stream"
        assert any(s.get("qid") == tctx.qid for s in wspans)
        # one complete critical path for the query, flat-fallback
        # (post-rebuild) execution included
        bd = critpath.fold_query(evs, tctx.qid)
        assert bd is not None and bd.phases
        assert sum(bd.phases.values()) == pytest.approx(bd.total_s)
        assert bd.total_s > 0 and bd.spans >= len(wspans)
        # byte identity: the kill consumed its budget, so a rerun on
        # the rebuilt gang is undisturbed — answers must not change
        again = sub.submit(q)
        assert set(out) == set(again)
        for c in out:
            assert out[c].tobytes() == again[c].tobytes(), c
