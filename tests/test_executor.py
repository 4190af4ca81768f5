"""Executor tests: versioned retry, failure budget, adaptive overflow
retry (regression for per-device overflow flags), stats, event log."""

import numpy as np
import pytest

from dryad_tpu import DryadConfig, DryadContext
from dryad_tpu.exec.executor import StageFailedError
from dryad_tpu.exec.faults import set_fake_stage_failure
from dryad_tpu.exec.stats import StageStatistics


def test_injected_failure_retries_and_succeeds(mesh8):
    ctx = DryadContext(num_partitions_=8)
    set_fake_stage_failure("group_by", 1)
    out = ctx.from_arrays({"k": np.arange(100, dtype=np.int32)}).group_by(
        "k", {"n": ("count", None)}
    ).collect()
    assert out["n"].sum() == 100
    kinds = [e["kind"] for e in ctx.events.events()]
    assert "stage_failed" in kinds
    assert kinds.count("stage_complete") >= 1


def test_failure_budget_exceeded(mesh8):
    ctx = DryadContext(num_partitions_=8, config=DryadConfig(max_stage_failures=2))
    set_fake_stage_failure("group_by", 99)
    with pytest.raises(StageFailedError, match="failure budget"):
        ctx.from_arrays({"k": np.arange(10, dtype=np.int32)}).group_by(
            "k", {"n": ("count", None)}
        ).collect()
    assert [e for e in ctx.events.events() if e["kind"] == "job_failed"]


def test_no_silent_row_loss_on_uneven_receive(mesh8):
    """Regression: resize overflow on ONE device must trip the global
    retry — previously the per-device flag was read as 'replicated' and
    rows silently vanished (98/100 keys)."""
    ctx = DryadContext(num_partitions_=8)
    for n in (100, 257, 1000):
        out = ctx.from_arrays({"k": np.arange(n, dtype=np.int32)}).group_by(
            "k", {"c": ("count", None)}
        ).collect()
        assert len(out["k"]) == n, f"lost keys at n={n}"
        assert set(out["k"].tolist()) == set(range(n))


def test_overflow_boost_event_emitted(mesh8):
    # Distinct keys with tiny slack: no combiner help, forces boost retry.
    ctx = DryadContext(
        num_partitions_=8, config=DryadConfig(shuffle_slack=1.0)
    )
    n = 4096
    out = ctx.from_arrays({"k": np.arange(n, dtype=np.int32)}).group_by(
        "k", {"c": ("count", None)}
    ).collect()
    assert len(out["k"]) == n


def test_stage_statistics_outlier_model():
    st = StageStatistics(outlier_sigmas=3.0)
    assert st.outlier_threshold() is None  # too few samples
    for d in [1.0, 1.1, 0.9, 1.05, 0.95, 1.0, 1.02, 0.98, 1.0, 1.0]:
        st.record(d)
    thr = st.outlier_threshold()
    assert thr is not None and thr < 2.0
    assert st.is_outlier(5.0)
    assert not st.is_outlier(1.0)


def test_event_log_jsonl_roundtrip(tmp_path):
    from dryad_tpu.exec.events import EventLog

    path = str(tmp_path / "ev.jsonl")
    log = EventLog(path)
    log.emit("job_start", stages=3)
    log.emit("stage_complete", stage=1, seconds=0.5)
    log.close()
    back = EventLog.load(path)
    assert [e["kind"] for e in back] == ["job_start", "stage_complete"]
    assert back[0]["stages"] == 3


def test_scalar_min_max_on_empty_table(mesh8):
    from dryad_tpu import DryadContext

    for ctx in (DryadContext(num_partitions_=8), DryadContext(local_debug=True)):
        q = ctx.from_arrays({"v": np.arange(5, dtype=np.int32)}).where(
            lambda c: c["v"] > 100
        )
        assert q.min_("v") is None
        assert q.max_("v") is None
        assert q.count() == 0
        assert q.sum_("v") == 0


def test_compile_cache_hits_across_collects(mesh8):
    from dryad_tpu import DryadContext

    ctx = DryadContext(num_partitions_=8)
    q = ctx.from_arrays({"k": np.arange(64, dtype=np.int32)}).group_by(
        "k", {"c": ("count", None)}
    )
    q.collect()
    n1 = len(ctx.executor._compiled)
    q.collect()
    n2 = len(ctx.executor._compiled)
    assert n2 == n1, f"recompiled on identical re-collect: {n1} -> {n2}"


def test_do_while_compiles_body_once(mesh8):
    from dryad_tpu import DryadContext

    ctx = DryadContext(num_partitions_=8)
    tbl = {"x": np.array([1.0], np.float32)}

    def body(q):
        return q.select(lambda cols: {"x": cols["x"] * 2})

    def cond(q):
        return q.aggregate_as_query({"m": ("max", "x")}).select(
            lambda cols: {"go": cols["m"] < 1000.0}
        )

    out = ctx.from_arrays(tbl).do_while(body, cond, max_iter=30).collect()
    assert out["x"][0] >= 1000.0
    n_after = len(ctx.executor._compiled)
    # body+cond compile once each (plus ingestion/egress stages), not per-iteration
    assert n_after <= 6, f"do_while recompiled per iteration: {n_after} programs"


def test_elastic_mesh_rebuild_after_exclusion(rng):
    """Failed-device exclusion: shrink the mesh, re-run on survivors
    (the requeue-with-exclusion recovery flow)."""
    import jax
    from dryad_tpu import DryadContext
    from dryad_tpu.parallel.mesh import num_partitions

    ctx = DryadContext(num_partitions_=8)
    tbl = {"k": rng.integers(0, 16, 512).astype(np.int32)}
    before = ctx.from_arrays(tbl).group_by("k", {"c": ("count", None)}).collect()

    bad = [d.id for d in jax.devices()[:2]]
    ctx.rebuild_mesh(bad)
    assert num_partitions(ctx.mesh) == 6
    after = ctx.from_arrays(tbl).group_by("k", {"c": ("count", None)}).collect()
    assert sorted(zip(before["k"], before["c"])) == sorted(
        zip(after["k"], after["c"])
    )


def test_exclude_all_devices_rejected():
    import jax
    from dryad_tpu import DryadContext

    ctx = DryadContext(num_partitions_=8)
    with pytest.raises(ValueError):
        ctx.rebuild_mesh([d.id for d in jax.devices()])


def _dw_body(q):
    return q.select(lambda c: {"v": c["v"] * 2.0})


def _dw_cond(q):
    return q.aggregate_as_query({"m": ("max", "v")}).select(
        lambda cols: {"go": cols["m"] < 100.0}
    )


def test_device_do_while_matches_driver_loop(rng):
    from dryad_tpu import DryadConfig, DryadContext

    tbl = {"v": np.array([1.0, 2.0, 3.0], np.float32)}

    def run(device):
        ctx = DryadContext(num_partitions_=8)
        return ctx.from_arrays(tbl).do_while(
            _dw_body, _dw_cond, max_iter=20, device=device
        ).collect()

    a = run(False)
    b = run(True)
    assert sorted(a["v"].tolist()) == sorted(b["v"].tolist())
    # loop semantics: doubles until max >= 100 -> 3*2^6 = 192
    assert max(b["v"]) == 192.0


def test_device_do_while_body_runs_once_when_cond_initially_false(rng):
    """DoWhile runs the body BEFORE checking cond (reference semantics);
    with cond false on the un-iterated input, both paths must still run
    the body exactly once (round-2 regression: the device path's
    lax.while_loop previously checked cond first and ran it zero times)."""
    from dryad_tpu import DryadContext

    tbl = {"v": np.array([150.0], np.float32)}  # cond (max < 100) false

    def run(device):
        ctx = DryadContext(num_partitions_=8)
        return ctx.from_arrays(tbl).do_while(
            _dw_body, _dw_cond, max_iter=20, device=device
        ).collect()

    a = run(False)
    b = run(True)
    assert a["v"].tolist() == [300.0]
    assert b["v"].tolist() == [300.0]


def test_hybrid_mesh_exclusion_preserves_dcn_axis():
    """exclude_devices on a 2-D (DCN x ICI) mesh keeps the 2-D structure
    (round-2 regression: it used to flatten to 1-D, losing the
    tree-exchange path after elastic recovery)."""
    from dryad_tpu.parallel.mesh import (
        exclude_devices,
        make_hybrid_mesh,
        num_partitions,
    )

    m = make_hybrid_mesh(2, 4)
    bad = [m.devices[0][0].id]
    m2 = exclude_devices(m, bad)
    assert m2.devices.ndim == 2
    assert m2.axis_names == m.axis_names
    # rows stay rectangular: both slices shrink to the smaller survivor
    assert m2.devices.shape == (2, 3)
    assert num_partitions(m2) == 6


def test_device_do_while_emits_done_event(tmp_path, rng):
    import json
    import os
    from dryad_tpu import DryadConfig, DryadContext

    ldir = str(tmp_path / "ev")
    ctx = DryadContext(
        num_partitions_=8, config=DryadConfig(event_log_dir=ldir)
    )
    tbl = {"v": np.array([1.0], np.float32)}
    ctx.from_arrays(tbl).do_while(
        _dw_body, _dw_cond, max_iter=20, device=True
    ).collect()
    events = []
    for f in os.listdir(ldir):
        with open(os.path.join(ldir, f)) as fh:
            events += [json.loads(l) for l in fh]
    kinds = {e["kind"] for e in events}
    assert "do_while_device_done" in kinds, kinds
    done = [e for e in events if e["kind"] == "do_while_device_done"]
    assert done[0]["iters"] == 7  # 1 -> 128


def _dw_body_multistage(q):
    # group_by forces a tee-free but... order_by after group_by lowers to
    # two stages -> must fall back to the driver loop.
    return (
        q.group_by("k", {"v": ("sum", "v"), "k2": ("first", "k")})
        .select(lambda c: {"k": c["k"], "v": c["v"]})
    )


def test_device_do_while_fallback_on_unsupported(tmp_path, rng):
    import json
    import os
    from dryad_tpu import DryadConfig, DryadContext

    ldir = str(tmp_path / "ev2")
    ctx = DryadContext(
        num_partitions_=8, config=DryadConfig(event_log_dir=ldir)
    )
    tbl = {
        "k": np.arange(8, dtype=np.int32),
        "v": np.ones(8, np.float32),
    }

    def body(q):
        # zip with itself -> multi-stage subplan
        return q.zip_(q.select(lambda c: dict(c)))

    def cond(q):
        return q.aggregate_as_query({"c": ("count", None)}).select(
            lambda cols: {"go": cols["c"] < 0}
        )

    out = ctx.from_arrays(tbl).do_while(
        body, cond, max_iter=3, device=True
    ).collect()
    events = []
    for f in os.listdir(ldir):
        with open(os.path.join(ldir, f)) as fh:
            events += [json.loads(l) for l in fh]
    kinds = {e["kind"] for e in events}
    assert "do_while_device_fallback" in kinds


def test_rebuilt_query_hits_compile_cache(rng):
    """Re-building the same logical pipeline (fresh Query objects, as a
    repeated caller does) must hit the structural compile cache: the
    lowering-created callables (ordering operands, mean finalize, salt,
    project) are VALUE-equal across lowerings.  An identity-keyed
    callable here recompiled the sort pipeline on every collect (the
    round-2 bench failure)."""
    from dryad_tpu import DryadContext

    ctx = DryadContext(num_partitions_=8)
    tbl = {
        "k": rng.integers(0, 50, 2048).astype(np.int32),
        "v": rng.standard_normal(2048).astype(np.float32),
    }

    def build():
        return (
            ctx.from_arrays(tbl)
            .group_by("k", {"c": ("count", None), "m": ("mean", "v")},
                      salt=4)
            .project(["k", "c", "m"])
            .order_by([("c", True), "k"])
            .collect()
        )

    first = build()
    n0 = len(ctx.executor._compiled)
    second = build()
    assert len(ctx.executor._compiled) == n0, (
        "rebuilt query recompiled stages"
    )
    assert first["k"].tolist() == second["k"].tolist()


def test_config_validation_rejects_bad_knobs():
    """validate() covers every numeric knob (verify-drive regression:
    sample_rate=-1 used to pass silently)."""
    import pytest

    from dryad_tpu.utils.config import DryadConfig

    for kw in (
        dict(sample_rate=-1.0),
        dict(sample_rate=0.0),
        dict(sample_rate=1.5),
        dict(max_shuffle_retries=-1),
        dict(max_stage_failures=0),
        dict(outlier_sigmas=0.0),
        dict(device_cache_bytes=-1),
        dict(rows_per_vertex=0),
    ):
        with pytest.raises(ValueError):
            DryadConfig(**kw)
    DryadConfig(sample_rate=1.0)  # boundary is legal


def _dup2(cols):
    import jax.numpy as jnp

    x = cols["x"]
    n = x.shape[0]
    out = jnp.stack([x, x + 1000], axis=1)
    return {"x": out}, jnp.ones((n, 2), jnp.bool_)


def test_do_while_growing_state_boosts_compaction(mesh8):
    """A body that doubles the state each round outgrows the stable
    loop capacity: compaction must BOOST (cross-mesh-reduced overflow
    flag) and keep every row — a device-local flag would silently drop
    rows on whichever partition overflowed first."""
    import numpy as np

    from dryad_tpu import DryadContext

    ctx = DryadContext(num_partitions_=8)
    q = ctx.from_arrays({"x": np.arange(16, dtype=np.int32)})

    def body(qq):
        return qq.select_many(_dup2, 2)

    def cond(qq):
        return qq.count_as_query().select(lambda c: {"go": c["count"] < 100})

    out = q.do_while(body, cond, max_iter=10).collect()
    # 16 -> 32 -> 64 -> 128 rows (cond false at 128)
    assert len(out["x"]) == 128
    kinds = [e["kind"] for e in ctx.executor.events.events()]
    assert "do_while_state_boost" in kinds


def test_the_exchanges_counts_ride_the_one_readback(mesh8, monkeypatch):
    """What an exchange saw (rows into the combiner before it, rows it
    sent, rows every chip received) comes back in the ``device_get``
    that fetches the overflow flag: as many host transfers of the
    executor as the stage has drains, with the plan fused into one
    dispatch or not, and a retry's counts on the retry's own event."""
    from dryad_tpu.exec import executor as EX

    fetched = []  # the executor's own reads: (flag, counts, what was seen)
    real = EX.jax.device_get

    def spy(tree):
        if isinstance(tree, tuple) and len(tree) == 3 and getattr(
                tree[0], "shape", None) == ():
            fetched.append(tree)
        return real(tree)

    monkeypatch.setattr(EX.jax, "device_get", spy)
    n = 4096
    table = {"k": (np.arange(n, dtype=np.int32) % 512) - 1}
    for slack, boosts in ((2.0, [1]), (1.0, [1, 2])):
        ctx = DryadContext(num_partitions_=8, config=DryadConfig(shuffle_slack=slack))
        del fetched[:]
        out = ctx.from_arrays(table).group_by("k", {"c": ("count", None)}).collect()
        assert len(out["k"]) == 512 and (out["c"] == 8).all()
        events = ctx.events.events()
        drains = [e for e in events if e["kind"] == "span" and e["name"] == "drain"]
        seen = [e for e in events if e["kind"] == "exchange_observed"]
        overflows = [e for e in events if e["kind"] == "stage_overflow"]
        assert [e["boost"] for e in seen] == boosts
        assert len(drains) == len(seen) == len(fetched) == len(boosts)
        assert len(overflows) == len(boosts) - 1
        assert [e["overflows"] for e in seen] == list(range(1, len(boosts))) + [
            len(boosts) - 1]
        last = seen[-1]
        # a chip holds 512 keys once each: the combiners leave every row
        assert (last["combine_rows_in"], last["combine_rows_out"]) == (n, n)
        assert sum(last["recv_rows"]) == n and len(last["recv_rows"]) == 8
        assert drains[-1]["recv_rows"] == last["recv_rows"]
        assert drains[-1]["overflows"] == len(overflows)
        if len(seen) > 1:  # the dispatch that overflowed dropped rows on the way
            assert sum(seen[0]["recv_rows"]) < seen[0]["combine_rows_out"]
    # a stage with no exchange reads nothing back for it and says nothing
    ctx = DryadContext(num_partitions_=8)
    ctx.from_arrays(table).select(lambda c: {"k": c["k"] + 1}).collect()
    assert not [e for e in ctx.events.events() if e["kind"] == "exchange_observed"]
