"""Deferred shuffle-overflow syncs: overflow-capable stages dispatch
speculatively up to ``overflow_sync_depth`` deep, and their flags drain
in ONE batched readback (the GM pump's concurrent vertex management,
``DrMessagePump.h:116-180``) — so through a high-latency control link a
k-shuffle pipeline pays one round-trip of control latency, not k.

Covers: >1 shuffle stages in flight,
correct recovery when a speculative stage overflows (suffix redo at a
larger boost), depth=1 legacy behavior, and differential correctness.
"""

import numpy as np
import pytest

from dryad_tpu import DryadContext
from dryad_tpu.exec.events import EventLog
from dryad_tpu.utils.config import DryadConfig


def _wire(ctx):
    ev = EventLog(None)
    ctx.executor.events = ev
    return ev


def _multi_shuffle_query(ctx, tbl):
    """Three SEPARATE overflow-capable stages (a fused chain is one
    stage): two independent shuffling group_bys whose outputs join."""
    a = ctx.from_arrays(tbl).group_by(
        ["k"], {"s": ("sum", "v"), "n": ("count", None)}
    )
    b = ctx.from_arrays(
        {"k": tbl["k"], "g": tbl["g"]}
    ).group_by(["k"], {"gmax": ("max", "g")})
    return a.join(b, "k", strategy="shuffle")


@pytest.fixture
def tbl(rng):
    return {
        "k": rng.integers(0, 200, 4000).astype(np.int32),
        "g": rng.integers(0, 7, 4000).astype(np.int32),
        "v": rng.standard_normal(4000).astype(np.float32),
    }


def _expected(tbl):
    exp = {}
    for k in np.unique(tbl["k"]):
        m = tbl["k"] == k
        exp[int(k)] = (
            float(tbl["v"][m].sum()), int(m.sum()), int(tbl["g"][m].max())
        )
    return exp


def test_multiple_shuffles_in_flight(mesh8, tbl):
    """The event log must show k>1 overflow-capable stages DISPATCHED
    before any drain, and exactly one drain for the window.

    plan_fuse=False: whole-DAG fusion (plan/fuse.py) would collapse
    this plan into ONE dispatched region — exactly the seam removal it
    exists for — but this test exercises the speculative window that
    the per-stage baseline (and any unfused seam: host boundaries,
    width-adaptation candidates) still relies on."""
    ctx = DryadContext(
        num_partitions_=8, config=DryadConfig(plan_fuse=False)
    )
    ev = _wire(ctx)
    out = _multi_shuffle_query(ctx, tbl).collect()

    exp = _expected(tbl)
    got = {
        int(k): (float(s), int(n), int(gm))
        for k, s, n, gm in zip(out["k"], out["s"], out["n"], out["gmax"])
    }
    assert set(got) == set(exp)
    for k in exp:
        assert abs(got[k][0] - exp[k][0]) < 1e-2 * max(1.0, abs(exp[k][0]))
        assert got[k][1:] == exp[k][1:]

    kinds = [e["kind"] for e in ev.events()]
    assert "stage_dispatched" in kinds
    drains = [e for e in ev.events() if e["kind"] == "overflow_drain"]
    assert drains and max(d["inflight"] for d in drains) >= 2, drains
    # no per-stage syncs happened for the windowed stages: their
    # completions are marked deferred
    deferred = [
        e for e in ev.events()
        if e["kind"] == "stage_complete" and e.get("deferred")
    ]
    assert len(deferred) >= 2


def test_overflow_under_deferral_recovers(mesh8, tbl):
    """A speculative stage that overflows (tiny slack, distinct keys)
    is re-run at a larger boost and the result is still correct."""
    ctx = DryadContext(
        num_partitions_=8, config=DryadConfig(shuffle_slack=1.0)
    )
    ev = _wire(ctx)
    n = 4096
    # keys start at -1 so the int auto-dense rewrite (0-based domains
    # only) stays off and the shuffling sort path runs
    out = (
        ctx.from_arrays({"k": np.arange(n, dtype=np.int32) - 1})
        .group_by("k", {"c": ("count", None)})
        .collect()
    )
    assert len(out["k"]) == n
    assert set(out["k"].tolist()) == set(range(-1, n - 1))
    kinds = [e["kind"] for e in ev.events()]
    assert "stage_overflow" in kinds
    # the redo ran through the synchronous path after the drain
    assert kinds.index("overflow_drain") < len(kinds)


def test_depth_one_is_legacy_per_stage_sync(mesh8, tbl):
    ctx = DryadContext(
        num_partitions_=8, config=DryadConfig(overflow_sync_depth=1)
    )
    ev = _wire(ctx)
    out = _multi_shuffle_query(ctx, tbl).collect()
    exp = _expected(tbl)
    assert {int(k) for k in out["k"]} == set(exp)
    kinds = [e["kind"] for e in ev.events()]
    assert "stage_dispatched" not in kinds
    assert "overflow_drain" not in kinds


def test_config_rejects_bad_depth():
    with pytest.raises(ValueError, match="overflow_sync_depth"):
        DryadConfig(overflow_sync_depth=0)


def test_deferral_differential_vs_oracle(mesh8, rng):
    """Windowed execution must not change ANY results: run a mixed
    pipeline (join + group_by + order_by) at depth 4 and depth 1 and
    against the oracle."""
    left = {
        "k": rng.integers(0, 40, 800).astype(np.int32),
        "v": rng.standard_normal(800).astype(np.float32),
    }
    right = {
        "k": rng.integers(0, 40, 300).astype(np.int32),
        "w": rng.integers(0, 100, 300).astype(np.int32),
    }

    def build(c):
        return (
            c.from_arrays(left)
            .join(c.from_arrays(right), "k")
            .group_by("k", {"s": ("sum", "v"), "n": ("count", None)})
            .order_by([("k", False)])
            .collect()
        )

    deep = build(DryadContext(num_partitions_=8))
    shallow = build(DryadContext(
        num_partitions_=8, config=DryadConfig(overflow_sync_depth=1)
    ))
    oracle = build(DryadContext(local_debug=True))
    for got in (deep, shallow):
        assert got["k"].tolist() == sorted(oracle["k"].tolist())
        by_k = dict(zip(oracle["k"].tolist(), oracle["n"].tolist()))
        assert dict(zip(got["k"].tolist(), got["n"].tolist())) == by_k
