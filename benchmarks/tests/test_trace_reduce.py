"""The reduction from a trace to busy time, idle share, time per
operation, each job's lead / device / tail and idle gaps by where in a
job they fell: its arithmetic on a hand-made
trace whose every number can be counted by hand, and the whole of it on
a small recorded xplane (``fixtures/``, recorded on the chips)."""

import pytest

import trace_reduce as TR


def hand_trace():
    """One chip, a 10 s window.  Operations: a while loop 1-4 that
    holds a gather 1-2 and a sort 2.5-3.5; an all-to-all 6-7; one
    operation 9.5-11 that the window cuts at 10.  Busy: 3 + 1 + 0.5."""
    ops = {0: [
        ("while.1 while", 1.0, 4.0),
        ("fusion.2 custom-call gather", 1.0, 2.0),
        ("sort.3 sort", 2.5, 3.5),
        ("all-to-all.4 all-to-all", 6.0, 7.0),
        ("fusion.5 fusion", 9.5, 11.0),
    ]}
    annotations = [
        ("bench:window", 0.0, 10.0),
        ("bench:fresh", 0.0, 5.0),
        ("bench:requery", 5.0, 8.0),
        ("bench:fresh:deeper", 0.0, 1.0),  # not a job: ignored
    ]
    return TR.Trace(ops, annotations)


def test_interval_arithmetic():
    assert TR.union([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert TR.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]
    assert TR.length([(0, 2), (3, 4)]) == 3
    assert TR.complement([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert TR.complement([], 0, 5) == [(0, 5)]


def test_self_times_take_children_out_of_their_parent():
    got = TR.self_times(hand_trace().ops[0])
    assert got["while.1 while"] == pytest.approx(1.0)  # 3 s less 1 + 1
    assert got["fusion.2 custom-call gather"] == pytest.approx(1.0)
    assert got["sort.3 sort"] == pytest.approx(1.0)
    assert sum(got.values()) == pytest.approx(3.0 + 1.0 + 1.5)


def test_job_phases_go_by_an_operations_midpoint():
    ops = hand_trace().ops[0]
    assert TR.job_phases(ops, 0.0, 5.0) == pytest.approx((1.0, 3.0, 1.0))
    assert TR.job_phases(ops, 5.0, 8.0) == pytest.approx((1.0, 1.0, 1.0))
    assert TR.job_phases(ops, 8.0, 9.0) is None
    # the device's clock runs a little ahead: the next job's first
    # operation starts inside this span, its midpoint does not
    late = ops + [("fusion.6 fusion", 4.99, 5.5)]
    assert TR.job_phases(late, 0.0, 5.0) == pytest.approx((1.0, 3.0, 1.0))
    assert TR.job_phases(late, 5.0, 8.0) == pytest.approx((0.0, 2.0, 1.0))
    assert TR.is_job("bench:fresh") and TR.is_job("bench:requery")
    assert not TR.is_job("bench:window") and not TR.is_job("bench:between_jobs")
    assert not TR.is_job("bench:fresh:ingest")


def test_reduce_on_the_hand_trace():
    s = TR.reduce(hand_trace())
    assert s["window_s"] == pytest.approx(10.0) and s["chips"] == 1
    assert s["busy_s"] == pytest.approx(4.5)
    assert s["idle_share"] == pytest.approx(0.55)
    assert sum(s["op_s"].values()) == pytest.approx(4.5)  # cut at the window
    assert s["op_s"]["fusion.5 fusion"] == pytest.approx(0.5)
    gaps = s["gap_s"]
    assert gaps["bench:fresh:lead"] == pytest.approx(1.0)
    assert "bench:fresh:between_ops" not in gaps  # the while loop spans 1-4
    assert gaps["bench:fresh:tail"] == pytest.approx(1.0)
    assert gaps["bench:requery:lead"] == pytest.approx(1.0)
    assert gaps["bench:requery:tail"] == pytest.approx(1.0)
    assert gaps["bench:between_jobs"] == pytest.approx(1.5)  # 8-9.5
    assert sum(gaps.values()) == pytest.approx(5.5)
    assert s["busy_in"]["bench:fresh"] == [pytest.approx(3.0)]
    assert s["busy_in"]["bench:requery"] == [pytest.approx(1.0)]
    assert s["phases"]["bench:fresh"] == [pytest.approx((1.0, 3.0, 1.0))]
    assert s["phases"]["bench:requery"] == [pytest.approx((1.0, 1.0, 1.0))]


def test_two_chips_are_averaged():
    trace = hand_trace()
    trace.ops[1] = [("fusion.2 custom-call gather", 1.0, 2.0)]
    s = TR.reduce(trace)
    assert s["chips"] == 2
    assert s["busy_s"] == pytest.approx((4.5 + 1.0) / 2)
    assert s["op_s"]["fusion.2 custom-call gather"] == pytest.approx(1.0)
    assert s["op_s"]["sort.3 sort"] == pytest.approx(0.5)
    # chip 1 ran one operation, 1-2, in the fresh job and none in the requery
    assert s["phases"]["bench:fresh"] == [pytest.approx((1.0, 2.0, 2.0))]
    assert s["phases"]["bench:requery"] == [pytest.approx((1.0, 1.0, 1.0))]
    assert s["gap_s"]["bench:requery:lead"] == pytest.approx((1.0 + 3.0) / 2)


def test_a_trace_without_device_work_is_an_error():
    trace = hand_trace()
    with pytest.raises(ValueError, match="no operation ran"):
        TR.reduce(TR.Trace({0: []}, trace.annotations))
    with pytest.raises(ValueError, match="bench:window"):
        TR.reduce(TR.Trace(trace.ops, trace.annotations[1:]))


def test_kinds_by_name():
    assert TR.is_gather("fusion.4 custom-call gather f32[8388608]")
    assert not TR.is_gather("all-gather.1 all-gather")
    assert TR.is_collective("all-to-all.4 all-to-all")
    assert TR.is_collective("fusion.9 all-reduce fusion")
    assert not TR.is_collective("sort.3 sort")
    assert TR.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0], ["c", 2.0]]


# -- the recorded xplane -----------------------------------------------------
# fixtures/groupby-4c.xplane.pb.gz: one pair of the group-by job (2^24 rows over
# 2^20 groups) on the four-chip v5e host, 454 operations a chip, recorded in
# PR 23 by a harness that also put annotations inside a job; those
# (``bench:fresh:ingest`` ...) are not jobs and the reduction passes them by.

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import gzip
    import os
    import shutil

    source = os.path.join(os.path.dirname(__file__), "fixtures",
                          "groupby-4c.xplane.pb.gz")
    path = tmp_path_factory.mktemp("xplane") / "groupby-4c.xplane.pb"
    with gzip.open(source, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return TR.load(str(path))


def test_recorded_trace_is_read_whole(recorded):
    assert {chip: len(ops) for chip, ops in recorded.ops.items()} == {
        0: 454, 1: 454, 2: 454, 3: 454}
    names = sorted({a[0] for a in recorded.annotations})
    assert names == [
        "bench:between_jobs", "bench:fresh", "bench:fresh:decode",
        "bench:fresh:execute", "bench:fresh:ingest", "bench:requery",
        "bench:requery:decode", "bench:requery:execute",
        "bench:requery:ingest", "bench:window"]
    for _, start, end in recorded.ops[0]:
        assert end >= start >= 0


def test_reduce_on_the_recorded_trace(recorded):
    s = TR.reduce(recorded)
    assert s["chips"] == 4
    assert s["window_s"] == pytest.approx(4.514334564, rel=1e-9)
    assert s["busy_s"] == pytest.approx(2.911827763, rel=1e-6)
    assert s["idle_share"] == pytest.approx(0.354981842, rel=1e-6)
    # self times add up to the busy union: nothing nested is counted twice
    assert sum(s["op_s"].values()) == pytest.approx(s["busy_s"], rel=1e-9)
    # busy + every idle gap = the window
    assert s["busy_s"] + sum(s["gap_s"].values()) == pytest.approx(
        s["window_s"], rel=1e-9)
    assert s["gap_s"]["bench:requery:tail"] == pytest.approx(0.5616, abs=1e-4)
    assert s["gap_s"]["bench:fresh:lead"] == pytest.approx(0.3495, abs=1e-4)
    (lead, device, tail), = s["phases"]["bench:fresh"]
    assert (lead, device, tail) == pytest.approx((0.3495, 1.4559, 0.5519), abs=1e-4)
    assert s["phases"]["bench:requery"] == [
        pytest.approx((0.0280, 1.4559, 0.5616), abs=1e-4)]
    assert s["busy_in"]["bench:requery"] == [pytest.approx(1.4559096, rel=1e-6)]
    # a gather and a collective operation, found by name
    gather = "fusion.5 custom fusion gather s32[8388608]"
    exchange = "all_to_all.29 all-to-all all_to_all s32[4,1,2097152]"
    assert s["op_s"][gather] == pytest.approx(0.3735611, rel=1e-6)
    assert s["op_s"][exchange] == pytest.approx(0.00077, rel=0.02)
    assert TR.is_gather(gather) and not TR.is_collective(gather)
    assert TR.is_collective(exchange) and not TR.is_gather(exchange)
    assert sorted(k.split()[0] for k in s["op_s"] if TR.is_collective(k)) == [
        "all-reduce.4", "all_to_all.25", "all_to_all.27", "all_to_all.29",
        "all_to_all.31", "psum.21"]
    assert TR.top(s["op_s"], 1)[0][0] == gather
