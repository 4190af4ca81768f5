"""Every metric file's ``read`` on a summary whose numbers can be
counted by hand (the hand trace of ``test_trace_reduce``), and on the
recorded xplane of ``fixtures/``."""

import os

import pytest

import run
import trace_reduce as TR
from test_trace_reduce import hand_trace, recorded  # noqa: F401 - a fixture

PAIRS = [
    {"i": 0, "t": 0.0, "fresh_s": 3.0, "requery_s": 2.0, "pair_s": 5.0, "end": 5.0},
    {"i": 1, "t": 6.0, "fresh_s": 3.2, "requery_s": 2.2, "pair_s": 5.4, "end": 11.4},
    {"i": 2, "t": 12.0, "fresh_s": 3.1, "end": 12.0},  # the requery raised: no pair
]


# the eight of BENCHMARK.json and ``collective_dev_share``, which waits for
# a cell on four chips (PERF.md, Open questions)
READERS = ("ingest_s", "execute_s", "egress_s", "mean_rows_per_s_chip",
           "window_compiles", "gather_dev_share", "collective_dev_share",
           "device_idle_share", "hbm_floor_share")


def cell():
    c = run.load_cell("sort-1c")
    c.peaks = {"hbm_bytes_per_s": 819e9}
    return c


def read(name, trace, spans, counters, c=None):
    return run.load_module("metrics", name).read(trace, spans, counters, c or cell())


def test_every_reader_on_the_hand_trace():
    trace = TR.reduce(hand_trace())
    spans = {"pairs": PAIRS}
    counters = {"xla_compiles": 0.0, "d2h_bytes": 1.0}
    # fresh 0-5 s: operations 1-4; requery 5-8 s: one operation 6-7
    assert read("ingest_s", trace, spans, counters) == pytest.approx(1.0)
    assert read("execute_s", trace, spans, counters) == pytest.approx(1.0)
    assert read("egress_s", trace, spans, counters) == pytest.approx(1.0)
    assert read("window_compiles", trace, spans, counters) == 0.0
    # two whole pairs of 2 x 2^25 rows, the last ends 11.4 s after opening
    assert read("mean_rows_per_s_chip", trace, spans, counters) == pytest.approx(
        2 * 2**26 / 11.4)
    # busy 4.5 s: gather 1.0, all-to-all 1.0
    assert read("gather_dev_share", trace, spans, counters) == pytest.approx(100 / 4.5)
    assert read("collective_dev_share", trace, spans, counters) == pytest.approx(100 / 4.5)
    assert read("device_idle_share", trace, spans, counters) == pytest.approx(55.0)
    # 2^29 bytes at 819 GB/s against 1.0 s of busy time in the requery
    assert read("hbm_floor_share", trace, spans, counters) == pytest.approx(
        100 * 2**29 / 819e9 / 1.0)


def test_a_reader_that_finds_nothing_returns_nothing():
    spans = {"pairs": [{"i": 0, "t": 0.0, "fresh_s": 1.0, "end": 0.0}]}
    for name in READERS:
        assert read(name, None, spans, {}) is None, name
    # a trace in which no job ran an operation has no phase to read
    trace = hand_trace()
    trace.annotations[1:] = [("bench:fresh", 8.0, 9.0)]
    summary = TR.reduce(trace)
    for name in ("ingest_s", "execute_s", "egress_s", "hbm_floor_share"):
        assert read(name, summary, spans, {}) is None, name


def test_every_listed_metric_has_a_reader_with_the_signature():
    import inspect

    names = sorted(f[:-3] for f in os.listdir(os.path.join(run.HERE, "metrics"))
                   if f.endswith(".py"))
    assert names == sorted(READERS)
    for name in names:
        fn = run.load_module("metrics", name).read
        assert list(inspect.signature(fn).parameters) == [
            "trace", "spans", "counters", "cell"]


def test_trace_readers_on_the_recorded_xplane(recorded):
    """The trace readers on fixtures/groupby-4c.xplane.pb.gz (one pair
    of the group-by job on the four-chip host, PR 23)."""
    trace = TR.reduce(recorded)
    c = run.Cell(name="groupby-4c", chips=4, config={}, traffic="groupby",
                 params={"rows": 2**24, "groups": 2**20},
                 job=run.load_module("jobs", "groupby"), end_to_end=[],
                 per_layer=[], peaks={"hbm_bytes_per_s": 819e9})
    spans, counters = {"pairs": []}, {}
    assert read("device_idle_share", trace, spans, counters, c) == pytest.approx(
        35.4981842, rel=1e-6)
    assert read("gather_dev_share", trace, spans, counters, c) == pytest.approx(
        63.98973805, rel=1e-6)
    assert read("collective_dev_share", trace, spans, counters, c) == pytest.approx(
        0.15037948, rel=1e-6)
    # 146,800,640 B over 4 x 819 GB/s against 1.4559 s busy in the requery
    assert read("hbm_floor_share", trace, spans, counters, c) == pytest.approx(
        100 * 146_800_640 / (4 * 819e9) / 1.4559096, rel=1e-6)
    # sharded ingest, the stage programs, and the 12 MB answer's way back
    assert read("ingest_s", trace, spans, counters, c) == pytest.approx(0.3495, abs=1e-4)
    assert read("execute_s", trace, spans, counters, c) == pytest.approx(1.4559, abs=1e-4)
    assert read("egress_s", trace, spans, counters, c) == pytest.approx(0.5616, abs=1e-4)
