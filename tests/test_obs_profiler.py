"""The program's spans on the profiler's clock (obs/span.py's
``Span`` -> ``TraceAnnotation`` bridge) and the operator scopes on the
device operations.

ONE CPU ``jax.profiler`` session, recorded once a module, around a
small ``order_by`` collect (fresh, then a requery), a two-stage plan
and a ``from_text`` count; every test below reads that one trace and
the context's event log.
"""

import os

import jax
import numpy as np
import pytest

from dryad_tpu import DryadContext
from dryad_tpu.obs import critpath

ROWS = 1 << 16
P = 8
SAMPLE = "dryad:other:resource_sample"
# the host passes that write a table's worth of host memory, each opened
# with ``account=True`` (obs/span.py)
HOST_PASSES = {
    "dryad:ingest:tokenize", "dryad:ingest:vocab", "dryad:ingest:encode",
    "dryad:ingest:pack", "dryad:readback:fetch_copy",
    "dryad:decode:decode", "dryad:decode:unpack",
}


def _annotations(trace_dir):
    """Every ``dryad:*`` event of the host plane: ``(name, start_ns,
    end_ns, stats)``."""
    found = []
    for root, _dirs, files in os.walk(trace_dir):
        found += [os.path.join(root, f) for f in files
                  if f.endswith(".xplane.pb")]
    assert len(found) == 1, found
    data = jax.profiler.ProfileData.from_file(found[0])
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("dryad:"):
                    # a stat sent twice (at open, then at close with
                    # another value) is there twice: a reader takes the
                    # last, as ``dict`` does
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats), list(ev.stats)))
    return sorted(out, key=lambda a: a[1])


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The session: what ran, what it answered, the annotations the
    profiler kept and the events the context kept."""
    work = tmp_path_factory.mktemp("obs_profiler")
    rng = np.random.default_rng(24)
    table = {
        "k": rng.integers(-(2**31), 2**31 - 1, ROWS).astype(np.int32),
        "v": rng.standard_normal(ROWS).astype(np.float32),
    }
    text = work / "corpus.txt"
    text.write_text(" ".join(f"w{i % 37}" for i in range(5000)))
    ctx = DryadContext(num_partitions_=P)
    metrics = ctx.executor.metrics

    def counters():
        return {n: metrics.total(n) for n in ("h2d_bytes", "d2h_bytes")}

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    trace_dir = str(work / "trace")
    rec = {"ctx": ctx, "table": table}
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        sort = ctx.from_arrays(table).order_by([("k", False)])
        c0 = counters()
        rec["fresh"] = sort.collect()
        c1 = counters()
        rec["requery"] = sort.collect()
        c2 = counters()
        # several stages (an aggregate joined to a second input), so
        # the executor has something to fuse
        small = (table["k"] % 64).astype(np.int32)
        rec["joined"] = (
            ctx.from_arrays({"k": small})
            .group_by("k", {"c": ("count", None)})
            .join(ctx.from_arrays({"k": np.arange(-63, 64, dtype=np.int32)}), "k")
            .collect()
        )
        rec["words"] = (
            ctx.from_text(str(text))
            .group_by("word", {"count": ("count", None)})
            .collect()
        )
        # a BYTES column: ``pack`` inside ``encode``, ``unpack`` inside
        # ``decode``
        rec["records"] = {
            "key": rng.integers(0, 256, (4096, 10), dtype=np.uint8),
            "payload": rng.integers(0, 256, (4096, 6), dtype=np.uint8),
        }
        rec["sorted_records"] = (
            ctx.from_arrays(rec["records"]).order_by([("key", False)]).collect()
        )
    finally:
        jax.profiler.stop_trace()
    rec["sort"] = sort
    rec["fresh_counters"] = {n: c1[n] - c0[n] for n in c0}
    rec["requery_counters"] = {n: c2[n] - c1[n] for n in c0}
    sent = _annotations(trace_dir)
    rec["annotations"] = [a[:4] for a in sent]
    rec["sent"] = {a[3]["span_id"]: a[4] for a in sent}
    rec["spans"] = [e for e in ctx.events.events() if e["kind"] == "span"]
    return rec


def _by_name(recorded, name):
    return [a for a in recorded["annotations"] if a[0] == name]


def _job(recorded, index):
    """The annotations of the ``index``-th collect: its root and
    everything that reaches it by ``parent_id``."""
    root = _by_name(recorded, "dryad:other:collect")[index]
    parents = {a[3]["span_id"]: a[3]["parent_id"]
               for a in recorded["annotations"]}

    def under(span_id):
        while span_id:
            if span_id == root[3]["span_id"]:
                return True
            span_id = parents.get(span_id, 0)
        return False

    return root, [a for a in recorded["annotations"]
                  if a is not root and under(a[3]["span_id"])]


def test_the_answers_are_right(recorded):
    order = np.argsort(recorded["table"]["k"], kind="stable")
    for answer in (recorded["fresh"], recorded["requery"]):
        np.testing.assert_array_equal(answer["k"], recorded["table"]["k"][order])
        np.testing.assert_array_equal(answer["v"], recorded["table"]["v"][order])
    assert sorted(recorded["words"]["count"]) == sorted(
        np.bincount(np.arange(5000) % 37))
    assert int(recorded["joined"]["c"].sum()) == ROWS


def test_every_boundary_of_a_job_is_in_the_host_plane(recorded):
    names = {a[0] for a in recorded["annotations"]}
    assert {
        "dryad:other:collect", "dryad:plan:lower", "dryad:plan:fuse",
        "dryad:ingest:tokenize", "dryad:ingest:vocab", "dryad:ingest:bind",
        "dryad:ingest:encode", "dryad:ingest:h2d",
        "dryad:compile:input+order_by", "dryad:dispatch:input+order_by",
        "dryad:readback:drain", "dryad:readback:fetch_wait",
        "dryad:readback:fetch_copy", "dryad:decode:decode",
    } <= names
    # every phase is one of critpath's
    assert {n.split(":")[1] for n in names} <= set(critpath.PHASES)


def test_a_jobs_spans_nest_under_its_collect(recorded):
    root, inside = _job(recorded, 0)
    # the telemetry sample falls in whichever span's event finds it due
    inside = [a for a in inside if a[0] != SAMPLE]
    got = {a[0] for a in inside}
    assert {
        "dryad:plan:lower", "dryad:ingest:bind", "dryad:ingest:encode",
        "dryad:ingest:h2d", "dryad:compile:input+order_by",
        "dryad:dispatch:input+order_by", "dryad:readback:drain",
        "dryad:readback:fetch_wait", "dryad:readback:fetch_copy",
        "dryad:decode:decode", "dryad:readback:drop",
        "dryad:ingest:release",
    } == got
    for name, start, end, stats in inside:
        assert root[1] <= start <= end <= root[2], name
        assert stats["qid"] == root[3]["qid"]
    by_id = {a[3]["span_id"]: a for a in inside}
    h2d, = [a for a in inside if a[0] == "dryad:ingest:h2d"]
    assert by_id[h2d[3]["parent_id"]][0] == "dryad:ingest:bind"
    compiled, = [a for a in inside if a[0].startswith("dryad:compile:")]
    assert by_id[compiled[3]["parent_id"]][0].startswith("dryad:dispatch:")
    # the job that copied host arrays in lets go of them at its end
    assert max(inside, key=lambda a: a[2])[0] == "dryad:ingest:release"
    # the requery finds the table resident and the program compiled
    _, again = _job(recorded, 1)
    assert {a[0] for a in again} - {SAMPLE} == got - {
        "dryad:ingest:bind", "dryad:ingest:encode", "dryad:ingest:h2d",
        "dryad:compile:input+order_by", "dryad:ingest:release"}


def test_bind_time_spans_have_no_parent_and_no_query(recorded):
    for name in ("dryad:ingest:tokenize", "dryad:ingest:vocab"):
        (_, _, _, stats), = _by_name(recorded, name)
        assert stats["parent_id"] == 0 and "qid" not in stats
    (_, _, _, stats), = _by_name(recorded, "dryad:ingest:tokenize")
    assert stats["bytes"] == len(" ".join(f"w{i % 37}" for i in range(5000)))
    (_, _, _, stats), = _by_name(recorded, "dryad:ingest:vocab")
    assert stats["rows"] == 5000


def test_the_tokenizer_says_what_it_found_and_vocab_is_bind_time_alone(recorded):
    (_, _, _, stats), = _by_name(recorded, "dryad:ingest:tokenize")
    # 37 words found in the tokenizer's table; a corpus of 23 KB is one
    # run on the caller's thread
    assert (stats["rows"], stats["distinct"], stats["runs"]) == (5000, 37, 1)
    assert stats["bytes_out"] == 16 * 5000
    # ``vocab`` sorts those 37: once a from_text, never in a collect()
    (_, _, _, vocab), = _by_name(recorded, "dryad:ingest:vocab")
    assert vocab["bytes_out"] == 8 * 37
    for index in range(len(_by_name(recorded, "dryad:other:collect"))):
        _, inside = _job(recorded, index)
        assert not {a[0] for a in inside} & {
            "dryad:ingest:tokenize", "dryad:ingest:vocab"}


def test_every_annotation_has_its_span_event(recorded):
    events = {e["span_id"]: e for e in recorded["spans"]}
    assert len(recorded["annotations"]) >= 40
    for name, start, end, stats in recorded["annotations"]:
        ev = events[stats["span_id"]]
        phase = critpath.phase_of(ev["name"], ev["cat"])
        assert name == f"dryad:{phase}:{ev['name']}"
        assert (ev["parent_id"] or 0) == stats["parent_id"]
        assert ev["qid"] == stats.get("qid")
        # one interval on two clocks
        assert (end - start) * 1e-9 == pytest.approx(ev["dur"], abs=2e-3)
    traced = {a[3]["span_id"] for a in recorded["annotations"]}
    assert {e["span_id"] for e in recorded["spans"]} == traced


def test_h2d_bytes_are_the_arrays_and_the_counter(recorded):
    _, inside = _job(recorded, 0)
    (_, _, _, stats), = [a for a in inside if a[0] == "dryad:ingest:h2d"]
    table = recorded["table"]
    # P partitions of ROWS / P rows: no padding, one validity byte a row
    assert stats["bytes"] == table["k"].nbytes + table["v"].nbytes + ROWS
    assert recorded["fresh_counters"]["h2d_bytes"] == stats["bytes"]
    assert recorded["requery_counters"]["h2d_bytes"] == 0
    # ONE ``encode`` a table since PR 36: the layout, written in one pass
    (encode,) = [a[3] for a in inside if a[0] == "dryad:ingest:encode"]
    assert encode["rows"] == encode["capacity"] == ROWS


def test_fetch_copy_bytes_are_the_arrays_and_the_counter(recorded):
    for index, kind in ((0, "fresh_counters"), (1, "requery_counters")):
        _, inside = _job(recorded, index)
        (_, _, _, copy), = [a for a in inside
                            if a[0] == "dryad:readback:fetch_copy"]
        (_, _, _, decode), = [a for a in inside if a[0] == "dryad:decode:decode"]
        assert copy["columns"] == 2
        # int32 + float32 + the validity byte, for every slot
        assert copy["bytes"] == 9 * copy["capacity"]
        assert copy["bytes"] == recorded[kind]["d2h_bytes"]
        assert decode["rows"] == ROWS == len(recorded["fresh"]["k"])
        assert decode["capacity"] == copy["capacity"] >= ROWS


def test_without_a_session_the_events_flow_and_the_answer_is_the_same(recorded):
    ctx = recorded["ctx"]
    before = len(ctx.events.events())
    answer = recorded["sort"].collect()
    for column in ("k", "v"):
        np.testing.assert_array_equal(answer[column], recorded["fresh"][column])
    new = [e for e in ctx.events.events()[before:]
           if e["kind"] == "span" and e["name"] != "resource_sample"]
    assert [e["name"] for e in new] == [
        "lower", "input+order_by", "drain", "fetch_wait", "fetch_copy",
        "decode", "drop", "drop", "collect"]
    root = new[-1]["span_id"]
    assert all(e["parent_id"] == root for e in new[:-1])
    copy = next(e for e in new if e["name"] == "fetch_copy")
    assert copy["bytes"] == recorded["requery_counters"]["d2h_bytes"]


def test_what_a_span_learns_before_it_closes_rides_its_annotation(recorded):
    events = {e["span_id"]: e for e in recorded["spans"]}
    # ``add()``ed after open: the tokenizer's rows, the plan's stages
    (_, _, _, stats), = _by_name(recorded, "dryad:ingest:tokenize")
    assert stats["rows"] == 5000 == events[stats["span_id"]]["rows"]
    assert stats["bytes_out"] == 4 * 4 * 5000  # h0, h1, r0, r1
    for _, _, _, stats in _by_name(recorded, "dryad:plan:lower"):
        assert stats["stages"] == events[stats["span_id"]]["stages"] >= 1
    # every numeric field of every event, with the value it had at close
    for name, _, _, stats in recorded["annotations"]:
        ev = events[stats["span_id"]]
        for field, value in ev.items():
            if isinstance(value, (int, float)) and field not in (
                    "ts", "mono", "dur", "span_id", "parent_id"):
                assert stats[field] == value, (name, field)


def test_a_changed_field_is_sent_again_once(recorded):
    # the dispatch that traces opens with 0 bytes on the wire and learns
    # its program's constants inside: the last value once, not twice
    first = _by_name(recorded, "dryad:dispatch:input+order_by")[0]
    sent = recorded["sent"][first[3]["span_id"]]
    values = [v for k, v in sent if k == "xchg_ici_bytes"]
    assert len(values) == 2 and values[0] == 0 < values[1] == first[3]["xchg_ici_bytes"]
    # an unchanged one (``stage``, ``boost``, the id) is sent at open alone
    for field in ("stage", "boost", "span_id", "parent_id", "xchg_elided"):
        assert [k for k, _ in sent].count(field) == 1, field
    # the next dispatch knew them at open: nothing is sent again
    again = _by_name(recorded, "dryad:dispatch:input+order_by")[1]
    sent = recorded["sent"][again[3]["span_id"]]
    assert [k for k, _ in sent].count("xchg_ici_bytes") == 1
    assert again[3]["xchg_ici_bytes"] == first[3]["xchg_ici_bytes"]


def test_every_host_pass_accounts_for_itself(recorded):
    events = {e["span_id"]: e for e in recorded["spans"]}
    seen = set()
    for name, _, _, stats in recorded["annotations"]:
        if name in HOST_PASSES:
            seen.add(name)
            ev = events[stats["span_id"]]
            for field in ("user_s", "sys_s"):
                assert stats[field] == ev[field] >= 0, (name, field)
            # and states the bytes it wrote
            made = "bytes" if name.endswith("fetch_copy") else "bytes_out"
            assert stats[made] == ev[made] > 0, name
        else:  # the plan, the compiles, the dispatches, the waits and
            # the frees: seconds alone
            assert not {"user_s", "sys_s", "bytes_out"} & set(stats), name
    assert seen == HOST_PASSES
    # inclusive of the children, like the seconds
    by_id = {a[3]["span_id"]: a for a in recorded["annotations"]}
    for name in ("dryad:ingest:pack", "dryad:decode:unpack"):
        for _, _, _, stats in _by_name(recorded, name):
            outer = by_id[stats["parent_id"]][3]
            assert stats["user_s"] <= outer["user_s"] + 1e-6
            assert stats["sys_s"] <= outer["sys_s"] + 1e-6


def test_bytes_out_is_the_arrays_a_pass_made(recorded):
    _, inside = _job(recorded, 0)
    table = recorded["table"]
    (encode,) = [a[3] for a in inside if a[0] == "dryad:ingest:encode"]
    # P x capacity slots of the physical columns and ``valid``, written once
    assert encode["capacity"] == ROWS
    assert encode["bytes_out"] == table["k"].nbytes + table["v"].nbytes + ROWS
    assert 0 <= encode["warm_bytes"] <= encode["bytes_out"]
    (_, _, _, decode), = [a for a in inside if a[0] == "dryad:decode:decode"]
    assert decode["bytes_out"] == table["k"].nbytes + table["v"].nbytes
    # BYTES columns: 10 bytes are 3 words, 6 are 2; ``pack`` lies inside
    # the table's ``encode``, whose bytes are the words' and ``valid``'s
    records = recorded["records"]
    np.testing.assert_array_equal(
        recorded["sorted_records"]["key"],
        records["key"][np.lexsort(records["key"].T[::-1])])
    packs = _by_name(recorded, "dryad:ingest:pack")
    assert sorted((a[3]["bytes"], a[3]["bytes_out"]) for a in packs) == [
        (4096 * 6, 4096 * 8), (4096 * 10, 4096 * 12)]
    by_id = {a[3]["span_id"]: a for a in recorded["annotations"]}
    outer = by_id[packs[0][3]["parent_id"]]
    assert outer[0] == "dryad:ingest:encode"
    assert outer[3]["bytes_out"] == 4096 * (12 + 8 + 1)
    unpacks = _by_name(recorded, "dryad:decode:unpack")
    assert sorted(a[3]["bytes_out"] for a in unpacks) == [4096 * 6, 4096 * 10]
    outer = by_id[unpacks[0][3]["parent_id"]]
    assert outer[0] == "dryad:decode:decode"
    assert outer[3]["bytes_out"] == 4096 * 16


def test_the_sample_is_a_child_of_the_span_that_paid_for_it(recorded):
    samples = _by_name(recorded, SAMPLE)
    assert samples
    by_id = {a[3]["span_id"]: a for a in recorded["annotations"]}
    for name, start, end, stats in samples:
        parent = by_id[stats["parent_id"]]
        assert parent[1] <= start <= end <= parent[2]


def test_an_accounted_span_over_a_first_touched_array(tmp_path):
    from dryad_tpu.exec.events import EventLog
    from dryad_tpu.obs.span import Tracer

    log = EventLog(None)
    tracer = Tracer(log)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tracer.span("encode", cat="ingest", account=True, rows=1) as sp:
            made = np.zeros(64 << 20, np.uint8)
            made[:] = 1  # every page of it, for the first time
            sp.add(bytes_out=made.nbytes)
    finally:
        jax.profiler.stop_trace()
    (_, _, _, stats, _), = _annotations(str(tmp_path))
    ev, = log.filter("span")
    for seen in (stats, ev):
        assert seen["bytes_out"] == 64 << 20  # to the byte
        # 16,384 pages touched for the first time: CPU seconds of the
        # process, the kernel's share under whichever of the two the
        # host books it (no fault is counted: the chip's host has none)
        assert seen["user_s"] >= 0 and seen["sys_s"] >= 0
        assert seen["user_s"] + seen["sys_s"] > 0
        assert not {"minflt", "majflt"} & set(seen)
    assert stats["user_s"] == ev["user_s"] and stats["sys_s"] == ev["sys_s"]


def test_without_a_session_nothing_is_sent_and_nothing_is_asked(monkeypatch):
    from dryad_tpu.exec.events import EventLog
    from dryad_tpu.obs import span as span_module

    sent, asked = [], []
    monkeypatch.setattr(span_module.TraceAnnotation, "set_metadata",
                        lambda self, **kw: sent.append(kw), raising=True)
    real = span_module.resource.getrusage
    monkeypatch.setattr(span_module.resource, "getrusage",
                        lambda who: asked.append(who) or real(who))
    log = EventLog(None)
    tracer = span_module.Tracer(log)
    assert not span_module.TraceAnnotation.is_enabled()
    with tracer.span("lower", cat="plan") as sp:
        sp.add(stages=2)
    assert asked == []  # a span not opened with ``account`` asks nothing
    with tracer.span("encode", cat="ingest", account=True, rows=3) as sp:
        sp.add(bytes_out=24)
    assert len(asked) == 2 and sent == []
    lower, encode = log.filter("span")
    assert lower["stages"] == 2 and "user_s" not in lower
    assert encode["bytes_out"] == 24 and encode["rows"] == 3
    assert {"user_s", "sys_s"} <= set(encode)


def test_a_disabled_tracer_opens_no_annotation():
    from dryad_tpu.obs.span import _NULL, Tracer

    assert Tracer().span("h2d", cat="ingest", bytes=1) is _NULL
    assert Tracer(None).current_id() is None


def test_a_collect_is_no_longer_mostly_other(recorded):
    events = recorded["ctx"].events.events()
    root, _ = _job(recorded, 0)
    fold = critpath.fold_query(events, root[3]["qid"])
    assert fold.total_s > 0
    assert fold.phases.get("other", 0.0) < 0.10 * fold.total_s
    assert {"plan", "ingest", "readback", "decode"} <= set(fold.phases)
    assert sum(fold.phases.values()) == pytest.approx(fold.total_s)


def test_phase_table():
    assert critpath.phase_of("lower", "plan") == "plan"
    assert critpath.phase_of("decode", "decode") == "decode"
    assert critpath.phase_of("collect", "job") == "other"
    assert critpath.phase_of("h2d", "ingest") == "ingest"
    assert critpath.phase_of("fetch_wait", "readback") == "readback"
    assert critpath.phase_of("input+order_by", "compile") == "compile"
    assert critpath.phase_of("input+order_by", "execute") == "dispatch"
    assert "plan" in critpath.PHASES and "decode" in critpath.PHASES


# -- operator scopes on the device operations -------------------------------

def _plans(ctx, work):
    rng = np.random.default_rng(7)
    k = (rng.integers(0, 64, 4096) - 1).astype(np.int32)
    v = rng.standard_normal(4096).astype(np.float32)
    text = work / "scopes.txt"
    text.write_text("a b c a b a " * 50)
    return {
        "sort": ctx.from_arrays({"k": k, "v": v}).order_by([("k", False)]),
        "groupby": ctx.from_arrays({"k": k, "v": v}).group_by(
            "k", {"c": ("count", None), "s": ("sum", "v")}),
        "wordcount": ctx.from_text(str(text)).group_by(
            "word", {"count": ("count", None)}).order_by(
                [("count", True)]).take(2),
    }


INNER = {
    "sort": {"dryad.exchange.layout", "dryad.exchange.collective",
             "dryad.sort.splitters", "dryad.sort.carry"},
    "groupby": {"dryad.exchange.layout", "dryad.exchange.collective",
                "dryad.group_reduce.layout", "dryad.group_reduce.fold",
                "dryad.sort.carry"},
    "wordcount": {"dryad.string_code.probe", "dryad.pallas_bucket"},
}


@pytest.mark.parametrize("shape", sorted(INNER))
def test_the_lowered_program_names_its_operators(shape, tmp_path, monkeypatch):
    """Every kernel ``apply_op`` ran put ``dryad.<kind>`` into the
    ``op_name`` of the compiled operations it produced, and the inner
    scopes sit under their operator's."""
    import re

    from dryad_tpu.exec.executor import GraphExecutor
    from dryad_tpu.plan.lower import lower

    lowered = []
    real = GraphExecutor._get_compiled

    def spy(self, *args, **kwargs):
        hit = real(self, *args, **kwargs)
        fn = hit.fn

        def lowering(*operands):
            lowered.append(fn.lower(*operands).compile().as_text())
            return fn(*operands)

        hit.fn = lowering
        return hit

    monkeypatch.setattr(GraphExecutor, "_get_compiled", spy)
    ctx = DryadContext(num_partitions_=4)
    query = _plans(ctx, tmp_path)[shape]
    query.collect()
    graph = lower([query.node], ctx.config, ctx.dictionary, P=4)
    kinds = {op.kind for stage in graph.stages for op in stage.ops}
    assert len(lowered) == 1 and kinds
    paths = re.findall(r'op_name="([^"]*)"', lowered[0])
    scoped = [p for p in paths if "/dryad." in p]
    # ``project`` picks columns: no operation.  Nor has the ``resize`` one
    # before a kernel that sorts valid rows first itself (PR 48).
    for kind in kinds - {"project", "resize"}:
        assert any(f"/dryad.{kind}/" in p + "/" for p in scoped), kind
    assert ("resize" in kinds) == (shape != "wordcount")
    assert not [p for p in scoped if "/dryad.resize/" in p + "/"]
    for scope in INNER[shape]:
        under = [p for p in scoped if f"/{scope}/" in p + "/"]
        assert under, scope
        # an inner scope lies inside an operator's scope
        for p in under:
            first = next(part for part in p.split("/")
                         if part.startswith("dryad."))
            assert first[len("dryad."):] in kinds, p
    # what is left unnamed under shard_map is the stage's own plumbing
    # (broadcasts and casts of its inputs, the overflow flag's psum)
    plumbing = {p.rsplit("/", 1)[-1].split(".")[0]
                for p in paths if "shard_map" in p and "/dryad." not in p}
    assert plumbing <= {"shard_map", "broadcast", "convert_element_type",
                        "gt", "psum", "or", "reduce_or", "ne", "reduce_sum",
                        "concatenate", "squeeze", "reshape"}, plumbing
