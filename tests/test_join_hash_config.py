"""The deployment ``hashjoin-wlb-zipf-4c`` as its cell ``join-hash-4c``
runs it, on the CPU mesh: ``benchmarks/jobs/join_hash.py`` loaded by
path, its ``bind(...)`` collected fresh and again through
``DryadContext`` at P = 4 and P = 1 over the key laws that stress a
co-partitioned join (Blanas's two skews, uniform keys, one key holding
45% of the probe side, which the overflow ladder answers), against the
job's NumPy reference; the alphabet that is the configuration's and
not the seed's, and the share of S a chip that follows from it; then
what the PR that added the cell put into the program for it: what a
dead pair slot reads (every join flavour's valid rows bit for bit what
the old fill gave), the co-partition's scope, the worst single exchange
and the join's pairs on the ``drain`` span, and the cell's stage
program at P = 4, pinned."""

import collections
import hashlib
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dryad_tpu import DryadContext
from dryad_tpu.columnar.batch import ColumnBatch
from dryad_tpu.exec import events as EV
from dryad_tpu.ops import join as J
from dryad_tpu.ops.hash import partition_ids
from dryad_tpu.utils.config import DryadConfig
from test_join_topk_config import lowered_programs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 1 << 13
PARAMS = {"rows_r": ROWS, "rows_s": ROWS, "zipf_theta": 1.05, "alphabet_seed": 54321,
          "expansion": 1.0}
# the cell's plan at a test's size: ``auto`` exchanges both sides once
# the right side is over ``broadcast_limit`` (2^16 rows by default)
CONFIG = DryadConfig(broadcast_limit=256)


@pytest.fixture(scope="module")
def job():
    path = os.path.join(ROOT, "benchmarks", "jobs", "join_hash.py")
    spec = importlib.util.spec_from_file_location("bench_job_join_hash", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def configuration():
    path = os.path.join(ROOT, "benchmarks", "configs", "hashjoin-wlb-zipf-4c.json")
    with open(path) as fh:
        return json.load(fh)


def zipf(theta):
    def make(job, rng):
        return job.make_table(rng, dict(PARAMS, zipf_theta=theta), None, 0)
    return make


def one_key_holds_45_percent(job, rng):
    """45% of S on one key and the rest uniform: at P = 4 its chip
    receives 59% of the rows into room for 50%."""
    table = job.make_table(rng, dict(PARAMS, zipf_theta=0.0), None, 0)
    key = table["S"]["key"].copy()
    key[rng.random(ROWS) < 0.45] = 77
    table["S"] = {"key": key, "payload": table["S"]["payload"]}
    table["want"] = job.reference(table)
    return table


# name -> (table maker, the ladder engages at P = 4)
LAWS = {
    "zipf_1.05": (zipf(1.05), False),
    "zipf_1.25": (zipf(1.25), False),
    "uniform": (zipf(0.0), False),
    "one_key_45_percent": (one_key_holds_45_percent, True),
}


def spans(events, name):
    return [e for e in events if e["kind"] == "span" and e["name"] == name]


@pytest.mark.parametrize("P", [4, 1])
@pytest.mark.parametrize("law", LAWS)
def test_the_cells_query_is_exact(job, law, P):
    make, retries = LAWS[law]
    table = make(job, np.random.default_rng([46, P]))
    assert table["want"]["matches"] == ROWS
    ctx = DryadContext(num_partitions_=P, config=CONFIG)
    query = job.bind(ctx, table, PARAMS)
    for answer in (query.collect(), query.collect()):  # fresh job, requery
        checks = job.compare(table, answer, PARAMS)
        assert set(checks) == {job.MATCHES, job.CHECKSUM, job.SHAPE}
        assert all(check == (0, 0) for check in checks.values()), checks
    events = ctx.events.events()
    plans = [e for e in events if e["kind"] == "join_plan"]
    assert {p["strategy"] for p in plans} == {"shuffle"}
    engaged = retries and P == 4
    overflows = [e for e in events if e["kind"] == "stage_overflow"]
    assert len(overflows) == (2 if engaged else 0)  # a job each: fresh, requery
    drains = spans(events, "drain")
    assert len(drains) == (4 if engaged else 2)
    assert drains[-1]["join_pairs"] >= ROWS
    if P == 4:
        assert drains[-1]["overflows"] == int(engaged)
    if engaged:
        # the brim: the fullest chip's rows are all its room holds, and rows were dropped
        assert drains[0]["recv_fill_max"] == 1.0 and drains[0]["join_pairs"] < ROWS
        # run again at twice the room: the same bits, four times the pair slots
        assert drains[-1]["boost"] == 2 and drains[-1]["recv_fill_max"] < 0.6
        assert drains[-1]["join_slots"] == 4 * plans[0]["out_capacity"]
    for name, wrong in job.controls(table, PARAMS).items():
        assert any(v > limit for v, limit in job.compare(table, wrong, PARAMS).values()), name
    control = job.compare(table, job.control(table, PARAMS), PARAMS)
    assert [n for n, (v, limit) in control.items() if v > limit] == [job.CHECKSUM]


# -- the alphabet is the configuration's ------------------------------------------------

def test_the_alphabet_is_the_same_for_every_seed_and_table(job):
    tables = {(seed, i): job.make_table(np.random.default_rng([seed, i]), PARAMS, None, i)
              for seed in (4600000001, 4600000002) for i in (0, 1)}
    alphabet = np.random.default_rng(54321).permutation(ROWS)
    ranks = {}
    for at, table in tables.items():
        keys, counts = np.unique(table["S"]["key"], return_counts=True)
        order = np.argsort(-counts, kind="stable")[:3]
        ranks[at] = [int(k) for k in keys[order]]
    assert set(map(tuple, ranks.values())) == {tuple(int(k) for k in alphabet[:3])}
    first = tables[(4600000001, 0)]
    for other in (tables[(4600000001, 1)], tables[(4600000002, 0)]):
        for side in ("R", "S"):
            for col in ("key", "payload"):
                assert not np.array_equal(first[side][col], other[side][col]), (side, col)


def test_the_configuration_states_the_share_of_s_a_chip(job, configuration):
    """The configuration's ``assumed`` at the cell's size, reckoned here
    from the 2^16 hottest keys alone (the rest of the mass, 39%, lies on
    67 million keys of at most 4e-7 each and falls evenly, to 1e-4): the
    cell's own alphabet under the engine's own hash."""
    stated = configuration["assumed"]["share_of_s_a_chip"]
    rows, theta = configuration["rows"], 1.05
    assert configuration["alphabet_seed"] == 54321 and rows == 1 << 26
    head = 1 << 16
    alphabet = np.random.default_rng(54321).permutation(rows)[:head].astype(np.int32)
    chips = np.asarray(partition_ids([jnp.asarray(alphabet)], 4))
    assert list(chips[:10]) == stated["the_ten_hottest_keys_chips"]
    total = sum(float((np.arange(lo + 1, min(lo + (1 << 22), rows) + 1, dtype=np.float64)
                       ** -theta).sum()) for lo in range(0, rows, 1 << 22))
    weights = np.arange(1, head + 1, dtype=np.float64) ** -theta / total
    shares = np.bincount(chips, weights=weights, minlength=4) + (1 - weights.sum()) / 4
    assert np.allclose(shares, stated["shares"], atol=2e-4)
    assert shares.max() * 4 == pytest.approx(stated["probe_side_balance"], abs=1e-3)


def test_the_chips_receive_the_share_the_job_file_reckons(job):
    """Scaled to a test's rows: what ``chip_shares`` says of an alphabet
    is what the probe side's exchange delivers, to the draw's noise."""
    table = job.make_table(np.random.default_rng([46, 7]), PARAMS, None, 0)
    shares = job.chip_shares(PARAMS, 4)
    assert shares.sum() == pytest.approx(1.0) and shares.max() * 4 > 1.05
    got = np.bincount(
        np.asarray(partition_ids([jnp.asarray(table["S"]["key"])], 4)), minlength=4)
    assert np.allclose(got / ROWS, shares, atol=0.02)
    ctx = DryadContext(num_partitions_=4, config=CONFIG)
    job.bind(ctx, table, PARAMS).collect()
    pairs, = [e for e in ctx.events.events() if e["kind"] == "join_observed"]
    # a pair a probe row, where its key hashed to; a few collisions more
    assert all(0 <= p - g < 8 for p, g in zip(pairs["pairs"], got))
    drain = spans(ctx.events.events(), "drain")[-1]
    assert drain["recv_balance_max"] == pytest.approx(got.max() * 4 / ROWS)


# -- what a dead pair slot reads ------------------------------------------------------------

def the_old_fill(offsets, counts, total, out_capacity):
    """``_slot_owners`` through PR 45: the running maximum alone, which
    leaves every slot past the last pair on the last row that owns one."""
    rows = jnp.arange(counts.shape[0], dtype=jnp.int32)
    first = jnp.where(counts > 0, offsets, out_capacity)
    heads = jnp.zeros((out_capacity,), jnp.int32).at[first].set(rows, mode="drop")
    return jax.lax.cummax(heads)


def batches(case):
    """(left, right, pair slots): every left table has invalid rows."""
    rng = np.random.default_rng(46)
    n_left, n_right, slots = {"as_the_cell": (512, 512, 512),
                              "left_shorter_than_the_slots": (96, 64, 640),
                              "no_pair_at_all": (128, 64, 256),
                              "more_pairs_than_slots": (256, 64, 200)}[case]
    lk = rng.integers(0, 48, n_left).astype(np.int32)
    rk = rng.integers(0, 48, n_right).astype(np.int32)
    if case == "no_pair_at_all":
        rk += 1000
    if case == "as_the_cell":  # a foreign key into a primary key
        rk = rng.permutation(n_right).astype(np.int32)
        lk = rng.integers(0, n_right, n_left).astype(np.int32)
    left = ColumnBatch({"k": jnp.asarray(lk),
                        "a": jnp.asarray(rng.random(n_left, dtype=np.float32))},
                       jnp.asarray(rng.random(n_left) < 0.8))
    right = ColumnBatch({"k": jnp.asarray(rk),
                         "b": jnp.asarray(rng.integers(-2**31, 2**31 - 1, n_right,
                                                       dtype=np.int64).astype(np.int32))},
                        jnp.asarray(rng.random(n_right) < 0.9))
    return left, right, slots


FLAVOURS = {
    "hash_join": lambda l, r, n: J.hash_join(l, r, ["k"], ["k"], n),
    "hash_join_outer": lambda l, r, n: J.hash_join_outer(
        l, r, ["k"], ["k"], n, {"b": jnp.int32(-1)}),
    "hash_join_ranked": lambda l, r, n: J.hash_join_ranked(
        l, r, ["k"], ["k"], n, rank_limit=2, boost=2),
    "group_join_counts": lambda l, r, n: J.group_join_counts(l, r, ["k"], ["k"], n),
    "exists_mask": lambda l, r, n: J.exists_mask(l, r, ["k"], ["k"], n),
}
CASES = ["as_the_cell", "left_shorter_than_the_slots", "no_pair_at_all",
         "more_pairs_than_slots"]


def valid_rows(out):
    """What a flavour's answer means: the valid rows' bits, in order; a
    per-left-row answer whole."""
    out = out[0]
    if not isinstance(out, ColumnBatch):
        return {"rows": np.asarray(out)}
    keep = np.asarray(out.valid)
    return {name: np.asarray(col)[keep].view(np.uint32) for name, col in out.data.items()}


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("flavour", FLAVOURS)
def test_every_flavours_valid_rows_are_what_the_old_fill_gave(monkeypatch, flavour, case):
    left, right, slots = batches(case)
    new = FLAVOURS[flavour](left, right, slots)
    with monkeypatch.context() as patched:
        patched.setattr(J, "_slot_owners", the_old_fill)
        old = FLAVOURS[flavour](left, right, slots)
    assert bool(new[1]) == bool(old[1]) == (case == "more_pairs_than_slots")
    got, want = valid_rows(new), valid_rows(old)
    assert sorted(got) == sorted(want)
    for name in want:
        assert np.array_equal(got[name], want[name]), name
    assert any(len(v) for v in want.values()) or case == "no_pair_at_all"


@pytest.mark.parametrize("case", CASES)
def test_a_dead_slot_reads_the_row_of_its_own_number(case):
    """Under the last pair the owners are the old ones; past it slot s
    reads row ``s % rows``: in range, ascending, the same for every
    table (the old fill left the whole tail on one row that moved with
    the data)."""
    left, right, slots = batches(case)
    _, _, start, counts = J._probe_ranges(left, right, ["k"], ["k"])
    li, _, pair_valid, _, offsets = (np.asarray(x) for x in J._expand_pairs(start, counts, slots))
    old = np.asarray(the_old_fill(jnp.asarray(offsets), counts, None, slots))
    live = np.asarray(pair_valid)
    assert np.array_equal(li[live], old[live])
    dead = np.flatnonzero(~live)
    assert np.array_equal(li[~live], dead % left.capacity)
    assert li.min() >= 0 and li.max() < left.capacity
    if case == "no_pair_at_all":
        assert not live.any() and len(set(old)) == 1 and len(set(li)) == left.capacity
    if case == "left_shorter_than_the_slots":
        assert slots > 6 * left.capacity and len(set(li[~live])) == left.capacity


# -- what the program says of the join ------------------------------------------------------

@pytest.fixture(scope="module")
def traced(job):
    """The cell's query at P = 4, a fresh job and a requery."""
    table = job.make_table(np.random.default_rng([46, 11]), PARAMS, None, 0)
    ctx = DryadContext(num_partitions_=4, config=CONFIG)
    query = job.bind(ctx, table, PARAMS)
    query.collect()
    query.collect()
    return table, ctx.events.events()


def test_the_drain_says_the_worst_exchange_and_the_joins_pairs(traced):
    table, events = traced
    drains = spans(events, "drain")
    seen = [e for e in events if e["kind"] == "exchange_observed"]
    joined = [e for e in events if e["kind"] == "join_observed"]
    plan, = [e for e in events if e["kind"] == "join_plan"]
    assert len(drains) == len(seen) == len(joined) == 2  # a fresh job and a requery
    sent_s = np.bincount(np.asarray(partition_ids(
        [jnp.asarray(table["S"]["key"])], 4)), minlength=4)
    for drain, exchanged, pairs in zip(drains, seen, joined):
        assert drain["exchanges"] == exchanged["exchanges"] == 2  # the probe side, the build side
        assert drain["resize_sorts"] == exchanged["resize_sorts"] == 0  # neither compacts (PR 48)
        assert drain["combine_rows_out"] == 2 * ROWS and sum(drain["recv_rows"]) == 2 * ROWS
        # the sum flattens what the probe side alone says
        summed = drain["recv_rows_max"] * 4 / drain["combine_rows_out"]
        assert drain["recv_balance_max"] == pytest.approx(sent_s.max() * 4 / ROWS)
        assert drain["recv_balance_max"] >= summed > 1.0
        # the capacity a chip's resize leaves: the slack's two shards
        capacity = plan["left_capacity"]
        assert capacity == 2 * ROWS // 4
        assert drain["recv_fill_max"] == pytest.approx(sent_s.max() / capacity)
        assert drain["recv_fill_max"] == pytest.approx(drain["recv_balance_max"] / 2)
        assert pairs["joins"] == 1 and pairs["slots"] == plan["out_capacity"] == capacity
        assert drain["join_slots"] == pairs["slots"]
        assert drain["join_pairs"] == sum(pairs["pairs"]) >= ROWS
        assert drain["join_pairs_max"] == max(pairs["pairs"])
        assert pairs["name"] == exchanged["name"] == "input+join+select+aggregate"


def test_the_events_obey_the_schema(traced):
    _, events = traced
    for kind in ("exchange_observed", "join_observed", "join_plan", "span"):
        required, optional = EV.EVENT_PAYLOADS[kind]
        mine = [e for e in events if e["kind"] == kind]
        assert mine and kind in EV.EVENT_KINDS
        for event in mine:
            fields = set(event) - {"ts", "mono", "kind"}
            assert set(required) <= fields, (kind, set(required) - fields)
            if kind != "span":  # a span's stats are its own
                assert fields <= set(required) | set(optional), kind
    # numbers alone reach a profiler annotation: the new fields are numbers
    drain = spans(events, "drain")[-1]
    for field, kind in (("recv_balance_max", float), ("recv_fill_max", float),
                        ("join_pairs", int), ("join_pairs_max", int), ("join_slots", int)):
        assert type(drain[field]) is kind, field


def test_one_dispatch_and_one_readback_a_job(traced):
    """The counts ride the overflow flag's readback: a job is one
    dispatch and one ``drain``, and copies back the answer's 36 B and
    nothing else but the flag's arrays."""
    _, events = traced
    executes = [e for e in events if e["kind"] == "span" and e.get("cat") == "execute"]
    assert len(executes) == 2 and len(spans(events, "drain")) == 2
    assert [e["inflight"] for e in spans(events, "drain")] == [1, 1]


def test_a_broadcast_join_says_its_pairs_and_no_exchange(job):
    table = job.make_table(np.random.default_rng([46, 13]), PARAMS, None, 0)
    ctx = DryadContext(num_partitions_=4)  # 2^13 rows: under the default broadcast_limit
    job.bind(ctx, table, PARAMS).collect()
    events = ctx.events.events()
    assert [e["strategy"] for e in events if e["kind"] == "join_plan"] == ["broadcast"]
    assert not [e for e in events if e["kind"] == "exchange_observed"]
    drain = spans(events, "drain")[-1]
    assert drain["join_pairs"] >= ROWS and "recv_balance_max" not in drain
    pairs, = [e for e in events if e["kind"] == "join_observed"]
    assert all(p >= ROWS // 4 for p in pairs["pairs"])  # a shard of S a chip, R whole


# -- the stage program ----------------------------------------------------------------------

def op_counts(program):
    return collections.Counter(re.findall(r"stablehlo\.([a-z_]+)", program.as_text()))


@pytest.fixture(scope="module")
def program(job):
    table = job.make_table(np.random.default_rng([46, 17]), PARAMS, None, 0)
    with pytest.MonkeyPatch.context() as patched:
        lowered, = lowered_programs(
            job, patched, table, PARAMS, 4, DryadContext(num_partitions_=4, config=CONFIG))
    return lowered


def test_the_co_partition_has_a_scope_of_its_own(program):
    from dryad_tpu.parallel import stage

    assert f"module @jit_{stage.PROGRAM_NAME} " in program.as_text()
    # the name stacks of the LOWERED text: a compiled program may come from a
    # cache, whose key leaves the names out (``parallel/stage.py``)
    paths = ["/" + path for path in re.findall(
        r'loc\("([^"]*dryad\.[^"]*)"', program.as_text(debug_info=True))]
    placed = "/dryad.join/dryad.join.copartition/"
    for scope in ("dryad.exchange.layout", "dryad.exchange.collective"):
        assert any(placed + scope + "/" in p + "/" for p in paths), scope
    # both exchanges lie there and nowhere else; their resizes trace nothing
    # since PR 48 (``exec/kernels.py::_reader_sorts``: the job says 0 of 2)
    assert all(placed in p for p in paths if "dryad.exchange." in p)
    assert not [p for p in paths if "dryad.resize" in p]
    # and every operation of the join in one of its parts
    inside = [p for p in paths if "/dryad.join/" in p + "/"]
    outside = {p.rsplit("/", 1)[-1] for p in inside if "/dryad.join/dryad.join." not in p}
    assert inside and outside <= {"or"}, outside  # the kernel's own: overflow | overflow
    for part in ("probe", "expand_pairs", "materialize", "exact", "observe"):
        assert any(f"/dryad.join/dryad.join.{part}/" in p + "/" for p in paths), part


def test_the_cells_program_at_four_partitions_is_pinned(program):
    """The operations of the lowered program, counted by kind: two
    exchanges (an ``all_to_all`` a column and the validity, each side),
    the sorts, the two stacked gathers, and the psums of what the
    exchanges and the join saw.  A change to the join's gathers, to the
    ``resize`` after an exchange or to what rides the readback moves
    it."""
    counts = op_counts(program)
    # seven sorts through PR 47: since PR 48 neither side's ``resize`` traces a
    # compaction (the probe sorts the right side itself; the left rows are
    # gathered where they lie, which the cell measured 0.207 s a job faster)
    assert counts["all_to_all"] == 6 and counts["sort"] == 5
    # two exchanges' (3, P) and one join's (1, P), the flag, the misses, the aggregates
    assert counts["all_reduce"] == 13
    digest = hashlib.sha256(json.dumps(sorted(counts.items())).encode()).hexdigest()
    assert digest == PINNED, sorted(counts.items())


PINNED = "44af40ee8ed6c16ccf523928cb9dfc562b6fc550dcb48723b50f73bab3033e50"
