"""The deployment ``tpch-sf10-1c`` as its cell ``tpch-q1-1c`` runs it, on
the CPU mesh at 1,500 - 6,000 orders: ``benchmarks/jobs/tpch_q1.py``
loaded by path, its ``bind(...)`` collected fresh and again through
``DryadContext`` at P = 1 and P = 4 against the job's NumPy int64
reference, to the unit, with ``local_debug`` as a second oracle; then
what the PR that added the cell put into the program for it: the exact
wide arithmetic against Python ints (``ops/wide.py``), DECIMAL and DATE
through ingest, ``where``, ``select``, ``group_by``, ``order_by`` and
egress, the channels a mean shares with a sum, the whole-column 64-bit
reduce as a tree, and the spans and counters that say what a fold
carries."""

import datetime
import decimal
import importlib.util
import json
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dryad_tpu
from dryad_tpu import DECIMAL, ColumnType, DryadContext, Schema
from dryad_tpu.ops import segmented as SEG
from dryad_tpu.ops import wide

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXACT = ("tpch_q1.groups_wrong", "tpch_q1.count_order_differs", "tpch_q1.rows_lost",
         "tpch_q1.rows_after_cutoff", "tpch_q1.sum_qty_off_units",
         "tpch_q1.sum_base_price_off_units", "tpch_q1.sum_disc_price_off_units",
         "tpch_q1.sum_charge_off_units")


@pytest.fixture(scope="module")
def job():
    path = os.path.join(ROOT, "benchmarks", "jobs", "tpch_q1.py")
    spec = importlib.util.spec_from_file_location("bench_job_tpch_q1", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def configuration():
    with open(os.path.join(ROOT, "benchmarks", "configs", "tpch-sf10-1c.json")) as fh:
        return json.load(fh)


def params_for(orders, partitions):
    return {"orders": orders, "parts": 2 * orders // 15 + 1, "slots": 1 << 15,
            "delta_days": 90, "partitions": partitions}


def table_of(job, orders, partitions, seed=49):
    params = params_for(orders, partitions)
    return job.make_table(np.random.default_rng([seed, 0]), params, None, 0), params


# -- the cell's query against its reference ------------------------------------------

@pytest.mark.parametrize("partitions", [1, 4])
@pytest.mark.parametrize("orders", [1500, 6000])
def test_q1_fresh_and_again_equals_the_reference_to_the_unit(job, orders, partitions):
    table, params = table_of(job, orders, partitions)
    query = job.bind(DryadContext(num_partitions_=partitions), table, params)
    assert [f.ctype for f in query.schema.fields] == [
        ColumnType.INT32, ColumnType.INT32, DECIMAL(2, wide=True),
        DECIMAL(2, wide=True), DECIMAL(4, wide=True), DECIMAL(6, wide=True),
        ColumnType.FLOAT32, ColumnType.FLOAT32, ColumnType.FLOAT32, ColumnType.INT32]
    want = job.answer_of(table["want"])
    for answer in (query.collect(), query.collect()):
        checks = job.compare(table, answer, params)
        assert set(EXACT) < set(checks)
        assert all(value <= limit for value, limit in checks.values()), checks
        for name in ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge"):
            assert answer[name].dtype == np.int64
            assert answer[name].tolist() == want[name].tolist()
        assert len(answer["count_order"]) == 4  # A|F, N|F, N|O, R|F


def test_local_debug_is_a_second_oracle(job):
    table, params = table_of(job, 1500, 1)
    answer = job.bind(DryadContext(local_debug=True), table, params).collect()
    checks = job.compare(table, answer, params)
    assert all(value <= limit for value, limit in checks.values()), checks


def test_the_reference_is_additive_and_the_table_is_the_seeds(job):
    table, params = table_of(job, 1500, 1)
    again, _ = table_of(job, 1500, 1)
    other, _ = table_of(job, 1500, 1, seed=50)
    for name, col in table["arrays"].items():
        assert np.array_equal(col, again["arrays"][name])
    assert not np.array_equal(table["arrays"]["l_quantity"][:100],
                              other["arrays"]["l_quantity"][:100])
    assert table["want"] == job.reference(table["arrays"], job.cutoff(params))
    a = table["arrays"]
    assert a["l_shipdate"].dtype == np.dtype("datetime64[D]")
    assert all(a[c].dtype == np.int32 for c in a if c != "l_shipdate")
    assert 100 <= a["l_quantity"].min() and a["l_quantity"].max() <= 5000
    assert a["l_extendedprice"].max() <= 10_495_000 and a["l_discount"].max() <= 10
    assert a["l_tax"].max() <= 8 and a["l_shipdate"].min() > np.datetime64("1992-01-01")
    assert set(np.unique(a["l_returnflag"])) == {ord("A"), ord("N"), ord("R")}
    assert table["kept"] < len(a["l_quantity"]) <= 7 * params["orders"]
    assert job.input_rows(params) == 4 * params["orders"]
    assert job.min_bytes(params) == 28 * 4 * params["orders"] + 224
    assert job.fold_bytes(params) == 2 * (41 + 45) * params["slots"]


def test_a_date_on_both_sides_of_the_cutoff_day(job):
    """Rows shipped ON 1998-09-02 count; rows of 1998-09-03 do not."""
    table, params = table_of(job, 1500, 1)
    day = job.cutoff(params)
    assert str(day) == "1998-09-02"
    arrays = {k: v.copy() for k, v in table["arrays"].items()}
    arrays["l_shipdate"][:40] = day
    arrays["l_shipdate"][40:100] = day + np.timedelta64(1, "D")
    want = job.reference(arrays, day)
    table = {"arrays": arrays, "want": want,
             "kept": sum(g["count"] for g in want.values())}
    assert table["kept"] == int(np.count_nonzero(arrays["l_shipdate"] <= day))
    answer = job.bind(DryadContext(num_partitions_=1), table, params).collect()
    checks = job.compare(table, answer, params)
    assert all(value <= limit for value, limit in checks.values()), checks


@pytest.mark.parametrize("partitions", [1, 4])
def test_a_group_that_does_not_occur_and_an_empty_table(job, partitions):
    table, params = table_of(job, 1500, partitions)
    day = job.cutoff(params)
    a = table["arrays"]
    keep = ~((a["l_returnflag"] == ord("N")) & (a["l_linestatus"] == ord("F")))
    for rows in (keep, np.zeros(len(keep), bool)):
        arrays = {k: v[rows] for k, v in a.items()}
        want = job.reference(arrays, day)
        part = {"arrays": arrays, "want": want,
                "kept": sum(g["count"] for g in want.values())}
        answer = job.bind(DryadContext(num_partitions_=partitions), part, params).collect()
        assert len(answer["count_order"]) == len(want) == (3 if rows.any() else 0)
        if rows.any():
            checks = job.compare(part, answer, params)
            assert all(value <= limit for value, limit in checks.values()), checks


def test_the_control_and_the_planted_faults_fail_by_the_numbers_meant(job, capsys):
    table, params = table_of(job, 6000, 1)
    control = job.compare(table, job.control(table, params), params)
    failed = {n for n, (value, limit) in control.items() if value > limit}
    # sums carried in float32: the money is off by far more than a unit
    # (at the cell's size every sum is; 24,000 rows of quantities still
    # fit an f32), the groups and the counts are right
    assert {"tpch_q1.sum_disc_price_off_units", "tpch_q1.sum_charge_off_units"} <= failed
    assert not failed & {"tpch_q1.groups_wrong", "tpch_q1.count_order_differs",
                         "tpch_q1.rows_lost", "tpch_q1.rows_after_cutoff"}
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[bench] fault job=tpch_q1")]
    faults = job.planted_faults(table, params)
    assert set(faults) == {"row_dropped", "discount_off_a_cent", "group_lost",
                           "late_row_counted"}
    assert len(said) == len(faults)
    for name, (answer, meant, alone) in faults.items():
        got = job.compare(table, answer, params)
        over = {n for n, (value, limit) in got.items() if value > limit}
        assert meant <= over and (over == meant or not alone), name
    assert faults["row_dropped"][1] >= {"tpch_q1.rows_lost"}
    assert faults["late_row_counted"][1] >= {"tpch_q1.rows_after_cutoff"}
    assert faults["group_lost"][1] == {"tpch_q1.groups_wrong"}
    assert "tpch_q1.count_order_differs" not in faults["discount_off_a_cent"][1]


def test_the_configuration_file_states_the_deployment(configuration, job):
    c = configuration
    assert c["name"] == "tpch-sf10-1c" and c["chips"] == 1
    assert c["architecture"] is None and c["scale_factor"] == 10
    assert len(c["source"]) <= 200
    # the fallback: the first half of SF 10's orders, stated with the cut
    assert c["reduced"] == ["rows"] and c["orders"] == 7_500_000
    assert c["source_rows"]["orders"] == 15_000_000 and c["source_rows"]["factor"] == 0.5
    assert c["source_rows"]["held_orders"] == c["orders"]
    for words in ("TPC-H", "3.0.1", "4.2.3", "2.4.1", "SF 10", "DELTA 90"):
        assert words in c["source"], words
    assert {"columns", "generator", "specification", "char1", "delta", "slots",
            "mix", "pool", "rows"} <= set(c["assumed"])
    said = " ".join(c["guarantees"])
    for words in ("each once", "unit of their scale", "2^-21", "1998-09-02",
                  "2^63", "deterministic"):
        assert words in said, words
    for constant in ("1,500,000", "200,000", "1 - 7", "151", "20001", "1 - 121",
                     "1 - 30", "1995-06-17"):
        assert constant in c["assumed"]["specification"], constant
    with open(os.path.join(ROOT, "benchmarks", "traffic", "tpch_q1.json")) as fh:
        traffic = json.load(fh)
    assert traffic["orders"] == c["orders"] and traffic["slots"] == 1 << 25
    assert 4.1 * traffic["orders"] < traffic["slots"]
    assert traffic["parts"] == 200_000 * c["scale_factor"] and traffic["pool"] == 2
    assert job.AVG_LIMIT == 2.0**-21


# -- the wide arithmetic against Python ints ------------------------------------------

EXTREMES_32 = [0, -1, 1, 2**31 - 1, -(2**31), 65535, 65536, -65536, 0x7FFF0001,
               -0x7FFF0001, 10_495_000, 108]
EXTREMES_64 = [0, -1, 1, 2**63 - 1, -(2**63), 2**32, -(2**32), 2**32 - 1,
               113_346_000_000, 0x7FFFFFFF_FFFF0001, -0x0000FFFF_FFFFFFFF]


def operands(extremes, bits, n=300, seed=1):
    rnd = random.Random(seed)
    half = 2 ** (bits - 1)
    return [*extremes, *(rnd.randint(-half, half - 1) for _ in range(n))]


def to_pair(values):
    u = [v & (2**64 - 1) for v in values]
    return (np.array([x & 0xFFFFFFFF for x in u], np.uint32),
            np.array([x >> 32 for x in u], np.uint32))


def from_pair(lo, hi):
    out = []
    for low, high in zip(np.asarray(lo).tolist(), np.asarray(hi).tolist()):
        v = (high << 32) | low
        out.append(v - 2**64 if v >= 2**63 else v)
    return out


def wrapped(v):
    v &= 2**64 - 1
    return v - 2**64 if v >= 2**63 else v


def test_mul32_32_is_the_exact_signed_product():
    a = operands(EXTREMES_32, 32)
    b = list(reversed(EXTREMES_32)) + operands([], 32, seed=2)
    got = from_pair(*wide.mul32_32(np.array(a, np.int32), np.array(b, np.int32)))
    assert got == [x * y for x, y in zip(a, b)]  # carries through both limbs


@pytest.mark.parametrize("op", ["mul64_32", "mul64", "add64", "sub64", "neg64",
                                "less64", "equal64", "widen"])
def test_the_pair_arithmetic_wraps_as_int64_does(op):
    a = operands(EXTREMES_64, 64)
    b = list(reversed(EXTREMES_64)) + operands([], 64, seed=3)
    small = (EXTREMES_32 * 30)[: len(a)]
    alo, ahi = to_pair(a)
    blo, bhi = to_pair(b)
    if op == "mul64_32":
        got = from_pair(*wide.mul64_32(alo, ahi, np.array(small, np.int32)))
        assert got == [wrapped(x * y) for x, y in zip(a, small)]
    elif op == "mul64":
        assert from_pair(*wide.mul64(alo, ahi, blo, bhi)) == [
            wrapped(x * y) for x, y in zip(a, b)]
    elif op == "add64":
        assert from_pair(*wide.add64(alo, ahi, blo, bhi)) == [
            wrapped(x + y) for x, y in zip(a, b)]
    elif op == "sub64":
        assert from_pair(*wide.sub64(alo, ahi, blo, bhi)) == [
            wrapped(x - y) for x, y in zip(a, b)]
    elif op == "neg64":
        assert from_pair(*wide.neg64(alo, ahi)) == [wrapped(-x) for x in a]
    elif op == "less64":
        assert np.asarray(wide.less64(alo, ahi, blo, bhi)).tolist() == [
            x < y for x, y in zip(a, b)]
        assert not np.asarray(wide.less64(alo, ahi, alo, ahi)).any()
    elif op == "equal64":
        assert np.asarray(wide.equal64(alo, ahi, blo, bhi)).tolist() == [
            x == y for x, y in zip(a, b)]
    else:
        assert from_pair(*wide.widen(np.array(small, np.int32))) == small


def test_a_decimal_expression_keeps_its_scale_and_is_exact():
    price = wide.Dec((jnp.array([10_495_000, 90_001, 100], jnp.int32),), 2)
    disc = wide.Dec((jnp.array([10, 0, 7], jnp.int32),), 2)
    tax = wide.Dec((jnp.array([8, 0, 3], jnp.int32),), 2)
    disc_price = price * (1 - disc)
    charge = disc_price * (1 + tax)
    assert (disc_price.scale, disc_price.wide) == (4, True)
    assert (charge.scale, charge.wide) == (6, True)
    assert from_pair(*disc_price.words) == [944_550_000, 9_000_100, 9_300]
    assert from_pair(*charge.words) == [102_011_400_000, 900_010_000, 957_900]
    assert not disc_price.narrow().wide
    assert np.asarray(disc_price.narrow().words[0]).tolist() == [944_550_000, 9_000_100, 9_300]
    # a sum takes the larger scale and the wider form; literals are exact
    total = charge + price
    assert (total.scale, total.wide) == (6, True)
    assert from_pair(*total.words) == [206_961_400_000, 1_800_020_000, 1_957_900]
    assert np.asarray((price - decimal.Decimal("0.01")).words[0]).tolist() == [
        10_494_999, 90_000, 99]
    assert from_pair(*(-charge).words) == [-102_011_400_000, -900_010_000, -957_900]
    assert from_pair(*(charge * -3).words)[0] == -306_034_200_000
    assert np.asarray(price > 900).tolist() == [True, True, False]
    assert np.asarray(price <= decimal.Decimal("900.01")).tolist() == [False, True, True]
    assert np.asarray(charge >= disc_price).tolist() == [True, True, True]
    assert np.asarray(charge == charge).all() and not np.asarray(charge != charge).any()
    assert np.asarray(charge.to_f32()).tolist() == pytest.approx(
        [102011.4, 900.01, 0.9579], rel=1e-6)
    for bad in (0.05, "0.05", True):
        with pytest.raises(TypeError):
            price * bad
    with pytest.raises(OverflowError):
        price + 10**20


# -- DECIMAL and DATE through the engine ----------------------------------------------

def money_table(rng, rows=3000):
    return {
        "k": rng.integers(0, 5, rows).astype(np.int32),
        "cents": rng.integers(-2**31, 2**31 - 1, rows).astype(np.int32),
        "big": rng.integers(-2**60, 2**60, rows).astype(np.int64),
        "day": np.datetime64("1990-01-01") + rng.integers(0, 9000, rows).astype(
            "timedelta64[D]"),
    }


MONEY = Schema([("k", ColumnType.INT32), ("cents", DECIMAL(2)),
                ("big", DECIMAL(3, wide=True)), ("day", ColumnType.DATE)])


@pytest.mark.parametrize("partitions", [1, 4])
def test_decimal_and_date_round_trip_and_order(rng, partitions):
    t = money_table(rng)
    ctx = DryadContext(num_partitions_=partitions)
    q = ctx.from_arrays(t, schema=MONEY)
    out = q.collect()
    assert out["cents"].dtype == np.int32 and out["big"].dtype == np.int64
    assert out["day"].dtype == np.dtype("datetime64[D]")
    for name in t:
        assert np.array_equal(out[name], t[name]), name
    # a datetime64[D] column is a DATE without a schema
    assert ctx.from_arrays({"day": t["day"]}).schema.field("day").ctype is ColumnType.DATE
    for key in ("cents", "big", "day"):
        got = q.order_by([(key, True)]).collect()[key]
        assert np.array_equal(got, np.sort(t[key])[::-1]), key
    assert dryad_tpu.date("1970-01-02") == 1
    assert dryad_tpu.date(datetime.date(1998, 9, 2)) == dryad_tpu.date(
        np.datetime64("1998-09-02")) == 10471
    kept = q.where(lambda c: c["day"] <= dryad_tpu.date("2000-01-01")).collect()
    assert len(kept["day"]) == int(np.count_nonzero(t["day"] <= np.datetime64("2000-01-01")))
    assert ctx.from_arrays(
        {"day": t["day"].astype("datetime64[s]")},
        schema=Schema([("day", ColumnType.DATE)])).collect()["day"].tolist() == t["day"].tolist()


@pytest.mark.parametrize("local_debug", [False, True])
def test_group_aggregates_of_decimal_and_date_columns(rng, local_debug):
    t = money_table(rng)
    ctx = DryadContext(local_debug=True) if local_debug else DryadContext(num_partitions_=4)
    q = ctx.from_arrays(t, schema=MONEY).group_by("k", {
        "s": ("sum", "cents"), "sb": ("sum", "big"), "lo": ("min", "cents"),
        "hi": ("max", "big"), "m": ("mean", "cents"), "mb": ("mean", "big"),
        "first_day": ("min", "day"), "last_day": ("max", "day"), "n": ("count", None),
    })
    types = {f.name: f.ctype for f in q.schema.fields}
    assert types["s"] == DECIMAL(2, wide=True) and types["sb"] == DECIMAL(3, wide=True)
    assert types["lo"] == DECIMAL(2) and types["hi"] == DECIMAL(3, wide=True)
    assert types["m"] is ColumnType.FLOAT32 and types["first_day"] is ColumnType.DATE
    out = q.order_by(["k"]).collect()
    for i, k in enumerate(out["k"].tolist()):
        mine = t["k"] == k
        cents, big = t["cents"][mine].astype(np.int64), t["big"][mine]
        assert out["s"][i] == cents.sum() and out["sb"][i] == big.sum()
        assert out["lo"][i] == cents.min() and out["hi"][i] == big.max()
        assert out["n"][i] == mine.sum()
        assert out["m"][i] == pytest.approx(cents.sum() / mine.sum() / 100, rel=2e-6)
        assert out["mb"][i] == pytest.approx(int(big.sum()) / int(mine.sum()) / 1000, rel=2e-6)
        assert out["first_day"][i] == t["day"][mine].min()
        assert out["last_day"][i] == t["day"][mine].max()


@pytest.mark.parametrize("local_debug", [False, True])
def test_whole_column_aggregates_of_decimals(rng, local_debug):
    t = money_table(rng)
    ctx = DryadContext(local_debug=True) if local_debug else DryadContext(num_partitions_=4)
    q = ctx.from_arrays(t, schema=MONEY)
    cents = t["cents"].astype(np.int64)
    assert q.sum_("cents") == cents.sum() and q.sum_("big") == t["big"].sum()
    assert q.min_("big") == t["big"].min() and q.max_("cents") == t["cents"].max()
    assert q.mean("cents") == pytest.approx(cents.sum() / len(cents) / 100, rel=2e-6)
    assert q.max_("day") == t["day"].max()
    with pytest.raises(ValueError, match="DATE"):
        q.aggregate_as_query({"x": ("sum", "day")}).collect()


def test_a_select_over_decimals_is_typed_and_other_plans_are_untouched(rng):
    t = money_table(rng)
    ctx = DryadContext(num_partitions_=1)
    q = ctx.from_arrays(t, schema=MONEY)

    def fn(cols):
        return {"k": cols["k"], "twice": cols["cents"] * 2, "fee": cols["cents"] + 5,
                "sq": cols["cents"] * cols["cents"], "day": cols["day"] + 1}

    sel = q.select(fn)
    types = {f.name: f.ctype for f in sel.schema.fields}
    assert types == {"k": ColumnType.INT32, "twice": DECIMAL(2, wide=True),
                     "fee": DECIMAL(2), "sq": DECIMAL(4, wide=True),
                     "day": ColumnType.DATE}
    out = sel.collect()
    cents = t["cents"].astype(np.int64)
    assert np.array_equal(out["twice"], cents * 2)
    assert np.array_equal(out["sq"], cents * cents)
    assert np.array_equal(out["fee"], (t["cents"] + np.int32(500)))  # wraps as int32
    assert np.array_equal(out["day"], t["day"] + np.timedelta64(1, "D"))
    # value-equal: a rebuilt query is the same plan to the stage cache
    assert q.select(fn).node.params["fn"] == sel.node.params["fn"]
    assert hash(q.select(fn).node.params["fn"]) == hash(sel.node.params["fn"])
    # a table without a DECIMAL hands its function on as it is
    plain = ctx.from_arrays({"k": t["k"]})
    assert plain.select(fn_plain).node.params["fn"] is fn_plain
    assert plain.where(pred_plain).node.params["fn"] is pred_plain
    with pytest.raises(ValueError, match="DECIMAL"):
        q.group_by("k", {"s": ("sum", "cents")}, dense=8)


def fn_plain(cols):
    return {"k": cols["k"]}


def pred_plain(cols):
    return cols["k"] > 1


def test_decimal_and_date_columns_survive_a_store(rng, tmp_path):
    t = money_table(rng, rows=200)
    ctx = DryadContext(num_partitions_=4)
    ctx.from_arrays(t, schema=MONEY).to_store(str(tmp_path / "money"))
    back = ctx.from_store(str(tmp_path / "money"))
    assert back.schema == MONEY
    out = back.collect()
    for name in t:
        assert out[name].dtype == t[name].dtype and np.array_equal(out[name], t[name])


def test_decimal_types_say_what_they_are():
    from dryad_tpu.columnar.schema import parse_ctype

    for ctype in (DECIMAL(2), DECIMAL(6, wide=True), DECIMAL(0)):
        assert parse_ctype(ctype.value) == ctype
    assert parse_ctype("date") is ColumnType.DATE
    assert DECIMAL(2).storage is ColumnType.INT32 and not DECIMAL(2).is_split
    assert DECIMAL(2, wide=True).storage is ColumnType.INT64
    assert ColumnType.DATE.storage is ColumnType.INT32
    assert Schema([("x", DECIMAL(4, wide=True))]).device_names() == ["x#h0", "x#h1"]
    assert Schema([("x", DECIMAL(4)), ("d", ColumnType.DATE)]).device_dtypes() == {
        "x": np.dtype(np.int32), "d": np.dtype(np.int32)}
    assert repr(DECIMAL(2)) == "DECIMAL(2)"
    for bad in (-1, 19, 2.0):
        with pytest.raises(ValueError):
            DECIMAL(bad)


# -- what a fold carries ---------------------------------------------------------------

def q1_aggs(job):
    table, params = table_of(job, 1500, 1)
    ctx = DryadContext(num_partitions_=1)
    return ctx, job.bind(ctx, table, params)


def test_a_mean_shares_the_sum_and_the_count_it_needs(job):
    from dryad_tpu.plan.lower import lower

    ctx, query = q1_aggs(job)
    (stage,) = lower([query.node], ctx.config, ctx.dictionary, P=1).stages
    folds = [op.params for op in stage.ops if op.kind == "group_reduce"]
    assert len(folds) == 2  # before the (elided) exchange and after it
    first, second = (SEG.fold_stats(f["keys"], f["aggs"]) for f in folds)
    # four sums, and ONE more 64-bit channel for the three averages
    assert first == dict(group_keys=2, agg_channels=5, agg64_channels=5,
                         agg_state_words=10)
    assert second == dict(group_keys=2, agg_channels=6, agg64_channels=5,
                          agg_state_words=11)  # the count rides the second fold
    assert sorted(a.col for a in folds[0]["aggs"] if a.op == "sum64") == [
        "charge#h0", "disc_price", "l_discount", "l_extendedprice", "l_quantity"]
    assert sum(a.op == "count" for a in folds[0]["aggs"]) == 1
    assert job.STATE_WORDS == (first["agg_state_words"], second["agg_state_words"])


def test_means_that_share_nothing_keep_their_own_channels(rng):
    t = {"k": rng.integers(0, 7, 500).astype(np.int32),
         "v": rng.standard_normal(500).astype(np.float32),
         "w": rng.integers(-100, 100, 500).astype(np.int64)}
    out = (DryadContext(num_partitions_=4).from_arrays(t)
           .group_by("k", {"mv": ("mean", "v"), "mw": ("mean", "w"), "sw": ("sum", "w"),
                           "n": ("count", None)}).order_by(["k"]).collect())
    for i, k in enumerate(out["k"].tolist()):
        mine = t["k"] == k
        assert out["mv"][i] == pytest.approx(t["v"][mine].mean(), rel=1e-5, abs=1e-6)
        assert out["mw"][i] == pytest.approx(t["w"][mine].mean(), rel=1e-6, abs=1e-6)
        assert out["sw"][i] == t["w"][mine].sum() and out["n"][i] == mine.sum()


@pytest.mark.parametrize("op", SEG.PAIR_OPS)
@pytest.mark.parametrize("rows", [0, 1, 5, 1000, 4097])
def test_the_whole_column_reduce_is_a_tree_and_exact(op, rows, rng):
    values = rng.integers(-2**62, 2**62, rows)
    valid = rng.random(rows) < 0.8
    lo, hi = to_pair(values.tolist())
    got = from_pair(*(x[None] for x in jax.jit(
        lambda lo, hi, valid: SEG.pair_scalar_reduce(op, lo, hi, valid))(
            jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(valid))))[0]
    kept = values[valid].tolist()
    want = {"sum64": wrapped(sum(kept)),
            "min64": min(kept, default=2**63 - 1),
            "max64": max(kept, default=-(2**63))}[op]
    assert got == want
    text = jax.jit(lambda lo, hi, valid: SEG.pair_scalar_reduce(op, lo, hi, valid)).lower(
        jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(valid)).as_text()
    assert "stablehlo.while" not in text and "reduce_window" not in text


def test_the_spans_say_what_the_fold_carries_and_what_the_columns_are(job):
    ctx, query = q1_aggs(job)
    query.collect()
    spans = [e for e in ctx.events.events() if e.get("kind") == "span"]
    (dispatch,) = [e for e in spans if e["name"].startswith("input+where")
                   and e["cat"] == "execute"]
    assert (dispatch["group_keys"], dispatch["agg_channels"],
            dispatch["agg64_channels"], dispatch["agg_state_words"]) == (2, 6, 5, 11)
    assert dispatch["xchg_elided"] == 2
    (lowered,) = [e for e in spans if e["name"] == "lower"]
    assert lowered["decimal_cols"] == 4 and lowered["date_cols"] == 1
    assert "l_extendedprice:decimal32[2]" in lowered["types"]
    assert "l_shipdate:date" in lowered["types"]
    # a stage without a builtin-aggregate group-by says nothing of a fold
    plain = DryadContext(num_partitions_=1)
    plain.from_arrays({"k": np.arange(8, dtype=np.int32)}).order_by(["k"]).collect()
    for e in plain.events.events():
        assert "agg_state_words" not in e


def test_the_wide_arithmetic_carries_its_scope(job):
    def fn(price, disc, tax):
        cols = {"l_extendedprice": wide.Dec((price,), 2), "l_discount": wide.Dec((disc,), 2),
                "l_tax": wide.Dec((tax,), 2), "l_quantity": wide.Dec((price,), 2),
                "l_returnflag": price, "l_linestatus": price}
        return wide.unwrap(job.pricing(cols))

    x = jnp.arange(8, dtype=jnp.int32)
    text = jax.jit(fn).lower(x, x, x).as_text(debug_info=True)
    assert "dryad.decimal" in text
    assert wide.SCOPE == "dryad.decimal"
