"""``tpch_q1``: TPC-H Q1, the Pricing Summary Report (TPC Benchmark H,
revision 3.0.1, clause 2.4.1), over a ``lineitem`` populated as clause
4.2.3 populates it: a scan of the fact table, a DATE predicate that
keeps 98.6% of it, four exact DECIMAL expressions a row and eight
aggregates over the four groups of ``(l_returnflag, l_linestatus)``::

    select l_returnflag, l_linestatus,
           sum(l_quantity), sum(l_extendedprice),
           sum(l_extendedprice * (1 - l_discount)),
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
           avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
    from lineitem where l_shipdate <= date '1998-12-01' - interval '90' day
    group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus

written against the public API with DECIMAL and DATE columns
(:func:`bind`): no physical column name, no float in a sum.  Money is
scaled integers: ``sum_charge`` is a product of three of them at scale
6, at most 1.13 x 10^11 a row and about 1.1 x 10^18 in the largest
group at SF 10 whole, which an int64 holds (2^63 is 9.2 x 10^18) and neither
an int32 nor an f32 does.

The table holds Q1's seven columns and no other, 28 B a row:
``l_quantity``, ``l_extendedprice``, ``l_discount``, ``l_tax``
DECIMAL(2) (int32 scaled integers), ``l_shipdate`` DATE,
``l_returnflag``, ``l_linestatus`` CHAR(1) as the int32 code point
(order-preserving).  Drawn an order at a time, vectorised, from
``--seed`` with NumPy (not ``dbgen``'s streams: the answers are not the
specification's qualification answers); the rows in order of order.

The reference is NumPy int64 with a masked sum a group, float64 for the
averages, and takes nothing from the program.

Parameters (from the traffic file): ``orders``, ``parts``, ``slots``
(the capacity the table is bound at: one shape for every seed's row
count), ``delta_days``, ``partitions``.
"""

import functools
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8  # of a table's drawing; the chip's host has thirteen cores a chip

GROUPS = "tpch_q1.groups_wrong"
COUNT = "tpch_q1.count_order_differs"
LOST = "tpch_q1.rows_lost"
LATE = "tpch_q1.rows_after_cutoff"
SUM_QTY = "tpch_q1.sum_qty_off_units"
SUM_BASE = "tpch_q1.sum_base_price_off_units"
SUM_DISC = "tpch_q1.sum_disc_price_off_units"
SUM_CHARGE = "tpch_q1.sum_charge_off_units"
AVG = "tpch_q1.avg_rel_err"

# The one limit that is not an equality: an average may lie this far,
# relative, from the float64 quotient of the exact sum and the count.
# The program rounds the 64-bit sum to f32 (two conversions and an
# add), divides by the f32 count and by 10^scale.  Set from readings,
# PERF.md section 4: the program's largest on the chip below, the
# float32 control's smallest above.
AVG_LIMIT = 2.0**-21

SUMS = (  # answer column, the number it is held by, its scale
    ("sum_qty", SUM_QTY, 2), ("sum_base_price", SUM_BASE, 2),
    ("sum_disc_price", SUM_DISC, 4), ("sum_charge", SUM_CHARGE, 6),
)
AVGS = (  # answer column, the exact sum it is the mean of, that sum's scale
    ("avg_qty", "sum_qty", 2), ("avg_price", "sum_base_price", 2),
    ("avg_disc", "sum_discount", 2),
)

STARTDATE = np.datetime64("1992-01-01")
ENDDATE = np.datetime64("1998-12-31")
CURRENTDATE = np.datetime64("1995-06-17")
CUTOFF_BASE = np.datetime64("1998-12-01")

STATE_WORDS = (10, 11)  # 4-byte words of scan state a slot, first fold and second


def cutoff(params) -> np.datetime64:
    return CUTOFF_BASE - np.timedelta64(int(params["delta_days"]), "D")


# -- the query ----------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def shipped_by(day: int):
    """The predicate for a cutoff day.  One function a day a process, so
    that every job of a run is the same plan to the compile cache."""
    return lambda cols: cols["l_shipdate"] <= day


def pricing(cols):
    """Q1's expressions, as SQL writes them: DECIMAL in, DECIMAL out.
    ``disc_price`` is at scale 4 and at most 1,049,500,000, so it goes
    on as the int32 it fits; ``charge`` is at scale 6 and 64 bits."""
    disc_price = cols["l_extendedprice"] * (1 - cols["l_discount"])
    return {
        "l_returnflag": cols["l_returnflag"],
        "l_linestatus": cols["l_linestatus"],
        "l_quantity": cols["l_quantity"],
        "l_extendedprice": cols["l_extendedprice"],
        "l_discount": cols["l_discount"],
        "disc_price": disc_price.narrow(),
        "charge": disc_price * (1 + cols["l_tax"]),
    }


def schema():
    from dryad_tpu import DECIMAL, ColumnType, Schema

    money, code = DECIMAL(2), ColumnType.INT32
    return Schema([
        ("l_quantity", money), ("l_extendedprice", money),
        ("l_discount", money), ("l_tax", money),
        ("l_shipdate", ColumnType.DATE),
        ("l_returnflag", code), ("l_linestatus", code),
    ])


def bind(ctx, table, params):
    import dryad_tpu

    per_partition = -(-int(params["slots"]) // int(params["partitions"]))
    return (
        ctx.from_arrays(table["arrays"], schema=schema(),
                        partition_capacity=per_partition)
        .where(shipped_by(dryad_tpu.date(cutoff(params))))
        .select(pricing)
        .group_by(["l_returnflag", "l_linestatus"], {
            "sum_qty": ("sum", "l_quantity"),
            "sum_base_price": ("sum", "l_extendedprice"),
            "sum_disc_price": ("sum", "disc_price"),
            "sum_charge": ("sum", "charge"),
            "avg_qty": ("mean", "l_quantity"),
            "avg_price": ("mean", "l_extendedprice"),
            "avg_disc": ("mean", "l_discount"),
            "count_order": ("count", None),
        })
        .order_by(["l_returnflag", "l_linestatus"])
    )


def _require_program() -> None:
    """A program without DECIMAL cannot run the cell: say so and leave
    at once, at import, before a table is drawn."""
    try:
        import dryad_tpu
    except ImportError:
        return  # the reference and the generator need no program
    if not hasattr(dryad_tpu, "DECIMAL") or not hasattr(dryad_tpu, "date"):
        raise SystemExit(
            "tpch_q1: this program has no DECIMAL / DATE column types "
            "(dryad_tpu.DECIMAL, dryad_tpu.date): it cannot bind TPC-H's "
            "lineitem or write Q1's exact expressions")


_require_program()


# -- the table and the reference ------------------------------------------------

def draw_lines(rng, orders: int, parts: int) -> dict:
    """``orders`` orders' lines, clause 4.2.3: an order's date uniform
    over [STARTDATE, ENDDATE - 151 days], 1 - 7 lines an order; a line's
    quantity 1 - 50, part 1 - ``parts``, extended price = quantity x the
    part's retail price, discount 0.00 - 0.10, tax 0.00 - 0.08, ship
    date 1 - 121 days after the order, receipt 1 - 30 days after that;
    returned (R or A, at random) if received by CURRENTDATE, else N;
    status O if shipped after CURRENTDATE, else F.  Money in cents."""
    last_order = int((ENDDATE - STARTDATE).astype(np.int64)) - 151
    orderdate = rng.integers(0, last_order, orders, dtype=np.int32, endpoint=True)
    lines = rng.integers(1, 7, orders, dtype=np.int8, endpoint=True)
    n = int(lines.sum(dtype=np.int64))
    quantity = rng.integers(1, 50, n, dtype=np.int32, endpoint=True)
    partkey = rng.integers(1, parts, n, dtype=np.int32, endpoint=True)
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)  # cents
    shipdate = np.repeat(orderdate, lines)
    shipdate += rng.integers(1, 121, n, dtype=np.int32, endpoint=True)
    receipt = shipdate + rng.integers(1, 30, n, dtype=np.int32, endpoint=True)
    today = int((CURRENTDATE - STARTDATE).astype(np.int64))
    returned = np.where(rng.integers(0, 1, n, dtype=np.int8, endpoint=True),
                        np.int32(ord("R")), np.int32(ord("A")))
    return {
        "l_quantity": quantity * 100,
        "l_extendedprice": quantity * retail,  # at most 50 x 209,900 cents
        "l_discount": rng.integers(0, 10, n, dtype=np.int32, endpoint=True),
        "l_tax": rng.integers(0, 8, n, dtype=np.int32, endpoint=True),
        "l_shipdate": STARTDATE + shipdate.astype("timedelta64[D]"),
        "l_returnflag": np.where(receipt <= today, returned, np.int32(ord("N"))),
        "l_linestatus": np.where(shipdate > today, np.int32(ord("O")),
                                 np.int32(ord("F"))),
    }


def reference(arrays, day) -> dict:
    """Q1's exact answer over ANY table of the seven columns:
    ``(flag, status) -> {count, sum_qty, sum_base_price, sum_disc_price,
    sum_charge, sum_discount}`` as Python ints of scaled integers, for
    the groups that occur among the rows shipped on or before ``day``.
    A masked int64 sum a group.  No product passes 2^63: a row's charge
    is at most 10,495,000 x 100 x 108 = 1.13 x 10^11, and 6.0 x 10^7
    rows of it 6.8 x 10^18."""
    price = arrays["l_extendedprice"].astype(np.int64)
    disc_price = price * (100 - arrays["l_discount"])
    columns = {
        "sum_qty": arrays["l_quantity"], "sum_base_price": price,
        "sum_disc_price": disc_price,
        "sum_charge": disc_price * (100 + arrays["l_tax"]),
        "sum_discount": arrays["l_discount"],
    }
    kept = arrays["l_shipdate"] <= day
    code = arrays["l_returnflag"] * 256 + arrays["l_linestatus"]
    out = {}
    for group in np.unique(code[kept]).tolist():
        mine = kept & (code == group)
        out[(group // 256, group % 256)] = {
            "count": int(np.count_nonzero(mine)),
            **{name: int(col[mine].sum(dtype=np.int64))
               for name, col in columns.items()},
        }
    return out


def merged(answers) -> dict:
    """References of disjoint runs of rows as the reference of all of
    them: every entry is a sum."""
    out: dict = {}
    for answer in answers:
        for group, entry in answer.items():
            mine = out.setdefault(group, dict.fromkeys(entry, 0))
            for name, value in entry.items():
                mine[name] += value
    return out


def make_table(rng, params, workdir, index):
    """The orders cut into ``THREADS`` runs, each drawn from a generator
    of its own spawned from ``rng`` and referenced as it is drawn (the
    answer is additive over runs of rows), side by side; the runs joined
    in order."""
    orders = int(params["orders"])
    cuts = np.linspace(0, orders, THREADS + 1).astype(np.int64)
    day = cutoff(params)

    def one(child, lo, hi):
        lines = draw_lines(child, int(hi - lo), int(params["parts"]))
        return lines, reference(lines, day)

    with ThreadPoolExecutor(THREADS) as pool:
        runs = list(pool.map(one, rng.spawn(THREADS), cuts[:-1], cuts[1:]))
        names = list(runs[0][0])
        columns = list(pool.map(
            lambda name: np.concatenate([lines[name] for lines, _ in runs]), names))
    arrays = dict(zip(names, columns))
    want = merged(answer for _, answer in runs)
    kept = sum(entry["count"] for entry in want.values())
    return {"arrays": arrays, "want": want, "kept": kept}


def answer_of(want) -> dict:
    """A reference answer as ``collect()`` hands one back: a row a group
    in ``(flag, status)`` order, the sums int64 scaled integers, the
    averages the float64 quotients rounded to f32."""
    groups = sorted(want)
    rows = [want[g] for g in groups]
    out = {
        "l_returnflag": np.array([g[0] for g in groups], np.int32),
        "l_linestatus": np.array([g[1] for g in groups], np.int32),
        "count_order": np.array([r["count"] for r in rows], np.int32),
    }
    for name, _, _ in SUMS:
        out[name] = np.array([r[name] for r in rows], np.int64)
    for name, total, scale in AVGS:
        out[name] = np.array(
            [r[total] / r["count"] / 10**scale for r in rows], np.float32)
    return out


# -- the comparison -------------------------------------------------------------

def compare(table, out, params):
    """name -> (number compared, its limit).  The groups (exactly those
    that occur, each once, in order), the count and the four sums are
    equalities, the sums to the unit of their scale; the averages are
    held relative to the float64 quotient of the exact sum and count."""
    want = table["want"]
    groups = sorted(want)
    got = list(zip(out["l_returnflag"].tolist(), out["l_linestatus"].tolist()))
    if got != groups:
        return {GROUPS: (len(set(got) ^ set(groups)) or 1, 0)}
    rows = [want[g] for g in groups]
    count = [int(c) for c in out["count_order"]]
    counted, kept = sum(count), int(table["kept"])
    checks = {
        GROUPS: (0, 0),
        COUNT: (sum(c != r["count"] for c, r in zip(count, rows)), 0),
        LOST: (max(0, kept - counted), 0),
        LATE: (max(0, counted - kept), 0),
    }
    for name, number, _ in SUMS:
        checks[number] = (
            max(abs(int(v) - r[name]) for v, r in zip(out[name], rows)), 0)
    worst = 0.0
    for name, total, scale in AVGS:
        for v, r in zip(out[name].tolist(), rows):
            exact = r[total] / r["count"] / 10**scale
            err = abs(v - exact) / abs(exact) if exact else abs(v)
            worst = max(worst, err if np.isfinite(err) else np.inf)
    checks[AVG] = (worst, AVG_LIMIT)
    return checks


def control(table, params):
    """The reference with every sum carried in float32, the precision
    below the exact DECIMAL the configuration states: each sum is off by
    far more than a unit of its scale, the counts are right.  Every run
    that reads the control reads the planted faults too
    (``benchmarks/limits.py``, which a PR that adds a cell may not
    edit): :func:`say_faults`."""
    say_faults(table, params)
    arrays, day = table["arrays"], cutoff(params)
    f32 = np.float32
    price = arrays["l_extendedprice"].astype(f32)
    disc_price = price * (f32(100) - arrays["l_discount"].astype(f32))
    columns = {
        "sum_qty": arrays["l_quantity"].astype(f32), "sum_base_price": price,
        "sum_disc_price": disc_price,
        "sum_charge": disc_price * (f32(100) + arrays["l_tax"].astype(f32)),
        "sum_discount": arrays["l_discount"].astype(f32),
    }
    kept = arrays["l_shipdate"] <= day
    code = arrays["l_returnflag"] * 256 + arrays["l_linestatus"]
    low = {}
    for group in table["want"]:
        mine = kept & (code == group[0] * 256 + group[1])
        low[group] = {
            "count": int(np.count_nonzero(mine)),
            **{name: int(col[mine].sum(dtype=f32)) for name, col in columns.items()},
        }
    return answer_of(low)


# -- planted faults ---------------------------------------------------------------

def planted_faults(table, params) -> dict:
    """name -> (answer, the numbers it has to come out not correct by,
    whether by those alone).  Each is the reference's answer of the
    table with one fault of a scan or a fold planted in it."""
    arrays, day = table["arrays"], cutoff(params)
    kept = np.flatnonzero(arrays["l_shipdate"] <= day)
    late = np.flatnonzero(arrays["l_shipdate"] == day + np.timedelta64(1, "D"))
    sums = {number for _, number, _ in SUMS}

    def of(rows, **changed):
        """The answer of the rows ``rows`` alone, ``changed`` columns
        replaced."""
        part = {name: changed.get(name, col)[rows] for name, col in arrays.items()}
        return answer_of(reference(part, np.datetime64("9999-12-31")))

    off = arrays["l_discount"].copy()
    off[kept[len(kept) // 2]] += 1
    rarest = min(table["want"], key=lambda g: table["want"][g]["count"])
    whole = answer_of(table["want"])
    lost = np.flatnonzero(
        (whole["l_returnflag"] != rarest[0]) | (whole["l_linestatus"] != rarest[1]))
    faults = {
        # the fold drops one row (a run end taken one slot early)
        "row_dropped": (of(np.delete(kept, len(kept) // 3)),
                        {COUNT, LOST} | sums, False),
        # one row's discount read a cent off: the money moves, the counts do not
        "discount_off_a_cent": (of(kept, l_discount=off),
                                {SUM_DISC, SUM_CHARGE}, False),
        # the rarest group (N|F) lost on the way
        "group_lost": ({name: col[lost] for name, col in whole.items()},
                       {GROUPS}, True),
    }
    if len(late):
        # ``<`` read as ``<=`` one day on: a row of the day after counted
        faults["late_row_counted"] = (
            of(np.append(kept, late[0])), {COUNT, LATE}, False)
    return faults


def say_faults(table, params) -> None:
    """Every planted fault through ``compare``, one ``[bench] fault``
    line each; a fault that passes, or that does not fail by the numbers
    meant for it, ends the run."""
    for name, (answer, meant, alone) in planted_faults(table, params).items():
        checks = compare(table, answer, params)
        over = {n for n, (value, limit) in checks.items() if value > limit}
        read = " ".join(f"{n}={checks[n][0]}/{checks[n][1]}" for n in sorted(meant)
                        if n in checks)
        print(f"[bench] fault job=tpch_q1 planted={name} "
              f"meant={','.join(sorted(meant))} {read} "
              f"not_correct_by={','.join(sorted(over)) or 'none'}", flush=True)
        if not meant <= over or (alone and over != meant):
            raise SystemExit(
                f"tpch_q1: the planted fault {name!r} must come out not correct "
                f"by {sorted(meant)}{' alone' if alone else ''}; it did by "
                f"{sorted(over) or 'nothing'}")


# -- what the metrics take ------------------------------------------------------

def input_rows(params) -> int:
    """The rows a table is expected to hold: 4 lines an order (a run's
    own count is within a part in 10^4 of it)."""
    return 4 * int(params["orders"])


def min_bytes(params) -> int:
    """Read the seven columns once (28 B a row); write 4 rows of 10
    columns (two keys, the count and three averages of 4 B, four sums of
    8 B)."""
    return 28 * input_rows(params) + 4 * (6 * 4 + 4 * 8)


def fold_bytes(params) -> int:
    """The least the two folds of one job move: the flag (1 B) and the
    state words of every slot (``STATE_WORDS``: five 64-bit sums in the
    first fold, and the count beside them in the second), read once and
    written once.  Both folds run over every slot the table is bound at:
    padding is scanned like rows."""
    slots = int(params["slots"])
    return sum(2 * (1 + 4 * words) * slots for words in STATE_WORDS)
