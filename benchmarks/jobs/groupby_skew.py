"""``groupby_skew``: ``group_by`` through a combiner the user writes
(``Decomposable``: the reference's ``[Decomposable]`` reducers,
``GroupByReduceTests.cs`` / ``IDecomposable.cs``) over keys drawn as
YCSB draws them: Zipfian, constant 0.99, the ranks scattered over the
key space.  Per key: the count, the latest reading by time (``last_ts``,
``last_v``) and the mean and variance of the readings, merged pairwise
(Chan et al.)::

    seed(row)   = n 1, ts, last v, mean v, m2 0
    merge(a, b) = n a.n + b.n; (ts, last) of the later row; d = b.mean - a.mean;
                  mean a.mean + d b.n / n; m2 a.m2 + b.m2 + d d a.n b.n / n
    finalize    = count n, last_ts ts, last_v last, mean, var m2 / n

None of ``last_v``, the pair ``(last_ts, last_v)`` or a stable ``var``
can be had from the builtin aggregates, and the merge is no addition:
it runs through ``ops/segmented.py::group_combine`` (a flagged
segmented scan that traces ``merge``), on every chip before the hash
exchange and again after it.

The table: ``k`` int32, the key of rank r is ``perm[r]`` for a
permutation of the key space from the seed (YCSB scrambles the ranks
with an FNV hash; a permutation spreads them as well and collides
nowhere); ``ts`` int32, a permutation of ``arange(rows)``: every row
its own time; ``v`` f32, standard normal + 0.25 x ``(k & 7)``, so that
groups have different means.  12 B a row.

The reference is NumPy in float64, from the definitions and not from
the merge (:func:`grouped_answers`: a group's rows side by side, the
mean, the squares about that mean in a second pass, the row of the
largest time), and takes nothing from the program.

Parameters (from the traffic file): ``rows``, ``groups``,
``zipf_theta``, ``partitions`` (how many chips cut the table: the
planted faults cut the hottest key's rows as the chips do, and
:func:`scan_bytes` sizes a chip's scans).
"""

import functools

import numpy as np

KEYS = "groupby_skew.keys_wrong"
COUNTS = "groupby_skew.counts_differ"
UNCOUNTED = "groupby_skew.rows_uncounted"
LAST_TS = "groupby_skew.last_ts_differ"
LAST_V = "groupby_skew.last_v_differ"
MEAN = "groupby_skew.mean_err_over_rms"
VAR = "groupby_skew.var_err_over_ms"

# The two limits that are not equalities, each RELATIVE TO THE GROUP'S
# OWN SCALE: a mean may lie ``MEAN_LIMIT`` x the group's root mean
# square from the float64 mean, a variance ``VAR_LIMIT`` x its mean
# square from the float64 variance (the standard deviation alone is 0
# for a group of one row, whose mean is still a rounded f32; the
# variance of such a group is 0 on both sides).  Not
# ``jobs/groupby.py``'s order-free bound: that grows with the rows of
# the group, and for the hottest one, two million rows, it is a quarter
# of the group's sum of |v|.  Set from readings, PERF.md section 4: the
# program's largest over ten seeds on the chip below, the bfloat16
# control's smallest above.
MEAN_LIMIT = 2.0**-16
VAR_LIMIT = 2.0**-14

SLACK = 2.0  # DryadConfig().shuffle_slack, the configuration's default
STATE_BYTES = 21  # the scan's flag (1 B) and five state words a slot
FAULT_RUNS = 4096  # fault ``no_cross_term`` cuts the hottest key's rows in so many


# -- the query ----------------------------------------------------------------

def seed(cols):
    import jax.numpy as jnp

    v = cols["v"]
    return {"n": jnp.ones_like(cols["k"]), "ts": cols["ts"], "last": v,
            "mean": v, "m2": jnp.zeros_like(v)}


def merge(a, b):
    """Associative and commutative: ``(ts, last)`` is the maximum under
    a total order, the moments are Chan's pairwise update."""
    import jax.numpy as jnp

    n = a["n"] + b["n"]
    take = (b["ts"] > a["ts"]) | ((b["ts"] == a["ts"]) & (b["last"] > a["last"]))
    d = b["mean"] - a["mean"]
    na, nb, nf = (x.astype(jnp.float32) for x in (a["n"], b["n"], n))
    return {
        "n": n,
        "ts": jnp.where(take, b["ts"], a["ts"]),
        "last": jnp.where(take, b["last"], a["last"]),
        "mean": a["mean"] + d * nb / nf,
        "m2": a["m2"] + b["m2"] + d * d * na * nb / nf,
    }


def finalize(cols):
    import jax.numpy as jnp

    return {
        "k": cols["k"], "count": cols["n"], "last_ts": cols["ts"],
        "last_v": cols["last"], "mean": cols["mean"],
        "var": cols["m2"] / cols["n"].astype(jnp.float32),
    }


@functools.lru_cache(maxsize=None)
def reducer():
    """One object a process, so that every job of a run is the same
    plan to the compile cache."""
    from dryad_tpu import ColumnType, Decomposable

    i32, f32 = ColumnType.INT32, ColumnType.FLOAT32
    return Decomposable(
        seed=seed, merge=merge, finalize=finalize,
        state_cols=["n", "ts", "last", "mean", "m2"],
        out_fields=[("count", i32), ("last_ts", i32), ("last_v", f32),
                    ("mean", f32), ("var", f32)],
    )


def bind(ctx, table, params):
    from dryad_tpu.ops import segmented

    if not hasattr(segmented, "segmented_scan"):
        # the parent of PR 41 scans with ``lax.associative_scan``: for
        # the TPU the six-channel scan never came out of the compiler at
        # the cell's size (cut after 1,500 s, PERF.md section 6).  Such
        # a program cannot run the cell: leave at once, not at the
        # harness's time limit
        raise SystemExit(
            "groupby_skew: this program's group_combine is a "
            "lax.associative_scan, which does not compile for the TPU at the "
            "cell's size: ops/segmented.py has no segmented_scan")
    return ctx.from_arrays(table["arrays"]).group_by("k", decomposable=reducer())


# -- the reference --------------------------------------------------------------

def grouped_answers(k, ts, v):
    """The answer of rows laid out group by group (a key's rows side by
    side; the groups in any order), in float64: one entry a group, in
    the layout's order.  Two passes for the variance; the latest row is
    the one that carries the group's largest time."""
    starts = np.concatenate([[0], np.flatnonzero(k[1:] != k[:-1]) + 1])
    n = np.diff(np.append(starts, len(k)))
    v64 = v.astype(np.float64)
    mean = np.add.reduceat(v64, starts) / n
    off = v64 - np.repeat(mean, n)
    var = np.add.reduceat(off * off, starts) / n
    last_ts = np.maximum.reduceat(ts, starts)
    row_of = np.empty(len(k), np.int64)  # a time is one row's: ts is a permutation
    row_of[ts] = np.arange(len(k))
    return {
        "k": k[starts], "count": n, "last_ts": last_ts,
        "last_v": v[row_of[last_ts]], "mean": mean, "var": var,
    }


def by_key(grouped, groups: int) -> dict:
    """An answer of :func:`grouped_answers` indexed by key, the form
    ``compare`` takes: ``count`` 0 marks a key that does not occur."""
    k = grouped["k"].astype(np.int64)
    out = {}
    for name, dtype in (("count", np.int64), ("last_ts", np.int64),
                        ("last_v", np.float32), ("mean", np.float64),
                        ("var", np.float64)):
        out[name] = np.zeros(groups, dtype)
        out[name][k] = grouped[name]
    return out


def reference(arrays, groups: int) -> dict:
    """The answer of ANY table of the three columns, indexed by key,
    with no sort: ``bincount`` for the count and for the two passes of
    the variance, ``maximum.at`` for the largest time.  What
    ``make_table`` computes group by group as it draws the table
    (:func:`grouped_answers`), from the definitions once more: the
    tests hold the two against each other and against a ``lexsort``."""
    k, ts, v = arrays["k"], arrays["ts"], arrays["v"]
    v64 = v.astype(np.float64)
    count = np.bincount(k, minlength=groups)
    some = np.maximum(count, 1)
    mean = np.bincount(k, weights=v64, minlength=groups) / some
    off = v64 - mean[k]
    last_ts = np.full(groups, -1, np.int64)
    np.maximum.at(last_ts, k, ts)
    row_of = np.empty(len(k), np.int64)
    row_of[ts] = np.arange(len(k))
    return {
        "count": count, "last_ts": np.where(count > 0, last_ts, 0),
        "last_v": np.where(count > 0, v[row_of[np.maximum(last_ts, 0)]],
                           np.float32(0)),
        "mean": mean, "var": np.bincount(k, weights=off * off, minlength=groups) / some,
    }


def answer_of(want) -> dict:
    """A reference answer (indexed by key) as ``collect()`` hands one
    back: a row a key that occurs, the program's types, the float64
    mean and variance rounded to f32."""
    k = np.flatnonzero(want["count"])
    return {
        "k": k.astype(np.int32), "count": want["count"][k].astype(np.int32),
        "last_ts": want["last_ts"][k].astype(np.int32), "last_v": want["last_v"][k],
        "mean": want["mean"][k].astype(np.float32),
        "var": want["var"][k].astype(np.float32),
    }


def make_table(rng, params, workdir, index):
    """Drawn rank by rank, so that the groups lie side by side and the
    reference needs no sort; then the rows are shuffled into the table:
    every key's rows fall on every chip."""
    rows, groups = int(params["rows"]), int(params["groups"])
    weight = np.arange(1, groups + 1, dtype=np.float64) ** -float(params["zipf_theta"])
    per_rank = rng.multinomial(rows, weight / weight.sum())
    key_of_rank = rng.permutation(groups).astype(np.int32)
    k = np.repeat(key_of_rank, per_rank)
    ts = rng.permutation(rows).astype(np.int32)
    v = rng.standard_normal(rows, dtype=np.float32)
    v += np.float32(0.25) * (k & 7).astype(np.float32)
    want = by_key(grouped_answers(k, ts, v), groups)
    place = rng.permutation(rows)
    return {"arrays": {"k": k[place], "ts": ts[place], "v": v[place]}, "want": want}


# -- the comparison -------------------------------------------------------------

def worst(err) -> float:
    """The largest entry; anything not finite counts as infinite (a NaN
    compares as no excess)."""
    if not len(err):
        return 0.0
    return float(np.max(np.where(np.isfinite(err), err, np.inf)))


def compare(table, out, params):
    """name -> (number compared, its limit).  The key set, ``count``,
    ``last_ts`` and the bits of ``last_v`` are equalities; ``mean`` and
    ``var`` are held relative to the group's own scale."""
    want, groups = table["want"], int(params["groups"])
    k = out["k"].astype(np.int64)
    inside = (k >= 0) & (k < groups)
    wrong = int(np.count_nonzero(~inside))
    wrong += len(k) - len(np.unique(k))  # a key came out twice
    wrong += abs(len(k) - int(np.count_nonzero(want["count"])))
    if not wrong:
        wrong = int(np.count_nonzero(want["count"][k] == 0))  # a key that never occurs
    if wrong:
        return {KEYS: (wrong, 0)}
    count = out["count"].astype(np.int64)
    mean64, var64 = want["mean"][k], want["var"][k]
    ms = var64 + mean64 * mean64  # the group's mean square
    with np.errstate(divide="ignore", invalid="ignore"):
        mean_err = np.abs(out["mean"].astype(np.float64) - mean64) / np.sqrt(ms)
        var_err = np.abs(out["var"].astype(np.float64) - var64) / ms
    return {
        KEYS: (0, 0),
        COUNTS: (int(np.count_nonzero(count != want["count"][k])), 0),
        UNCOUNTED: (abs(int(count.sum()) - int(params["rows"])), 0),
        LAST_TS: (int(np.count_nonzero(out["last_ts"] != want["last_ts"][k])), 0),
        LAST_V: (int(np.count_nonzero(
            out["last_v"].view(np.uint32) != want["last_v"][k].view(np.uint32))), 0),
        MEAN: (worst(mean_err), MEAN_LIMIT),
        VAR: (worst(var_err), VAR_LIMIT),
    }


def control(table, params):
    """The reference with ``v`` carried in bfloat16, the precision below
    the f32 the configuration states: ``last_v`` is then not the row's
    bits, and the mean and the variance of a small group are a part in
    2^9 off.  Every run that reads the control reads the planted faults
    too (``benchmarks/limits.py``, which a PR that adds a cell may not
    edit): :func:`say_faults`."""
    import ml_dtypes

    say_faults(table, params)
    arrays = table["arrays"]
    low = arrays["v"].astype(ml_dtypes.bfloat16).astype(np.float32)
    return answer_of(reference({**arrays, "v": low}, int(params["groups"])))


# -- planted faults ---------------------------------------------------------------

def planted_faults(table, params) -> dict:
    """name -> (answer, the numbers it has to come out not correct by,
    whether by those alone).  Each is the reference's answer with the
    hottest key's entry made as one fault of a combiner would leave it;
    the key's rows are cut among the chips as the table is, in
    ``partitions`` runs of its rows."""
    arrays = table["arrays"]
    base = answer_of(table["want"])
    at = int(np.argmax(base["count"]))
    hot = int(base["k"][at])
    shard = len(arrays["k"]) // int(params["partitions"])
    mine = np.flatnonzero(arrays["k"] == hot)
    parts = [mine[mine // shard == p] for p in range(int(params["partitions"]))]

    def of(rows):
        """The key's answer from these of its rows alone."""
        ts, v = arrays["ts"][rows], arrays["v"][rows]
        v64, last = v.astype(np.float64), int(np.argmax(ts))
        return {"k": hot, "count": len(rows), "last_ts": ts[last], "last_v": v[last],
                "mean": v64.mean(), "var": v64.var()}

    def with_hot(**fields):
        out = {name: col.copy() for name, col in base.items()}
        for name, value in fields.items():
            out[name][at] = value
        return out

    v64 = arrays["v"][mine].astype(np.float64)
    runs = np.array_split(v64, min(FAULT_RUNS, len(v64)))
    m2 = sum(float(((r - r.mean()) ** 2).sum()) for r in runs)
    rest = of(np.concatenate(parts[1:]))
    pieces = [of(p) for p in parts if len(p)]
    return {
        # ``m2 = a.m2 + b.m2``: the squares about each run's own mean, summed
        "no_cross_term": (with_hot(var=m2 / len(v64)), {VAR}, True),
        # the per-chip states of the key never merged: the key out once a chip
        "unmerged": ({name: np.concatenate(
            [np.delete(col, at), np.asarray([p[name] for p in pieces], col.dtype)])
            for name, col in base.items()}, {KEYS}, True),
        # the latest reading taken by value and not by time
        "last_by_value": (with_hot(last_v=arrays["v"][mine].max()), {LAST_V}, True),
        # the first chip's partial state of the key lost on the way
        "dropped_state": (with_hot(**rest), {COUNTS, UNCOUNTED}, False),
    }


def say_faults(table, params) -> None:
    """Every planted fault through ``compare``, one ``[bench] fault``
    line each; a fault that passes, or that does not fail by the number
    meant for it, ends the run."""
    for name, (answer, meant, alone) in planted_faults(table, params).items():
        checks = compare(table, answer, params)
        over = {n for n, (value, limit) in checks.items() if value > limit}
        read = " ".join(f"{n}={checks[n][0]}/{checks[n][1]}" for n in sorted(meant)
                        if n in checks)
        print(f"[bench] fault job=groupby_skew planted={name} "
              f"meant={','.join(sorted(meant))} {read} "
              f"not_correct_by={','.join(sorted(over)) or 'none'}", flush=True)
        if not meant <= over or (alone and over != meant):
            raise SystemExit(
                f"groupby_skew: the planted fault {name!r} must come out not correct "
                f"by {sorted(meant)}{' alone' if alone else ''}; it did by "
                f"{sorted(over) or 'nothing'}")


# -- what the metrics take ------------------------------------------------------

def input_rows(params) -> int:
    return int(params["rows"])


def min_bytes(params) -> int:
    """Read k, ts, v once (12 B a row); write the key and five answer
    words once for every group (24 B)."""
    return 12 * int(params["rows"]) + 24 * int(params["groups"])


def scan_bytes(params) -> int:
    """The least the two scans of one job move on ONE chip: the flag
    and the five state words of every slot scanned (``STATE_BYTES``)
    read once and written once.  The scan before the exchange runs over
    the chip's share of the rows, the one after it over ``SLACK`` times
    that (``exec/kernels.py::_do_resize``): the padded slots are
    scanned like rows."""
    slots = int(params["rows"]) // int(params["partitions"])
    return int(2 * STATE_BYTES * (1 + SLACK) * slots)
