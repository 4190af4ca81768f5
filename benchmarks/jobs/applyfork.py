"""``applyfork``: the multi-output DAG (BASELINE.json shape 4, the
reference's ``ApplyAndForkTests.cs``): a per-partition ``apply`` adds a
column, a ``fork`` routes every row into one of two streams of
different record types, one stream is read twice (the Tee), and all
three answers come back from ONE job::

    base      = from_arrays(table).apply(score_fn)        # score = payload * 0.5 + 1
    hot, rest = base.fork(split_fn, ...)                   # (key & 7) < hot_eighths
    A = hot.order_by(["key"])                              # (key, score), sorted
    B = rest                                               # (key, payload), in input order
    C = hot.aggregate_as_query(n = count, t = sum(score))  # one row
    ctx.collect_many([A, B, C])                            # -> (A, B, C)

The table is ``sort``'s: an int32 key uniform over the whole range and
an f32 payload that is a function of its key, so "a column follows its
key" is checked row by row without an argsort.  ``score`` is exact in
f32 however it is fused: ``payload`` is a 24-bit integer, its half is
exact, and the sum is rounded once either way.

The reference is NumPy alone, from the seed, and takes nothing from the
program.  Every pass over a table's worth of rows goes in blocks of
``BLOCK_ROWS``, so the check of a window's answers stays in seconds.

Parameters (from the traffic file): ``rows``, ``hot_eighths``.
"""

import functools

import numpy as np

BLOCK_ROWS = 1 << 16  # rows a pass: the pass's arrays stay in cache
OUTPUTS = 3
SUM = "applyfork.tee_sum_err_over_tol"  # the one number that holds the Tee's ``t``


def blocks(n: int):
    return ((lo, min(n, lo + BLOCK_ROWS)) for lo in range(0, n, BLOCK_ROWS))


def key_payload(key: np.ndarray) -> np.ndarray:
    """``jobs/sort.py::key_payload``, copied (a job file imports no
    other): 24 bits of the key's hash as an f32."""
    mixed = key.view(np.uint32) * np.uint32(2654435761)
    return (mixed >> np.uint32(8)).astype(np.float32)


def score_of(payload: np.ndarray, dtype=np.float32) -> np.ndarray:
    """What ``apply`` adds, carried in ``dtype`` (the control's is
    bfloat16) and handed back as f32."""
    score = payload * np.float32(0.5) + np.float32(1.0)
    return score.astype(dtype).astype(np.float32)


def is_hot(key: np.ndarray, params) -> np.ndarray:
    return (key & 7) < int(params["hot_eighths"])


def reference(arrays, params, score_dtype=np.float32):
    """The three answers, ``(A, B, C)``, with ``score`` carried in
    ``score_dtype`` and ``t`` summed from it in float64."""
    key, payload = arrays["key"], arrays["payload"]
    hot = is_hot(key, params)
    hot_key = np.sort(key[hot])
    score = np.empty(len(hot_key), np.float32)
    t = 0.0
    for lo, hi in blocks(len(hot_key)):
        score[lo:hi] = score_of(key_payload(hot_key[lo:hi]), score_dtype)
        t += float(score[lo:hi].sum(dtype=np.float64))
    return (
        {"key": hot_key, "score": score},
        {"key": key[~hot], "payload": payload[~hot]},
        {"n": np.asarray([len(hot_key)], np.int32),
         "t": np.asarray([t], np.float64)},
    )


def table_of(key: np.ndarray, params) -> dict:
    """The table of these keys and what its answers are compared with.
    A's scores are not kept: they are a function of A's keys."""
    arrays = {"key": key, "payload": key_payload(key)}
    (a, b, c) = reference(arrays, params)
    t = float(c["t"][0])
    return {
        "arrays": arrays,
        "want_hot_key": a["key"],
        "want_rest": b,
        "want_t": t,
        # relative and a few roundings wide, set from readings (PERF.md
        # section 4): an f32 sum formed in blocks and trees, as XLA
        # forms it, reads 1 - 4 roundings of 2^-24 off the float64 sum
        # at every size tried, and the smallest fault planted
        # (``wrong_sums``) reads 5e-3.  Not jobs/groupby.py's
        # order-free bound: that grows with the rows summed, and over
        # 25 M of them it is three times the sum
        "tol": 64 * 2.0**-23 * t + 1e-6,
    }


def make_table(rng, params, workdir, index):
    rows = int(params["rows"])
    key = rng.integers(-(2**31), 2**31, rows, dtype=np.int64).astype(np.int32)
    return table_of(key, params)


def score_fn(batch):
    """The opaque per-partition function of ``apply``."""
    return batch.with_column("score", batch["payload"] * 0.5 + 1.0)


@functools.lru_cache(maxsize=None)
def split_fn(hot_eighths: int):
    """The fork's function: one object a value of ``hot_eighths``, so
    that every job of a run is the same plan to the compile cache."""
    def split(batch):
        hot = (batch["key"] & 7) < hot_eighths
        return (batch.filter(hot).select(["key", "score"]),
                batch.filter(~hot).select(["key", "payload"]))

    return split


class OneJob:
    """What the harness drives: ``.collect()`` submits the queries as
    ONE job (``DryadContext.collect_many``) and hands back the tuple
    ``(A, B, C)``."""

    def __init__(self, ctx, queries):
        self.ctx, self.queries = ctx, queries

    def collect(self):
        return self.ctx.collect_many(self.queries)


def bind(ctx, table, params):
    if not hasattr(ctx, "collect_many"):
        # a program with no multi-output job cannot run the cell at
        # all: leave at once, not after a window of failed pairs
        raise SystemExit(
            "applyfork: the program has no job of several outputs: "
            f"{type(ctx).__name__} has no collect_many()")
    from dryad_tpu import ColumnType, Schema

    i32, f32 = ColumnType.INT32, ColumnType.FLOAT32
    base = ctx.from_arrays(table["arrays"]).apply(
        score_fn, schema=Schema([("key", i32), ("payload", f32), ("score", f32)]))
    hot, rest = base.fork(
        split_fn(int(params["hot_eighths"])),
        [Schema([("key", i32), ("score", f32)]),
         Schema([("key", i32), ("payload", f32)])])
    return OneJob(ctx, [
        hot.order_by(["key"]),
        rest,
        hot.aggregate_as_query({"n": ("count", None), "t": ("sum", "score")}),
    ])


def compare(table, answer, params):
    """name -> (number compared, its limit) over the tuple ``(A, B,
    C)``; every count exact, the Tee's f32 sum within ``tol`` (a few
    roundings, relative) of the float64 sum."""
    want_key, want_rest = table["want_hot_key"], table["want_rest"]
    shapes_ok = (
        isinstance(answer, (tuple, list)) and len(answer) == OUTPUTS
        and set(answer[0]) == {"key", "score"}
        and set(answer[1]) == {"key", "payload"}
        and set(answer[2]) == {"n", "t"}
    )
    if not shapes_ok:
        return {"applyfork.rows_missing": (len(table["arrays"]["key"]), 0)}
    a, b, c = answer
    missing = (abs(len(want_key) - len(a["key"]))
               + abs(len(want_rest["key"]) - len(b["key"])))
    if (missing or a["score"].shape != want_key.shape
            or b["payload"].shape != want_rest["key"].shape
            or c["n"].shape != (1,) or c["t"].shape != (1,)):
        return {"applyfork.rows_missing": (missing or 1, 0)}
    out_of_order = off_key = misrouted = rest_off = 0
    for lo, hi in blocks(len(want_key)):
        key = a["key"][lo:hi]
        out_of_order += int(np.count_nonzero(key != want_key[lo:hi]))
        off_key += int(np.count_nonzero(
            a["score"][lo:hi] != score_of(key_payload(key))))
        misrouted += int(np.count_nonzero(~is_hot(key, params)))
    for lo, hi in blocks(len(want_rest["key"])):
        rest_off += int(np.count_nonzero(
            (b["key"][lo:hi] != want_rest["key"][lo:hi])
            | (b["payload"][lo:hi] != want_rest["payload"][lo:hi])))
    err = abs(float(c["t"][0]) - table["want_t"])
    if not np.isfinite(err):  # a NaN compares as no excess
        err = np.inf
    return {
        "applyfork.rows_missing": (0, 0),
        "applyfork.hot_keys_out_of_order": (out_of_order, 0),
        "applyfork.hot_scores_off_key": (off_key, 0),
        "applyfork.hot_rows_misrouted": (misrouted, 0),
        "applyfork.rest_rows_off": (rest_off, 0),
        "applyfork.tee_count_off": (abs(int(c["n"][0]) - len(a["key"])), 0),
        SUM: (err / table["tol"], 1.0),
    }


def control(table, params):
    """The reference with ``score`` carried in bfloat16, the precision
    below the f32 the configuration states, and ``t`` summed from it.
    It fails by ``hot_scores_off_key`` (nine scores in ten are not
    their key's bit for bit) and NOT by the sum: over 25 M rows the
    bfloat16 roundings cancel, and ``tee_sum_err_over_tol`` reads under
    0.1.  What holds the sum is ``wrong_sums``, so every run that reads
    the control (``benchmarks/limits.py``, which a PR that adds a cell
    may not edit) reads those too: :func:`say_wrong_sums`."""
    import ml_dtypes

    say_wrong_sums(table, params)
    a, b, c = reference(table["arrays"], params, ml_dtypes.bfloat16)
    return a, b, {"n": c["n"], "t": c["t"].astype(np.float32)}


def wrong_sums(table, params) -> dict:
    """Faults planted on the Tee's sum alone, name -> answer: the
    reference with ``t`` summed from the wrong column, with one part
    in 192 of the hot rows dropped from it, and left at zero.  Each has
    to come out not correct through ``compare``, by
    ``tee_sum_err_over_tol`` and by nothing else."""
    a, b, c = reference(table["arrays"], params)
    payload = key_payload(a["key"])
    block = a["score"][:max(1, len(a["score"]) // 192)].sum(dtype=np.float64)

    def with_t(t):
        return a, b, {"n": c["n"], "t": np.asarray([t], np.float32)}

    return {
        "wrong_column": with_t(payload.sum(dtype=np.float64)),
        "dropped_block": with_t(float(c["t"][0]) - block),
        "zero": with_t(0.0),
    }


def say_wrong_sums(table, params) -> None:
    """Every planted fault through ``compare``, one ``[bench] fault``
    line each with what the sum's number read; a fault that passes, or
    that fails by another number, ends the run."""
    for name, answer in wrong_sums(table, params).items():
        checks = compare(table, answer, params)
        over = sorted(n for n, (value, limit) in checks.items() if value > limit)
        value, limit = checks[SUM]
        print(f"[bench] fault job=applyfork planted={name} number={SUM} "
              f"value={value} limit={limit} not_correct_by={','.join(over) or 'none'}",
              flush=True)
        if over != [SUM]:
            raise SystemExit(
                f"applyfork: the planted fault {name!r} must come out not correct "
                f"by {SUM} alone; it did by {over or 'nothing'}")


def input_rows(params) -> int:
    return int(params["rows"])


def min_bytes(params) -> int:
    """The table read once (key + payload, 8 B a row) and every row
    written once, into A or into B (8 B a row)."""
    return 16 * int(params["rows"])
