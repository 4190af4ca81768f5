"""``groupby``: sort-path ``group_by`` (count + f32 sum) over an int32
key whose domain holds one negative key, which keeps the auto-dense
rewrite off — the hash-exchange path (BASELINE.json shape 2).  A copy
of ``chip_smoke.py`` step B.

Parameters: ``rows``, ``groups``.
"""

import numpy as np


def make_table(rng, params, workdir, index):
    rows, K = int(params["rows"]), int(params["groups"])
    k = (rng.integers(0, K, rows, dtype=np.int64) - 1).astype(np.int32)
    v = rng.standard_normal(rows, dtype=np.float32)
    want_c = np.bincount(k + 1, minlength=K)
    want_s = np.bincount(k + 1, weights=v, minlength=K)  # float64
    sum_abs = np.bincount(k + 1, weights=np.abs(v), minlength=K)
    # f32 accumulation: one rounding per add, each relative to a
    # partial sum no larger than the group's sum of |v|
    tol = (want_c + 8) * 2.0**-23 * sum_abs + 1e-6
    return {
        "arrays": {"k": k, "v": v},
        "want_c": want_c, "want_s": want_s, "tol": tol,
    }


def bind(ctx, table, params):
    return ctx.from_arrays(table["arrays"]).group_by(
        "k", {"c": ("count", None), "s": ("sum", "v")}
    )


def compare(table, out, params):
    want_c, want_s, tol = table["want_c"], table["want_s"], table["tol"]
    slot = out["k"].astype(np.int64) + 1
    inside = (slot >= 0) & (slot < len(want_c))
    wrong = int(np.count_nonzero(~inside))
    wrong += len(slot) - len(np.unique(slot))  # a key came out twice
    wrong += abs(len(slot) - int(np.count_nonzero(want_c)))
    if wrong:
        return {"groupby.keys_wrong": (wrong, 0)}
    err = np.abs(out["s"].astype(np.float64) - want_s[slot])
    return {
        "groupby.keys_wrong": (0, 0),
        "groupby.counts_differ": (
            int(np.count_nonzero(out["c"] != want_c[slot])), 0),
        "groupby.sum_err_over_tol": (float(np.max(err / tol[slot])), 1.0),
    }


def control(table, params):
    """The reference with ``v`` carried in bfloat16 (the precision below
    the f32 the configuration states) and summed exactly."""
    import ml_dtypes

    k, v = table["arrays"]["k"], table["arrays"]["v"]
    K = len(table["want_c"])
    low = v.astype(ml_dtypes.bfloat16).astype(np.float64)
    s = np.bincount(k + 1, weights=low, minlength=K)
    slot = np.flatnonzero(table["want_c"])
    return {
        "k": (slot - 1).astype(np.int32),
        "c": table["want_c"][slot].astype(np.int32),
        "s": s[slot].astype(np.float32),
    }


def input_rows(params) -> int:
    return int(params["rows"])


def min_bytes(params) -> int:
    """Read k + v once (8 B a row); write k, c, s once for every group."""
    return 8 * int(params["rows"]) + 12 * int(params["groups"])
