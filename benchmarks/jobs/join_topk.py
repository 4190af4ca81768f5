"""``join_topk``: fact JOIN dimension on a foreign key, ``order_by`` +
``take`` — broadcast join, pair expansion, fused top-k (BASELINE.json
shape 5).  A copy of ``chip_smoke.py`` step D.

Parameters: ``rows`` (fact), ``dim_rows``, ``top``, ``expansion``.
"""

import numpy as np


def make_table(rng, params, workdir, index):
    rows, dim_rows = int(params["rows"]), int(params["dim_rows"])
    top_n = int(params["top"])
    key = rng.integers(0, dim_rows, rows, dtype=np.int64).astype(np.int32)
    payload = rng.standard_normal(rows, dtype=np.float32)
    dim_key = rng.permutation(dim_rows).astype(np.int32)
    weight = rng.standard_normal(dim_rows, dtype=np.float32)
    # the NumPy gather: weight of dimension row `key`, top rows by payload
    by_key = np.empty(dim_rows, np.float32)
    by_key[dim_key] = weight
    top = np.argpartition(-payload, top_n)[:top_n]
    top = top[np.argsort(-payload[top], kind="stable")]
    if len(np.unique(payload[top])) != top_n:
        raise ValueError("this seed ties two of the top payloads")
    return {
        "fact": {"key": key, "payload": payload},
        "dim": {"dkey": dim_key, "weight": weight},
        "want": {"payload": payload[top], "key": key[top],
                 "weight": by_key[key[top]]},
    }


def bind(ctx, table, params):
    fact = ctx.from_arrays(table["fact"])
    dim = ctx.from_arrays(table["dim"])
    return (
        fact.join(dim, "key", "dkey", expansion=float(params["expansion"]),
                  strategy="auto")
        .order_by([("payload", True)])
        .take(int(params["top"]))
    )


def compare(table, out, params):
    want = table["want"]
    n = len(want["payload"])
    if any(len(out[c]) != n for c in ("payload", "key", "weight")):
        return {"join_topk.rows_missing": (abs(n - len(out["payload"])) or 1, 0)}
    return {
        "join_topk.rows_missing": (0, 0),
        "join_topk.top_rows_differ": (
            int(np.count_nonzero((out["payload"] != want["payload"])
                                 | (out["key"] != want["key"]))), 0),
        "join_topk.weights_differ": (
            int(np.count_nonzero(out["weight"] != want["weight"])), 0),
    }


def control(table, params):
    """The reference with the joined weight carried in bfloat16, the
    precision below the f32 the configuration states."""
    import ml_dtypes

    want = table["want"]
    return {
        "payload": want["payload"].copy(), "key": want["key"].copy(),
        "weight": want["weight"].astype(ml_dtypes.bfloat16).astype(np.float32),
    }


def input_rows(params) -> int:
    return int(params["rows"]) + int(params["dim_rows"])


def min_bytes(params) -> int:
    """Read both tables once (8 B a row); write ``top`` rows of four
    4-byte columns (key, payload, dkey, weight)."""
    return 8 * (int(params["rows"]) + int(params["dim_rows"])) \
        + 16 * int(params["top"])
