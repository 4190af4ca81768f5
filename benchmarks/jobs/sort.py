"""``sort``: ``order_by`` over an int32 key with an f32 payload, the
whole table back to the host — the TeraSort shape (BASELINE.json shape
3).  Data, query and checks are a copy of ``chip_smoke.py`` step A: a
later PR may change the program, not this yardstick.

Parameters (from the traffic file): ``rows``.
"""

import numpy as np


def key_payload(key: np.ndarray) -> np.ndarray:
    """A payload that is a function of its key (24 bits, f32-exact), so
    "payload follows its key" is checked row by row, duplicates
    included, without an argsort of the reference."""
    mixed = key.view(np.uint32) * np.uint32(2654435761)
    return (mixed >> np.uint32(8)).astype(np.float32)


def make_table(rng, params, workdir, index):
    rows = int(params["rows"])
    key = rng.integers(-(2**31), 2**31, rows, dtype=np.int64).astype(np.int32)
    return {
        "arrays": {"key": key, "payload": key_payload(key)},
        "want_key": np.sort(key),
    }


def bind(ctx, table, params):
    return ctx.from_arrays(table["arrays"]).order_by(["key"])


def compare(table, out, params):
    """name -> (number compared, its limit); all exact."""
    want = table["want_key"]
    if out["key"].shape != want.shape or out["payload"].shape != want.shape:
        return {"sort.rows_missing": (abs(len(want) - len(out["key"])) or 1, 0)}
    return {
        "sort.rows_missing": (0, 0),
        "sort.keys_out_of_order": (
            int(np.count_nonzero(out["key"] != want)), 0),
        "sort.payloads_off_key": (
            int(np.count_nonzero(out["payload"] != key_payload(out["key"]))),
            0),
    }


def control(table, params):
    """The reference answer with the payload carried in bfloat16, the
    precision below the f32 the configuration states."""
    import ml_dtypes

    want = table["want_key"]
    low = key_payload(want).astype(ml_dtypes.bfloat16).astype(np.float32)
    return {"key": want.copy(), "payload": low}


def input_rows(params) -> int:
    return int(params["rows"])


def min_bytes(params) -> int:
    """Read key + payload once, write both once: 4 columns of 4 bytes."""
    return 16 * int(params["rows"])
