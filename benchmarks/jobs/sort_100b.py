"""``sort_100b``: the sort benchmark's record (sortbenchmark.org, Indy /
GraySort rules; ``gensort`` makes it, ``valsort`` checks it): 100 bytes,
the first 10 a binary key compared as unsigned bytes (``memcmp``
order), the other 90 a payload that must arrive with its key.  The
query is ``order_by`` over the key with the payload carried, the whole
sorted table back on the host as ``[rows, 10]`` and ``[rows, 90]``
``uint8``, byte for byte.

The reference here is NumPy alone and takes nothing from the program:
the keys viewed as a big-endian ``>u8`` and ``>u2`` and ordered by
``np.lexsort``.

Parameters (from the traffic file): ``rows``.
"""

import numpy as np

KEY_BYTES, PAYLOAD_BYTES = 10, 90
RECORD_BYTES = KEY_BYTES + PAYLOAD_BYTES
BLOCK_ROWS = 1 << 16  # rows a pass: the pass's arrays stay in cache
_WORDS = -(-PAYLOAD_BYTES // 8)  # 64-bit words that cover a payload
_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_MIX1 = np.uint64(0xBF58476D1CE4E5B9)
_MIX2 = np.uint64(0x94D049BB133111EB)
# what payload word w adds to the key's mix: (w + 1) x golden, mod 2^64
_STEPS = [np.uint64((w + 1) * 0x9E3779B97F4A7C15 % 2**64) for w in range(_WORDS)]


def key_words(key: np.ndarray):
    """``[rows, 10]`` uint8 -> (first 8 bytes as uint64, last 2 as
    uint16), both big-endian reads: their lexicographic order is the
    keys' memcmp order."""
    key = np.ascontiguousarray(key)
    return (key[:, :8].copy().view(">u8").ravel().astype(np.uint64),
            key[:, 8:].copy().view(">u2").ravel().astype(np.uint16))


def key_payload(key: np.ndarray) -> np.ndarray:
    """The 90 payload bytes of every key, ``[rows, 90]`` uint8.  gensort
    writes the record number and filler; this writes a function of the
    key, so that "the payload follows its key" is checked row by row,
    duplicates included, without an argsort.  All ten key bytes go
    through one splitmix64 round (every bit of it depends on every key
    bit); byte ``j`` is byte ``j % 8`` of that mixed once more with
    ``j // 8``: every byte depends on the key and on its position, so a
    payload word that is dropped, swapped with another or cut short
    shows."""
    rows = len(key)
    out = np.empty((rows, PAYLOAD_BYTES), np.uint8)
    for lo in range(0, rows, BLOCK_ROWS):
        k8, k2 = key_words(key[lo:lo + BLOCK_ROWS])
        seed = k8 + (k2.astype(np.uint64) + np.uint64(1)) * _GOLDEN
        seed = (seed ^ (seed >> np.uint64(30))) * _MIX1
        seed = (seed ^ (seed >> np.uint64(27))) * _MIX2
        seed ^= seed >> np.uint64(31)
        words = np.empty((len(k8), _WORDS), np.uint64)
        for w in range(_WORDS):
            mixed = (seed + _STEPS[w]) * _MIX1
            words[:, w] = mixed ^ (mixed >> np.uint64(29))
        out[lo:lo + BLOCK_ROWS] = words.view(np.uint8)[:, :PAYLOAD_BYTES]
    return out


def reference_order(key: np.ndarray) -> np.ndarray:
    """The stable permutation that puts the keys in memcmp order."""
    k8, k2 = key_words(key)
    return np.lexsort((k2, k8))


def table_of(key: np.ndarray) -> dict:
    """The table of these keys and its reference answer's keys."""
    key = np.ascontiguousarray(key, np.uint8)
    return {
        "arrays": {"key": key, "payload": key_payload(key)},
        "want_key": key[reference_order(key)],
    }


def make_table(rng, params, workdir, index):
    """Binary keys uniform over all 2^80 values (gensort's default mode,
    no ``-a``, no ``-s``)."""
    rows = int(params["rows"])
    return table_of(rng.integers(0, 256, (rows, KEY_BYTES), dtype=np.uint8))


def bind(ctx, table, params):
    try:
        return ctx.from_arrays(table["arrays"]).order_by(["key"])
    except TypeError as err:
        # a program with no fixed-width bytes column cannot run the cell
        # at all: leave at once, not after a window of failed pairs
        raise SystemExit(
            f"sort_100b: the program takes no [rows, width] uint8 column: {err}")


def rows_off(got: np.ndarray, want: np.ndarray) -> int:
    """Rows in which any byte differs."""
    off = 0
    for lo in range(0, len(want), BLOCK_ROWS):
        off += int(np.count_nonzero(
            (got[lo:lo + BLOCK_ROWS] != want[lo:lo + BLOCK_ROWS]).any(axis=1)))
    return off


def payloads_off(key: np.ndarray, payload: np.ndarray) -> int:
    """Rows whose payload is not its key's, in any of the 90 bytes."""
    off = 0
    for lo in range(0, len(key), BLOCK_ROWS):
        want = key_payload(key[lo:lo + BLOCK_ROWS])
        off += int(np.count_nonzero(
            (payload[lo:lo + BLOCK_ROWS] != want).any(axis=1)))
    return off


def compare(table, out, params):
    """name -> (number compared, its limit); all exact."""
    want = table["want_key"]
    key, payload = np.asarray(out["key"]), np.asarray(out["payload"])
    if (key.dtype != np.uint8 or payload.dtype != np.uint8
            or key.shape != want.shape
            or payload.shape != (len(want), PAYLOAD_BYTES)):
        return {"sort100b.rows_missing": (abs(len(want) - len(key)) or 1, 0)}
    return {
        "sort100b.rows_missing": (0, 0),
        "sort100b.keys_out_of_order": (rows_off(key, want), 0),
        "sort100b.payloads_off_key": (payloads_off(key, payload), 0),
    }


def control(table, params):
    """The cheaper wrong answer: the table ordered by the key's first
    word alone (four bytes, one uint32 compare), ties left in input
    order.  Keys that tie on four bytes come out in the wrong order
    about half the time (at 2^23 uniform keys some 8,000 pairs tie),
    with their own payloads."""
    arrays = table["arrays"]
    first = arrays["key"][:, :4].copy().view(">u4").ravel()
    order = np.argsort(first, kind="stable")
    return {"key": arrays["key"][order], "payload": arrays["payload"][order]}


def input_rows(params) -> int:
    return int(params["rows"])


def min_bytes(params) -> int:
    """Read every record once and write it once: the published 100
    bytes, whatever words a program pads them to."""
    return 2 * RECORD_BYTES * int(params["rows"])
