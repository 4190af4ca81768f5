"""``wordcount``: ``from_text`` -> ``group_by`` count -> top words, on
the dense route (``string_code`` + the Pallas bucket kernel), no
exchange (BASELINE.json shape 1).  A copy of ``chip_smoke.py`` step C;
the vocabulary ``w00000 ...`` is ``bench.py``'s.

Parameters: ``rows`` (words), ``vocab``, ``top``.  A table is a corpus
file written in set-up; reading and tokenizing it is inside the fresh
job's clock (tokenizing IS WordCount's ingest).
"""

import os

import numpy as np


def _write_corpus(path: str, ids: np.ndarray, vocab: int) -> None:
    """``w00000 w00001 ...`` for the given word ids: a (vocab, 7) byte
    table gathered by id."""
    digits = (np.arange(vocab)[:, None] // 10 ** np.arange(4, -1, -1)) % 10
    words = np.empty((vocab, 7), np.uint8)
    words[:, 0] = ord("w")
    words[:, 1:6] = digits + ord("0")
    words[:, 6] = ord(" ")
    with open(path, "wb") as fh:
        for lo in range(0, len(ids), 1 << 24):
            fh.write(words[ids[lo : lo + (1 << 24)]].tobytes())


def make_table(rng, params, workdir, index):
    rows, vocab = int(params["rows"]), int(params["vocab"])
    if vocab > 100_000:
        raise ValueError("the w00000 vocabulary has five digits")
    # Zipf over the vocabulary, as words in a corpus are
    cdf = np.cumsum(1.0 / np.arange(1, vocab + 1))
    cdf /= cdf[-1]
    ids = np.searchsorted(cdf, rng.random(rows, dtype=np.float32))
    ids = np.minimum(ids, vocab - 1).astype(np.int32)
    want = np.bincount(ids, minlength=vocab)
    path = os.path.join(workdir, f"corpus{index}.txt")
    _write_corpus(path, ids, vocab)
    return {
        "path": path, "want": want,
        "want_top": np.sort(want)[::-1][: int(params["top"])],
    }


def bind(ctx, table, params):
    return (
        ctx.from_text(table["path"], column="word")
        .group_by("word", {"count": ("count", None)})
        .order_by([("count", True)])
        .take(int(params["top"]))
    )


def _word_counts(table, words):
    """Reference count of each returned word; -1 for a word that is
    not in the corpus' vocabulary."""
    got = np.full(len(words), -1, np.int64)
    for i, w in enumerate(words):
        tail = str(w)[1:]
        if str(w)[:1] == "w" and tail.isdigit() and int(tail) < len(table["want"]):
            got[i] = table["want"][int(tail)]
    return got


def compare(table, out, params):
    top = table["want_top"]
    if len(out["word"]) != len(top) or len(out["count"]) != len(top):
        return {"wordcount.rows_missing": (abs(len(top) - len(out["word"])) or 1, 0)}
    count = np.asarray(out["count"]).astype(np.int64)
    return {
        "wordcount.rows_missing": (0, 0),
        "wordcount.top_counts_differ": (int(np.count_nonzero(count != top)), 0),
        "wordcount.word_counts_differ": (
            int(np.count_nonzero(_word_counts(table, out["word"]) != count)), 0),
    }


def control(table, params):
    """The reference with counts accumulated in bfloat16 (8 bits of
    mantissa), the precision below the MXU path's f32 accumulator."""
    import ml_dtypes

    low = table["want"].astype(ml_dtypes.bfloat16).astype(np.int64)
    order = np.argsort(-low, kind="stable")[: int(params["top"])]
    return {
        "word": np.array([f"w{i:05d}" for i in order], object),
        "count": low[order],
    }


def input_rows(params) -> int:
    return int(params["rows"])


def min_bytes(params) -> int:
    """Read the four u32 physical columns of the word column once
    (16 B a word); write ``top`` rows of the same width plus a count."""
    return 16 * int(params["rows"]) + 20 * int(params["top"])
