"""``join_hash``: two tables too large to broadcast, joined on a key,
the joined rows counted and summed — the main-memory hash-join
benchmark's Workload B (Balkesen et al., ICDE 2013; Kim et al., VLDB
2009) with Blanas et al.'s skewed foreign key (SIGMOD 2011).  Across
chips both sides are exchanged inside the join kernel (``auto`` ->
``shuffle``: ``exec/kernels.py::_co_partition_for_join``) and no
combiner stands in front of a popular key.

``R`` (build): ``key`` int32, a permutation of ``0 .. rows_r - 1`` (a
primary key), ``payload`` int32.  ``S`` (probe): ``key`` int32, a
foreign key into ``R`` drawn Zipf(``zipf_theta``) over R's key range,
``payload`` int32.  8 B a row.  The key of rank r is ``alphabet[r]``,
``alphabet = np.random.default_rng(alphabet_seed).permutation(rows_r)``
(:func:`alphabet`): the SAME for every ``--seed`` and every table of the
pool.  Which customers are popular is a fact of the deployment; what a
run draws is the rows (how many of each rank, where they lie, the
payloads).  It is what the source does (its generator permutes the
alphabet once, from a seed its ``main.c`` fixes) and what makes the
cell steady: the ten hottest keys are 22.5% of S, and the chips they
hash to set which chip's gathers a job waits for (``PERF.md`` section
4).  The answer is the source's "not materialised": one
row, ``matches`` (the pairs) and ``checksum``, the int32 sum modulo
2^32 of ``S.payload ^ R.payload`` over the pairs: a function of BOTH
payloads of a pair whose sum is exact on the device (the engine's
``sum`` of an int32 column is an int32 that wraps; so is the psum over
the chips).

The reference is NumPy and takes nothing from the program: R's payload
laid out by key, gathered at S's keys, mixed with S's payloads, summed
in int64 and cut to its low 32 bits.

Parameters (from the traffic file): ``rows_r``, ``rows_s``,
``zipf_theta``, ``alphabet_seed``, ``expansion``.
"""

import functools
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8  # of a table's drawing; the chip's host has thirteen cores a chip

MATCHES = "join_hash.matches_differ"
CHECKSUM = "join_hash.checksum_differs"
SHAPE = "join_hash.answer_rows_wrong"


# -- the query ----------------------------------------------------------------

def mix(cols):
    """Of a joined row: both payloads in one word.  One object a
    process, so that every job of a run is the same plan to the compile
    cache."""
    return {"mix": cols["payload"] ^ cols["payload_r"]}


def bind(ctx, table, params):
    s = ctx.from_arrays(table["S"])
    r = ctx.from_arrays(table["R"])
    return (
        s.join(r, "key", "key", expansion=float(params["expansion"]),
               strategy="auto")
        .select(mix)
        .aggregate_as_query({"matches": ("count", None),
                             "checksum": ("sum", "mix")})
    )


# -- the table and the reference ------------------------------------------------

def in_chunks(fn, n: int, parts: int = THREADS):
    """``fn(lo, hi)`` over ``parts`` cuts of ``range(n)`` in threads
    (NumPy's loops release the lock), the pieces joined."""
    cuts = np.linspace(0, n, parts + 1).astype(np.int64)
    with ThreadPoolExecutor(parts) as pool:
        return np.concatenate(list(pool.map(fn, cuts[:-1], cuts[1:])))


def zipf_weights(ranks: int, theta: float):
    """``rank ** -theta`` for ranks ``1 .. ranks``, not normalised."""
    return in_chunks(
        lambda lo, hi: np.arange(lo + 1, hi + 1, dtype=np.float64) ** -theta, ranks)


def zipf_ranks(rng, rows: int, ranks: int, theta: float):
    """``rows`` independent Zipf(theta) draws over ``ranks`` ranks, in
    ascending order: uniforms, sorted BEFORE they are searched in the
    CDF (sorted queries walk the CDF front to back: 6 s for 2^26 rows
    over 2^26 ranks in one thread where unsorted ones take minutes; a
    multinomial over 2^26 categories is a binomial a category)."""
    cdf = np.cumsum(zipf_weights(ranks, theta))
    u = rng.random(rows)
    u.sort()
    u *= cdf[-1]
    at = in_chunks(lambda lo, hi: np.searchsorted(cdf, u[lo:hi], side="right"), rows)
    return np.minimum(at, ranks - 1, out=at)  # u == cdf[-1] by rounding


def permutation(rng, n: int):
    out = np.arange(n, dtype=np.int32)
    rng.shuffle(out)
    return out


@functools.lru_cache(maxsize=1)
def alphabet(alphabet_seed: int, rows_r: int):
    """The key of every Zipf rank: the configuration's, not the run's.
    One array a process (every table of a pool has the same)."""
    out = np.random.default_rng(alphabet_seed).permutation(rows_r).astype(np.int32)
    out.setflags(write=False)
    return out


def payloads(rng, rows: int):
    """Every bit of the 4 bytes in use."""
    return rng.integers(-2**31, 2**31 - 1, rows, dtype=np.int32, endpoint=True)


def reference(table) -> dict:
    """``matches`` and ``checksum`` of ANY pair of tables of the two
    columns in which R's keys are unique: plain NumPy."""
    r, s = table["R"], table["S"]
    span = int(max(r["key"].max(), s["key"].max())) + 1
    by_key = np.zeros(span, np.int32)
    held = np.zeros(span, np.bool_)
    by_key[r["key"]] = r["payload"]
    held[r["key"]] = True
    found = held[s["key"]]
    mixed = (by_key[s["key"]] ^ s["payload"])[found]
    total = int(mixed.sum(dtype=np.int64)) & 0xFFFFFFFF
    return {"matches": int(np.count_nonzero(found)),
            "checksum": int(np.uint32(total).astype(np.int32))}


def make_table(rng, params, workdir, index):
    """S is drawn in rank order (the sorted ranks, each rank's key by
    the configuration's :func:`alphabet`), then its rows are shuffled
    into the table: every key's rows fall on every chip.  Neither table
    is partitioned on the key.  The independent parts are drawn side by
    side, each from a generator of its own spawned from ``rng``: but for
    the alphabet the table depends on the seed alone."""
    rows_r, rows_s = int(params["rows_r"]), int(params["rows_s"])
    for_ranks, for_place, for_r = rng.spawn(3)
    with ThreadPoolExecutor(4) as pool:
        ranks = pool.submit(zipf_ranks, for_ranks, rows_s, rows_r,
                            float(params["zipf_theta"]))
        key_of_rank = pool.submit(alphabet, int(params["alphabet_seed"]), rows_r)
        place = pool.submit(permutation, for_place, rows_s)
        r = pool.submit(lambda: {"key": permutation(for_r, rows_r),
                                 "payload": payloads(for_r, rows_r)})
        s_key = key_of_rank.result()[ranks.result()][place.result()]
        table = {"R": r.result(),
                 "S": {"key": s_key, "payload": payloads(for_ranks, rows_s)}}
    table["want"] = reference(table)
    return table


# -- the comparison -------------------------------------------------------------

def compare(table, out, params):
    """name -> (number compared, its limit): equalities, both."""
    want = table["want"]
    if any(len(np.atleast_1d(out.get(c, []))) != 1 for c in ("matches", "checksum")):
        return {SHAPE: (1, 0)}
    return {
        SHAPE: (0, 0),
        MATCHES: (abs(int(np.atleast_1d(out["matches"])[0]) - want["matches"]), 0),
        CHECKSUM: (int(int(np.atleast_1d(out["checksum"])[0]) != want["checksum"]), 0),
    }


def control(table, params):
    """The reference with the payloads carried in 16 bits, the
    precision below the 32 the configuration states: every pair is
    still found, the checksum is another."""
    low = {side: {"key": table[side]["key"],
                  "payload": table[side]["payload"].astype(np.int16).astype(np.int32)}
           for side in ("R", "S")}
    want = reference(low)
    return {"matches": np.asarray([want["matches"]], np.int32),
            "checksum": np.asarray([want["checksum"]], np.int32)}


def controls(table, params) -> dict:
    """Answers that are wrong on purpose, one fault each; between them
    every limit of :func:`compare` fails: one bit of one S payload
    flipped (the checksum alone; every S row is in a pair), one S row
    dropped (a pair fewer, and its mix), two answer rows."""
    r, s = table["R"], table["S"]
    flipped = s["payload"].copy()
    flipped[len(flipped) // 2] ^= 1 << 17
    wrong = {
        "payload_bit_flipped": reference(
            {"R": r, "S": {"key": s["key"], "payload": flipped}}),
        "row_dropped": reference(
            {"R": r, "S": {name: col[1:] for name, col in s.items()}}),
    }
    out = {name: {c: np.asarray([v], np.int32) for c, v in want.items()}
           for name, want in wrong.items()}
    out["two_rows"] = {c: np.asarray([v, v], np.int32)
                       for c, v in table["want"].items()}
    return out


def chip_shares(params, partitions: int):
    """The share of S each of ``partitions`` chips receives in
    expectation: the Zipf mass of the keys the ENGINE's hash
    (``dryad_tpu.ops.hash.partition_ids``, what ``exchange_hash`` routes
    by) sends there.  ``max(shares) * partitions`` is the
    ``probe_side_balance`` to expect, to a part in a thousand (the
    draw's noise on the hottest keys).  Not the reference's: it says
    what the configuration's ``assumed`` states."""
    import jax.numpy as jnp

    from dryad_tpu.ops.hash import partition_ids

    rows_r = int(params["rows_r"])
    weights = zipf_weights(rows_r, float(params["zipf_theta"]))
    chip = np.asarray(partition_ids(
        [jnp.asarray(alphabet(int(params["alphabet_seed"]), rows_r))], partitions))
    mass = np.bincount(chip, weights=weights, minlength=partitions)
    return mass / mass.sum()


# -- what the metrics take ------------------------------------------------------

def input_rows(params) -> int:
    return int(params["rows_r"]) + int(params["rows_s"])


def min_bytes(params) -> int:
    """Read both tables once (8 B a row); write one row of two words."""
    return 8 * (int(params["rows_r"]) + int(params["rows_s"])) + 8
