"""Dictionary-code lookup for STRING keys — the auto-dense bridge.

A STRING device column is Hash64 word pairs (``columnar/schema.py``);
the context ``StringDictionary`` knows every distinct string a context
ever ingested.  That makes a plain ``group_by`` over a string column a
*dense* problem in disguise: assign each dictionary entry a dense code
(its insertion rank), map rows (h0, h1) -> code on device, and the
whole GroupBy rides the MXU bucket kernel (``ops/pallas_bucket.py``)
with no shuffle — the reference pays a full hash repartition for the
same query (``DryadLinqQueryNode.cs:3581``).

The mapping table is host-built open addressing over the 64-bit hash
(linear probing, power-of-two slots, load <= 0.5); the device does not
probe it: lookup merges the rows with the slots by one carried sort and
hands each row the code of the slot that holds its two words
(``CodeTable.lookup``).  Tables are wrapped in VALUE-equal
objects so the executor's structural compile cache can key on table
*content* (the legacy baked-constant path), or — with
``stringcode_runtime_tables`` — on the table's **shape palette tier**
only, with the arrays fed as call-time device operands (the
static-vs-operand split: DrJAX keeps MapReduce primitives compiling
once per shape the same way).  Every table dimension is quantized to
the power-of-two palette (:func:`palette_domain`), so a widening
vocabulary crosses O(log vocab) tiers instead of forcing O(widenings)
recompiles.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def _mix(h0: np.ndarray, h1: np.ndarray) -> np.ndarray:
    """Slot hash from the two Hash64 words (uint32)."""
    return (h0 ^ (h1 * np.uint32(0x9E3779B9))).astype(np.uint32)


def palette_domain(n: int) -> int:
    """Power-of-two shape-palette step for a dense code domain of ``n``
    codes (min 4).  ONE quantization shared by CodeTable slot sizing,
    DecodeTable padding, and the ingest scope's tier-change test — a
    vocabulary that widens within a step keeps every traced shape (and
    therefore every compile-cache key) identical."""
    d = 4
    while d < max(n, 1):
        d *= 2
    return d


class CodeTable:
    """Open-addressing (h0, h1) -> dense code map; VALUE-equal.

    ``slots_h0/h1``: uint32 hash words per slot; ``slots_code``: int32
    code or -1 for empty; ``num_codes`` = K; misses map to
    ``num_codes_padded`` (past every real code — the dense kernel's
    out-of-range drop in BOTH palette modes).

    Shape palette: ``num_slots`` is ``2 * palette_domain(K)`` (load
    <= 0.5) and the traced lookup depends on nothing else of the table
    — two tables of one ``num_slots`` tier produce byte-identical
    traces and the arrays can travel as runtime operands
    (``operand_arrays``).  ``max_probe``, the longest chain the host
    build met, is a diagnostic only: the device lookup is a merge and
    has no probe budget."""

    operand_arity = 3  # (slots_h0, slots_h1, slots_code)

    def __init__(self, pairs: np.ndarray):
        """``pairs``: (K, 2) uint32 — (h0, h1) per code, in code order."""
        K = len(pairs)
        S = 2 * palette_domain(K)
        h0 = pairs[:, 0].astype(np.uint32)
        h1 = pairs[:, 1].astype(np.uint32)
        slots_h0 = np.zeros(S, np.uint32)
        slots_h1 = np.zeros(S, np.uint32)
        slots_code = np.full(S, -1, np.int32)
        start = _mix(h0, h1) & np.uint32(S - 1)
        max_probe = 1
        for code in range(K):
            j = int(start[code])
            probe = 1
            while slots_code[j] >= 0:
                j = (j + 1) & (S - 1)
                probe += 1
            slots_h0[j] = h0[code]
            slots_h1[j] = h1[code]
            slots_code[j] = code
            max_probe = max(max_probe, probe)
        self.num_slots = S
        self.num_codes = K
        self.num_codes_padded = S // 2  # pow2 >= K: the palette domain
        self.max_probe = max_probe
        self.slots_h0 = slots_h0
        self.slots_h1 = slots_h1
        self.slots_code = slots_code
        import hashlib

        # Content digest FIRST; the Python-level fingerprint derives
        # from it so __hash__ is process-stable (Python's hash() over
        # bytes is per-process salted — job packages and checkpoint
        # meta compare fingerprints across processes).
        self._sha = hashlib.sha1(
            np.int64(S).tobytes()
            + slots_h0.tobytes() + slots_h1.tobytes() + slots_code.tobytes()
        ).hexdigest()
        self._fp = int(self._sha[:16], 16)

    def __eq__(self, other) -> bool:
        return (
            type(other) is CodeTable
            and other._fp == self._fp
            and other.num_slots == self.num_slots
            and np.array_equal(other.slots_h0, self.slots_h0)
            and np.array_equal(other.slots_h1, self.slots_h1)
            and np.array_equal(other.slots_code, self.slots_code)
        )

    def __hash__(self) -> int:
        return self._fp

    def __repr__(self) -> str:
        # content-addressed and PROCESS-STABLE (checkpoint fingerprints
        # embed repr(param)); digest frozen at init — arrays immutable
        return (
            f"CodeTable(S={self.num_slots},K={self.num_codes},"
            f"probe={self.max_probe},sha={self._sha[:12]})"
        )

    # -- runtime-operand protocol (exec.operands.DeviceOperandPool) ----
    def operand_signature(self) -> Tuple:
        """Shape-palette tier: everything the traced lookup bakes in.
        Tables sharing a signature are interchangeable at call time."""
        return ("CodeTable", self.num_slots)

    def operand_arrays(self) -> Tuple[np.ndarray, ...]:
        return (self.slots_h0, self.slots_h1, self.slots_code)

    def operand_sha(self) -> str:
        return self._sha

    def lookup(self, h0, h1, operands=None):
        """Device lookup: (n,) uint32 words -> (n,) int32 codes, misses
        -> num_codes_padded (dropped by the dense kernel's range mask).

        ``operands``: the (slots_h0, slots_h1, slots_code) device
        arrays when the tables travel as runtime operands; None bakes
        them into the trace as constants (legacy path).  Either way the
        trace depends only on ``operand_signature()`` values.

        A merge, not a probe: the ``S`` slots (as they lie, empty ones
        included) and the ``n`` rows are sorted together by one
        ``lax.sort`` on both hash words and then on one word that says
        who an element is (a row's index; ``-(code + 2)`` for a slot, so
        -1 is an empty one): slots come first inside a run of equal
        words, and with every operand a key the sort needs no stability.
        A real entry then lies before every row of its run, so its
        code travels down the run by a running maximum: each real entry
        and each run's head writes
        ``(t << b) | (code + 1)``, ``t`` twice the count of real entries
        so far (plus one for a head that is not a real entry, which so
        takes over from the entry before it with code + 1 = 0, a miss);
        ``t`` never falls, so the maximum so far is the last word
        written.  ``t`` has ``log2 S + 1`` bits and ``code + 1`` has
        ``log2 S``: up to ``S = 2^15`` one int32 holds both, past that
        the code travels in as many slices as it takes (two to
        ``S = 2^20``), each its own running maximum.  A second sort, on
        the carried word, brings the codes back to row order.  No gather,
        no loop, no probe budget: the hit is the exact match of both
        words, whatever the chain in the host-built table.

        Chip-measured on one v5e, seconds a call (PR 29; "the loop" is
        the ``fori_loop`` this replaces, ``probe_bound`` rounds of three
        row-sized gathers): 2^23 rows into 2^15 slots, the loop (64
        rounds) 12.616, this 0.0430 (0.0658 as a stable sort on the two
        words alone, which carries an index more; 0.0475 into 2^17
        slots, the code in two slices); 2^12 rows into 2^18 slots, the
        largest table the default ``auto_dense_limit`` allows, the loop
        (32 rounds) 0.00425, this 0.00137; 2^12 into 2^21, the loop (64)
        0.00809, this 0.01147: the one shape measured with the loop
        ahead, by 3.4 ms, at a table no default plan builds, so there is
        one form.  The code carried by a copy-forward
        ``lax.associative_scan`` in place of the running maximum: 2^20
        rows into 2^15, 0.00780 against 0.00475, and at 2^23 its compile
        for a v5e did not end in 16 minutes."""
        import jax
        import jax.numpy as jnp

        S = self.num_slots
        if operands is not None:
            th0, th1, tco = operands
        else:
            th0 = jnp.asarray(self.slots_h0)
            th1 = jnp.asarray(self.slots_h1)
            tco = jnp.asarray(self.slots_code)
        n = h0.shape[0]
        with jax.named_scope("dryad.string_code.probe"):
            k0, k1, who = jax.lax.sort(
                (
                    jnp.concatenate([th0, h0]),
                    jnp.concatenate([th1, h1]),
                    jnp.concatenate(
                        [-(tco + 2), jnp.arange(n, dtype=jnp.int32)]
                    ),
                ),
                num_keys=3, is_stable=False,
            )
            real = who <= -2
            head = jnp.concatenate([
                jnp.ones((1,), jnp.bool_),
                (k0[1:] != k0[:-1]) | (k1[1:] != k1[:-1]),
            ])
            t = 2 * jnp.cumsum(real.astype(jnp.int32)) + (~real)
            code1 = jnp.where(real, -1 - who, 0)  # code + 1; 0 = none
            code_bits = (S // 2).bit_length()
            step = 31 - (S + 1).bit_length()  # what int32 leaves beside t
            low = (1 << step) - 1
            found = jnp.zeros_like(code1)
            for lo in range(0, code_bits, step):
                last = jax.lax.cummax(jnp.where(
                    head | real, (t << step) | ((code1 >> lo) & low), 0
                ))
                found = found | ((last & low) << lo)
            # rows ascend on ``who``; the slots, all negative, come first
            _, found = jax.lax.sort(
                (who, found), num_keys=1, is_stable=False
            )
        code = found[S:] - 1
        return jnp.where(code < 0, jnp.int32(self.num_codes_padded), code)


class DecodeTable:
    """Dense code -> STRING physical words (h0, h1, r0, r1); VALUE-equal.

    ``words``: (K, 4) uint32 in code order.  The padded gather buffer
    (``2 * palette_domain(K)`` rows, zero-filled past K) is built ONCE
    at construction — it doubles as the zero-pad for any per-partition
    slice and as the fixed-shape runtime operand."""

    operand_arity = 1  # (padded words buffer,)

    def __init__(self, words: np.ndarray):
        import hashlib

        self.words = np.ascontiguousarray(words, np.uint32)
        K = len(self.words)
        self.num_codes_padded = palette_domain(K)
        R = 2 * self.num_codes_padded
        padded = np.zeros((R, 4), np.uint32)
        padded[:K] = self.words
        self.words_padded = padded
        self._sha = hashlib.sha1(
            np.int64(R).tobytes() + self.words.tobytes()
        ).hexdigest()
        self._fp = int(self._sha[:16], 16)

    def __eq__(self, other) -> bool:
        return (
            type(other) is DecodeTable
            and other._fp == self._fp
            and np.array_equal(other.words, self.words)
        )

    def __hash__(self) -> int:
        return self._fp

    def __repr__(self) -> str:
        return f"DecodeTable(K={len(self.words)},sha={self._sha[:12]})"

    # -- runtime-operand protocol --------------------------------------
    def operand_signature(self) -> Tuple:
        return ("DecodeTable", self.words_padded.shape[0])

    def operand_arrays(self) -> Tuple[np.ndarray, ...]:
        return (self.words_padded,)

    def operand_sha(self) -> str:
        return self._sha

    def slice_rows(self, start, count: int, operands=None):
        """Device gather of ``count`` code rows from ``start`` (dynamic):
        returns a (count, 4) uint32 block, rows past K zero-filled.

        ``operands``: the padded device buffer when it travels as a
        runtime operand; None bakes the precomputed host buffer in as a
        trace constant (legacy path — no per-call ``np.concatenate``)."""
        import jax
        import jax.numpy as jnp

        R = self.words_padded.shape[0]
        tab = operands[0] if operands is not None else jnp.asarray(
            self.words_padded
        )
        return jax.lax.dynamic_slice_in_dim(
            tab, jnp.clip(start, 0, R - count), count, axis=0
        )


def build_tables(dictionary) -> Tuple[CodeTable, DecodeTable]:
    """Build the (code, decode) pair from a context StringDictionary in
    insertion order (stable per context; the job package ships the
    driver's lowered plan, so one table serves the whole job).

    Memoized on the dictionary keyed by its length — entries are
    append-only, so length is a valid version stamp; repeated lowers of
    a warm pipeline skip the O(vocabulary) Python build.

    Known granularity limit: the table covers the whole CONTEXT
    dictionary, not the key column's own vocabulary — a context that
    ingested unrelated string columns pays proportionally more buckets
    (correctness unaffected; empty buckets drop at the validity mask).
    """
    cached = getattr(dictionary, "_stringcode_cache", None)
    if cached is not None and cached[0] == len(dictionary):
        return cached[1]
    hashes = []
    strings = []
    for h, s in dictionary.items():
        hashes.append(h)
        strings.append(s)
    tables = _tables_from(hashes, strings)
    dictionary._stringcode_cache = (len(hashes), tables)
    return tables


def _tables_from(hashes, strings) -> Tuple[CodeTable, DecodeTable]:
    """Assemble the (code, decode) pair from parallel hash/string lists
    — the ONE place that knows the physical word layout (shared by the
    whole-dictionary and per-ingest-subset builders)."""
    from dryad_tpu.columnar.schema import split64, string_prefix_rank

    K = len(hashes)
    arr = np.asarray(hashes, np.uint64)
    lo, hi = split64(arr)
    sarr = np.asarray(strings, object)
    r0 = string_prefix_rank(sarr, 0) if K else np.zeros(0, np.uint32)
    r1 = string_prefix_rank(sarr, 4) if K else np.zeros(0, np.uint32)
    pairs = np.stack([lo, hi], axis=1) if K else np.zeros((0, 2), np.uint32)
    words = (
        np.stack([lo, hi, r0, r1], axis=1) if K else np.zeros((0, 4), np.uint32)
    )
    return CodeTable(pairs), DecodeTable(words)


def build_tables_subset(
    dictionary, hashes: np.ndarray
) -> Tuple[CodeTable, DecodeTable]:
    """Build the (code, decode) pair over a SUBSET of the dictionary —
    the key column's own per-ingest vocabulary (``api.query.
    static_str_vocab``) — in dictionary INSERTION order (deterministic
    given the context dictionary; the job package ships the tables
    inside the lowered plan).  Insertion order makes a widening
    vocabulary's tables APPEND-ONLY: existing codes keep their values
    and their probe slots, so the runtime-operand pool can scatter just
    the new entries into the device buffers instead of re-uploading
    (sorted-hash order would renumber every code past each insertion
    point).  Hashes absent from the dictionary are skipped: they cannot
    decode, and the runtime miss guard covers fabricated values.  A
    (len, digest)-keyed memo on the dictionary makes warm re-lowers
    O(1)."""
    hs = np.unique(np.asarray(hashes, np.uint64))
    key = (len(dictionary), hs.tobytes())
    cached = getattr(dictionary, "_stringcode_subset_cache", None)
    if cached is not None and cached[0] == key:
        return cached[1]
    want = set(hs.tolist())
    kept = []
    strings = []
    for h, s in dictionary.items():  # insertion (= code) order
        if h in want:
            kept.append(h)
            strings.append(s)
    tables = _tables_from(kept, strings)
    dictionary._stringcode_subset_cache = (key, tables)
    return tables
