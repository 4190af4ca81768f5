"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no arguments, every visible chip, default ``DryadConfig()``.
It drives the engine's main path — ``DryadContext`` → planner →
``GraphExecutor`` → ``shard_map`` stages over ``make_mesh()`` — through
four of the five reference shapes (``BASELINE.json``) at a size a
deployment would hold on the chip (2^26 rows per chip: one chip's
share of the 1 TB / v5e-256 sort is 3.9 GB), and checks every answer
against plain NumPy:

  A  range-partitioned sort            ``order_by``            (shape 3)
  B  GroupBy + aggregate, sort path    hash exchange           (shape 2)
  C  WordCount, dense MXU path         Pallas bucket kernel    (shape 1)
  D  Join + OrderBy                    broadcast join + top-k  (shape 5)
  E  GroupBy, a user's combiner        ``Decomposable``, Zipf  (shape 2)

Step E is the ``groupby-skew-4c`` cell's query and reference, loaded
from ``benchmarks/jobs/groupby_skew.py``, at a quarter of the other
steps' rows (2^24 a chip, the cell's): its scan carries six channels
over every slot of the exchange's padded capacity.

Each step runs its query twice on one context and prints ``rows``,
``first_s`` (ingest + compile + run) and ``repeat_s`` (resident table,
compiled program); both end in a host readback, so the clock stops
after the device does.  These are smoke timings, not a benchmark.

It exits non-zero, printing no result line, when jax finds no TPU — no
CPU fallback, no platform switch.  On success the last line of stdout
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.

The step functions take ``(ctx, rows, seed)`` and hold nothing
TPU-specific, so ``tests/test_chip_smoke.py`` runs the same steps at
2^12 rows on the 8-device CPU mesh; ``main()`` holds the TPU-only
assertions.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

import numpy as np

LOG2_ROWS_PER_CHIP = 26
SEED = 20260926
VOCAB = 1 << 14  # the wordcount-1c cell's vocabulary
DIM_ROWS = 1 << 16
TOP_WORDS = 20
TOP_JOIN = 100
# FK join: every fact row matches exactly one dimension row, so the
# candidate-pair buffer needs the fact table's size plus room for
# 31-bit hash collisions among the 2^16 dimension keys — not the 4x a
# many-to-many join reserves by default.
JOIN_EXPANSION = 1.25


class SmokeFailure(AssertionError):
    """An answer, a plan or a device fact was not what it has to be."""


def need(ok, *what) -> None:
    """The smoke's check: raises (also under ``python -O``)."""
    if not ok:
        raise SmokeFailure(" ".join(str(w) for w in what))


def say(step: str, **fields) -> None:
    """One ``key=value`` result line on stdout."""
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[smoke] step={step} {body}", flush=True)


def _run_twice(ctx, step: str, rows: int, run, check, **extra) -> None:
    """``run()`` twice, ``check(out)`` on both answers, one result line.
    ``compile_s`` is the executor's own count of the first dispatch of
    each new program (trace + XLA compile), a part of ``first_s``."""
    metrics = ctx.executor.metrics
    times, compiled = [], []
    for _ in range(2):
        c0 = metrics.total("xla_compile_s"), metrics.total("xla_compiles")
        t0 = time.perf_counter()
        out = run()  # collect(): ends in the device->host readback
        times.append(time.perf_counter() - t0)
        compiled.append((metrics.total("xla_compile_s") - c0[0],
                         int(metrics.total("xla_compiles") - c0[1])))
        check(out)
    need(compiled[1][1] == 0,
         f"the repeat compiled {compiled[1][1]} programs")
    say(step, rows=rows, first_s=f"{times[0]:.3f}",
        repeat_s=f"{times[1]:.3f}", compile_s=f"{compiled[0][0]:.3f}",
        programs=compiled[0][1], ok=True, **extra)


def _plan_kinds(ctx, query) -> list:
    from dryad_tpu.parallel.mesh import num_partitions
    from dryad_tpu.plan.lower import lower

    graph = lower(
        [query.node], ctx.config, ctx.dictionary,
        P=num_partitions(ctx.mesh),
    )
    return [op.kind for st in graph.stages for op in st.ops]


def _key_payload(key: np.ndarray) -> np.ndarray:
    """A payload that is a function of its key (24 bits, f32-exact):
    "payload follows its key" is then checkable row by row, duplicates
    included, without an argsort of the reference."""
    mixed = key.view(np.uint32) * np.uint32(2654435761)
    return (mixed >> np.uint32(8)).astype(np.float32)


# -- step A: range-partitioned sort (samples/terasort.py) -------------------

def step_sort(ctx, rows: int, seed: int) -> None:
    rng = np.random.default_rng(seed)
    key = rng.integers(-(2**31), 2**31, rows, dtype=np.int64).astype(np.int32)
    table = {"key": key, "payload": _key_payload(key)}
    want = np.sort(key)
    q = ctx.from_arrays(table).order_by(["key"])

    def check(out):
        need(out["key"].shape == (rows,), out["key"].shape)
        need(np.array_equal(out["key"], want), "keys differ from np.sort")
        need(np.array_equal(out["payload"], _key_payload(out["key"])),
             "a payload left its key")

    _run_twice(ctx, "A_sort", rows, q.collect, check)


# -- step B: GroupBy + aggregate on the sort path ---------------------------

def step_groupby(ctx, rows: int, seed: int) -> None:
    rng = np.random.default_rng(seed + 1)
    K = 1 << 20
    # the -1 puts one negative key in the domain, which keeps the int
    # auto-dense rewrite off: this step is the sort + hash-exchange path
    k = (rng.integers(0, K, rows, dtype=np.int64) - 1).astype(np.int32)
    v = rng.standard_normal(rows, dtype=np.float32)
    q = ctx.from_arrays({"k": k, "v": v}).group_by(
        "k", {"c": ("count", None), "s": ("sum", "v")}
    )
    kinds = _plan_kinds(ctx, q)
    need("group_reduce_dense" not in kinds, kinds)
    if ctx.executor.P > 1:
        need("exchange_hash" in kinds, kinds)
    want_c = np.bincount(k + 1, minlength=K)
    want_s = np.bincount(k + 1, weights=v, minlength=K)
    sum_abs = np.bincount(k + 1, weights=np.abs(v), minlength=K)
    # f32 accumulation: one rounding per add, each relative to a
    # partial sum no larger than the group's sum of |v|
    tol = (want_c + 8) * 2.0**-23 * sum_abs + 1e-6

    def check(out):
        slot = out["k"].astype(np.int64) + 1
        need(len(np.unique(slot)) == len(slot), "a key came out twice")
        need(len(slot) == int(np.count_nonzero(want_c)),
             "groups:", len(slot), "want", int(np.count_nonzero(want_c)))
        need(np.array_equal(out["c"], want_c[slot]), "counts differ")
        err = np.abs(out["s"].astype(np.float64) - want_s[slot])
        worst = int(np.argmax(err - tol[slot]))
        need(np.all(err <= tol[slot]),
             f"sum of key {slot[worst] - 1}: err {err[worst]:.3e} > "
             f"tol {tol[slot][worst]:.3e}")

    _run_twice(ctx, "B_groupby", rows, q.collect, check,
               groups=int(np.count_nonzero(want_c)))


# -- step C: WordCount on the dense MXU path --------------------------------

def _write_corpus(path: str, ids: np.ndarray) -> None:
    """``w00000 w00001 ...`` for the given word ids, vectorised: a
    (VOCAB, 7) byte table gathered by id and written in slabs."""
    digits = (np.arange(VOCAB)[:, None] // 10 ** np.arange(4, -1, -1)) % 10
    words = np.empty((VOCAB, 7), np.uint8)
    words[:, 0] = ord("w")
    words[:, 1:6] = digits + ord("0")
    words[:, 6] = ord(" ")
    with open(path, "wb") as fh:
        for lo in range(0, len(ids), 1 << 24):
            fh.write(words[ids[lo : lo + (1 << 24)]].tobytes())


def step_wordcount(ctx, rows: int, seed: int) -> None:
    rng = np.random.default_rng(seed + 2)
    # Zipf over the vocabulary, as words in a corpus are
    cdf = np.cumsum(1.0 / np.arange(1, VOCAB + 1))
    cdf /= cdf[-1]
    ids = np.searchsorted(cdf, rng.random(rows, dtype=np.float32))
    ids = np.minimum(ids, VOCAB - 1).astype(np.int32)
    want = np.bincount(ids, minlength=VOCAB)
    want_top = np.sort(want)[::-1][:TOP_WORDS]

    with tempfile.TemporaryDirectory(prefix="dryad_smoke_") as tmp:
        path = os.path.join(tmp, "corpus.txt")
        _write_corpus(path, ids)
        del ids

        src: list = []  # the from_text query, made inside the first run

        def run():
            if not src:
                # inside the clock: tokenizing IS WordCount's ingest
                src.append(ctx.from_text(path, column="word"))
            q = (
                src[0].group_by("word", {"count": ("count", None)})
                .order_by([("count", True)])
                .take(TOP_WORDS)
            )
            kinds = _plan_kinds(ctx, q)
            need("string_code" in kinds, kinds)
            need("group_reduce_dense" in kinds, kinds)
            need("exchange_hash" not in kinds, kinds)
            return q.collect()

        def check(out):
            need(len(out["word"]) == TOP_WORDS, len(out["word"]))
            need(np.array_equal(out["count"], want_top),
                 out["count"], want_top)
            for w, c in zip(out["word"], out["count"]):
                need(want[int(str(w)[1:])] == c, w, c)

        _run_twice(ctx, "C_wordcount", rows, run, check, vocab=VOCAB)


# -- step D: Join + OrderBy --------------------------------------------------

def step_join(ctx, rows: int, seed: int) -> None:
    rng = np.random.default_rng(seed + 3)
    key = rng.integers(0, DIM_ROWS, rows, dtype=np.int64).astype(np.int32)
    payload = rng.standard_normal(rows, dtype=np.float32)
    dim_key = rng.permutation(DIM_ROWS).astype(np.int32)
    weight = rng.standard_normal(DIM_ROWS, dtype=np.float32)
    fact = ctx.from_arrays({"key": key, "payload": payload})
    dim = ctx.from_arrays({"dkey": dim_key, "weight": weight})
    q = (
        fact.join(dim, "key", "dkey", expansion=JOIN_EXPANSION,
                  strategy="auto")
        .order_by([("payload", True)])
        .take(TOP_JOIN)
    )
    # the NumPy gather: weight of dimension row `key`, top rows by payload
    by_key = np.empty(DIM_ROWS, np.float32)
    by_key[dim_key] = weight
    top = np.argpartition(-payload, TOP_JOIN)[:TOP_JOIN]
    top = top[np.argsort(-payload[top], kind="stable")]
    need(len(np.unique(payload[top])) == TOP_JOIN, "seed gives a tie")

    def check(out):
        need(np.array_equal(out["payload"], payload[top]), "top rows differ")
        need(np.array_equal(out["key"], key[top]), "keys differ")
        need(np.array_equal(out["weight"], by_key[key[top]]),
             "joined weights differ from the gather")

    _run_twice(ctx, "D_join", rows, q.collect, check, dim_rows=DIM_ROWS)


# -- step E: GroupBy through a combiner the user writes, Zipf keys ------------

def _skew_job():
    """``benchmarks/jobs/groupby_skew.py``, by path: the cell's query,
    table and reference are not copied here."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmarks", "jobs", "groupby_skew.py")
    spec = importlib.util.spec_from_file_location("smoke_groupby_skew", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def step_decomposable(ctx, rows: int, seed: int) -> None:
    job = _skew_job()
    P = ctx.executor.P
    params = {"rows": rows, "groups": max(1 << 6, rows >> 4), "zipf_theta": 0.99,
              "partitions": P}
    table = job.make_table(np.random.default_rng(seed + 4), params, None, 0)
    q = job.bind(ctx, table, params)
    kinds = _plan_kinds(ctx, q)
    need(kinds.count("group_combine") == 2 and "group_reduce" not in kinds, kinds)
    mark = [len(ctx.events.events())]

    def check(out):
        for name, (value, limit) in job.compare(table, out, params).items():
            need(value <= limit, f"{name}: {value} over {limit}")
        # what the job's exchange read back with its overflow flag
        events = ctx.events.events()[mark[0]:]
        mark[0] += len(events)
        need(not [e for e in events if e["kind"] == "stage_overflow"],
             "an exchange of combined rows overflowed at the default slack")
        seen = [e for e in events if e["kind"] == "exchange_observed"]
        need(len(seen) == int(P > 1), seen)
        for e in seen:
            need(e["combine_rows_in"] == rows and sum(e["recv_rows"]) ==
                 e["combine_rows_out"] < rows, e)
            say("E_exchange", combine_rows_in=e["combine_rows_in"],
                combine_rows_out=e["combine_rows_out"],
                recv_rows="/".join(map(str, e["recv_rows"])), boost=e["boost"])

    _run_twice(ctx, "E_decomposable", rows, q.collect, check,
               groups=int(np.count_nonzero(table["want"]["count"])),
               hottest=int(table["want"]["count"].max()))


STEPS = (
    ("A", step_sort),
    ("B", step_groupby),
    ("C", step_wordcount),
    ("D", step_join),
    ("E", step_decomposable),
)


# -- TPU-only assertions ------------------------------------------------------

@contextlib.contextmanager
def _compiled_texts(texts: list):
    """Record the compiled text of every stage program the executor
    builds inside the block (``jit(...).lower(args).compile().as_text()``
    on first call; the persistent cache makes jit's own compile of the
    same program a cache read)."""
    from dryad_tpu.exec import executor as EX

    class Recording:
        def __init__(self, jitted):
            self.jitted, self.seen = jitted, False

        def __call__(self, *args):
            if not self.seen:
                self.seen = True
                texts.append(self.jitted.lower(*args).compile().as_text())
            return self.jitted(*args)

    saved = EX.compile_stage, EX.compile_fused
    EX.compile_stage = lambda mesh, fn: Recording(saved[0](mesh, fn))
    EX.compile_fused = lambda mesh, fn: Recording(saved[1](mesh, fn))
    try:
        yield
    finally:
        EX.compile_stage, EX.compile_fused = saved


def _memory_rows(devices) -> list:
    keys = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
    return [
        {"id": d.id,
         **{k: int((d.memory_stats() or {}).get(k, 0)) for k in keys}}
        for d in devices
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--log2-rows-per-chip", type=int, default=LOG2_ROWS_PER_CHIP,
        help="rows per chip in every step, as a power of two "
             f"(default {LOG2_ROWS_PER_CHIP}; smaller is for debugging)",
    )
    ap.add_argument("--steps", default="ABCDE",
                    help="which steps to run (default ABCDE)")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; jax found platform={platform!r} "
            f"({len(devices)} x {devices[0].device_kind}).  No fallback.",
            file=sys.stderr,
        )
        return 1
    chips = len(devices)

    import jaxlib

    from dryad_tpu import DryadContext
    from dryad_tpu.runtime import bindings as RB
    from dryad_tpu.utils.compile_cache import enable_compile_cache

    cache_dir, cache_from = enable_compile_cache()
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - version print only
        libtpu = "unknown"
    say("preamble", platform=platform,
        device_kind=repr(devices[0].device_kind), chips=chips, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, compile_cache=cache_dir, compile_cache_from=cache_from)

    # The native library is built HERE, from the committed source; the
    # Python tokenizer twin would simply time out at this size.
    err = RB.build_native(force=True)
    if err is not None:
        print(f"chip_smoke: native build failed: {err}", file=sys.stderr)
        return 1
    need(RB.native_available(), "native library built but did not load")
    say("preamble", native_available=True, native_built_this_run=True)

    n = args.log2_rows_per_chip
    ctx = DryadContext()
    need(ctx.executor.P == chips, ctx.executor.P, chips)

    for name, fn in STEPS:
        if name not in args.steps:
            continue
        rows = (1 << n) * chips
        if name == "E":
            rows >>= 2  # the cell's rows a chip at the default size
        if name == "A":
            # per-device memory right after the ingest: job_start is
            # emitted once the inputs are bound and before any stage runs
            snap: dict = {}

            def tap(ev):
                if not snap and ev.get("kind") == "job_start":
                    snap["mem"] = _memory_rows(devices)

            ctx.events.add_tap(tap)
            fn(ctx, rows, SEED)
            ctx.events.remove_tap(tap)
            for row in snap["mem"]:
                say("A_ingest_memory", **row)
            peaks = [r["peak_bytes_in_use"] for r in snap["mem"]]
            need(min(peaks) > 0, "memory_stats() reported nothing")
            need(peaks[0] <= 1.5 * min(peaks),
                 f"device 0 peaked at {peaks[0]} after ingest, others at "
                 f"{peaks[1:]}: the table was staged on the default device")
            sample = ctx.telemetry.sample()
            say("telemetry", **{k: v for k, v in sample.items()
                                if k not in ("mono", "probes")})
            need(sample["source"] == "device", sample)
        elif name == "C":
            texts: list = []
            with _compiled_texts(texts):
                fn(ctx, rows, SEED)
            mosaic = sum("tpu_custom_call" in t for t in texts)
            say("C_kernel", compiled_programs=len(texts),
                with_tpu_custom_call=mosaic)
            need(mosaic >= 1,
                 "no compiled stage of step C holds the Mosaic custom "
                 "call: the Pallas kernel did not run")
        else:
            fn(ctx, rows, SEED)
        for row in _memory_rows(devices):
            say(f"{name}_memory", **row)

    if chips >= 4 and chips % 2 == 0 and "B" in args.steps:
        # The 2 x (chips/2) hybrid mesh, once, on real devices — last,
        # so that everything above has printed its result first.  On
        # the four-chip v5e host this query ended the process with
        # SIGSEGV inside the TPU runtime (PR 21's run; PERF.md Open
        # questions): the smoke exits non-zero there until that is
        # repaired, which is the truth about that configuration.
        hybrid = DryadContext(dcn_slices=2)
        need(tuple(hybrid.mesh.shape.values()) == (2, chips // 2))
        say("B_hybrid", mesh="x".join(map(str, hybrid.mesh.shape.values())))
        step_groupby(hybrid, 1 << n, SEED)

    say("done", wall_s=f"{time.perf_counter() - t_start:.1f}")
    print(json.dumps({
        "ok": True,
        "device": {"platform": platform,
                   "kind": devices[0].device_kind, "count": chips},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
