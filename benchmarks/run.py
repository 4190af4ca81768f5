#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process; it fails (no CPU fallback) when jax finds no TPU or
fewer chips than the cell asks for.  One ``DryadContext()`` with the
default ``DryadConfig()`` over the cell's chips; set-up (a pool of host
tables and their NumPy answers from ``--seed``, one warm-up pair); then
a measured window of *pairs*: a fresh job (new host table -> answer on
the host) and a requery (the same query again, table resident), both
the user's ``Query.collect()``, one client, closed loop, back to back.
A pair starts only while less than ``--seconds`` have passed and runs
to its end: nothing is cut and nothing is divided by ``--seconds``.
Every answer is kept and compared with the reference once the window
has closed, so nothing of the harness runs between jobs.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with --trace 1).
See benchmarks/README.md.
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class NoChips(RuntimeError):
    """jax found no TPU, or fewer chips than the cell asks for."""


def say(tag: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[bench] {tag} {body}", flush=True)


def load_module(directory: str, name: str):
    """The file ``<directory>/<name>.py`` as a module, found by name."""
    path = os.path.join(HERE, directory, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{directory}_{name.replace('-', '_').replace('.', '_')}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: str
    params: dict
    job: object
    end_to_end: list
    per_layer: list
    peaks: dict = dataclasses.field(default_factory=dict)  # of this device kind

    @property
    def pair_rows(self) -> int:
        """Input rows of one pair: both jobs read every input table."""
        return 2 * self.job.input_rows(self.params)


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """Everything about one cell, found by the names in BENCHMARK.json."""
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r}; have {sorted(cells)}")
    entry = cells[workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(root, config_entry["file"])) as fh:
        config = json.load(fh)
    with open(os.path.join(HERE, "traffic", entry["traffic"] + ".json")) as fh:
        params = json.load(fh)

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(
        name=workload, chips=int(entry["chips"]), config=config,
        traffic=entry["traffic"], params=params,
        job=load_module("jobs", params["job"]),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)],
    )


def require_chips(chips: int):
    """The devices jax found, which must be TPU chips and enough of
    them.  Raises :class:`NoChips` otherwise: no fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChips(
            f"needs a TPU; jax found platform={devices[0].platform!r} "
            f"({len(devices)} x {devices[0].device_kind})")
    if len(devices) < chips:
        raise NoChips(f"the cell asks for {chips} chips; jax found {len(devices)}")
    return devices


def load_peaks(device_kind: str) -> dict:
    """This device's published peaks; a kind that ``peaks.json`` does
    not hold is an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as fh:
        peaks = json.load(fh)
    if device_kind not in peaks:
        raise KeyError(f"peaks.json has no entry for device kind {device_kind!r}")
    return peaks[device_kind]


def make_pool(cell: Cell, seed: int, workdir: str) -> list:
    """``pool`` host tables with their reference answers, from the seed."""
    import numpy as np

    return [
        cell.job.make_table(np.random.default_rng([seed, i]), cell.params,
                            workdir, i)
        for i in range(int(cell.params.get("pool", 4)))
    ]


def keep_worst(worst: dict, checks: dict) -> None:
    """Fold ``{number: (value, limit)}`` into the worst seen so far."""
    for name, (value, limit) in checks.items():
        worst[name] = (max(worst.get(name, (value, limit))[0], value), limit)


class StallWatch:
    """Marks a job that took far longer than its kind ever has: over
    ``FACTOR`` times the fastest job of the kind so far plus
    ``ROOM_S``.  Its pair's line then says ``stalled=<kind>`` and the
    window's line counts such jobs.  Now and then a job stalls for
    some 5 s (PERF.md, Findings, PR 23) and moves a window's mean rate
    by a tenth and its medians by nothing; the lines say in which run
    and job.  Arithmetic on the clocks only: nothing runs beside
    the job."""

    FACTOR, ROOM_S = 1.5, 0.25

    def __init__(self):
        self.fastest: dict = {}

    def saw(self, kind: str, took: float, rec: dict) -> None:
        fastest = self.fastest.get(kind)
        if fastest is not None and took > self.FACTOR * fastest + self.ROOM_S:
            rec["stalled"] = kind
        self.fastest[kind] = min(took, fastest or took)


def run_pair(ctx, cell: Cell, table, index: int, t_open: float, watch: StallWatch):
    """One fresh job and one requery through the user's calls, clocked.
    The answers stay in the record until :func:`check_pair`, after the
    clocks (and, in the window, every pair) have stopped."""
    import jax

    job, params = cell.job, cell.params
    rec = {"i": index, "attempted": 0, "failed": 0, "answers": []}
    t0 = time.perf_counter()
    rec["t"] = t0 - t_open
    try:
        rec["attempted"] += 1
        with jax.profiler.TraceAnnotation("bench:fresh"):
            query = job.bind(ctx, table, params)
            rec["answers"].append(query.collect())
        t1 = time.perf_counter()
        rec["fresh_s"] = t1 - t0
        rec["attempted"] += 1
        with jax.profiler.TraceAnnotation("bench:requery"):
            rec["answers"].append(query.collect())
        t2 = time.perf_counter()
        rec["requery_s"] = t2 - t1
        rec["pair_s"] = t2 - t0
        watch.saw("fresh", rec["fresh_s"], rec)
        watch.saw("requery", rec["requery_s"], rec)
    except Exception:  # noqa: BLE001 - a job that raises is a failed job
        traceback.print_exc()
        rec["failed"] += 1
    rec["end"] = rec["t"] + rec.get("pair_s", 0.0)
    fields = {k: f"{rec[k]:.6f}" for k in ("t", "fresh_s", "requery_s", "pair_s")
              if k in rec}
    say("pair", i=index, **fields, raised=rec["failed"],
        **({"stalled": rec["stalled"]} if "stalled" in rec else {}))
    return rec


def check_pair(cell: Cell, table, rec: dict, worst: dict) -> None:
    """Every answer of the pair against the table's reference; a job
    whose answer differs counts in ``failed``.  Frees the answers."""
    for answer in rec.pop("answers"):
        checks = cell.job.compare(table, answer, cell.params)
        if any(value > limit for value, limit in checks.values()):
            rec["failed"] += 1
        keep_worst(worst, checks)


def run_window(ctx, cell: Cell, pool, seconds: float, watch: StallWatch) -> list:
    """Pairs back to back from a pair boundary; a new pair starts only
    while less than ``seconds`` have passed, and the one in flight runs
    to its end.  Nothing else runs between pairs: the answers are
    compared once the window has closed."""
    import jax

    records = []
    t_open = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench:window"):
        while time.perf_counter() - t_open < seconds:
            i = len(records)
            records.append(run_pair(ctx, cell, pool[i % len(pool)], i, t_open, watch))
    return records


def median_of(records, key: str) -> float:
    values = [r[key] for r in records if key in r]
    if not values:
        raise RuntimeError(f"no job of the window gave a {key}")
    return statistics.median(values)


def end_to_end(cell: Cell, pairs: list, setup_s: float) -> dict:
    """The cell's end-to-end metrics: the medians of the window's fresh
    jobs and of its requeries, and the set-up."""
    values = {
        "fresh_job_s": median_of(pairs, "fresh_s"),
        "requery_s": median_of(pairs, "requery_s"),
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer(cell: Cell, trace, spans, counters) -> dict:
    """Every per-layer metric of the cell whose reader finds something
    to read (``metrics/<name>.py``: ``read(trace, spans, counters, cell)``)."""
    out = {}
    for m in cell.per_layer:
        value = load_module("metrics", m["name"]).read(trace, spans, counters, cell)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_report(devices, chips: int) -> dict:
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(cell: Cell, devices, seed: int, seconds: float, traced: bool) -> dict:
    import jax

    import trace_reduce

    from dryad_tpu import DryadContext
    from dryad_tpu.utils.compile_cache import enable_compile_cache

    # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache: a
    # fixed path inside the checkout (the path is part of the key)
    cache_dir, _ = enable_compile_cache()

    ctx = DryadContext(num_partitions_=cell.chips)
    metrics = ctx.executor.metrics
    counter_names = ("xla_compiles", "xla_compile_s", "d2h_bytes")
    workdir = tempfile.mkdtemp(prefix="dryad_bench_")
    trace_dir = os.path.join(ROOT, ".bench_out", f"trace-{cell.name}")
    watch, worst = StallWatch(), {}
    try:
        pool = make_pool(cell, seed, workdir)
        say("setup", workload=cell.name, seed=seed, chips=cell.chips,
            pool=len(pool), compile_cache=cache_dir,
            pool_ready_s=f"{time.perf_counter() - T_START:.3f}")
        warm = run_pair(ctx, cell, pool[0], -1, time.perf_counter(), watch)
        check_pair(cell, pool[0], warm, worst)
        say("warm", compiles=int(metrics.total("xla_compiles")),
            compile_s=f"{metrics.total('xla_compile_s'):.3f}")

        def on_event(ev):
            if ev.get("kind") == "xla_compile":
                say("compile_in_window", **{k: v for k, v in ev.items()
                                            if k in ("stage", "seconds", "qid")})

        ctx.events.add_tap(on_event)
        before = {n: metrics.total(n) for n in counter_names}
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        setup_s = time.perf_counter() - T_START
        try:
            pairs = run_window(ctx, cell, pool, seconds, watch)
        finally:
            if traced:
                jax.profiler.stop_trace()
        ctx.events.remove_tap(on_event)
        counters = {n: metrics.total(n) - before[n] for n in counter_names}
        t_check = time.perf_counter()
        for rec in pairs:
            check_pair(cell, pool[rec["i"] % len(pool)], rec, worst)
        check_s = time.perf_counter() - t_check
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    attempted = sum(r["attempted"] for r in pairs) + warm["attempted"]
    failed = sum(r["failed"] for r in pairs) + warm["failed"]
    for name, (value, limit) in sorted(worst.items()):
        say("check", number=name, worst=value, limit=limit,
            ok=int(value <= limit))
    done = [r for r in pairs if "pair_s" in r]
    result = {
        "correct": bool(failed == 0 and pairs and len(done) == len(pairs)),
        "attempted": attempted, "failed": failed,
        "device": device_report(devices, cell.chips),
    }
    if traced:
        cell.peaks = load_peaks(devices[0].device_kind)
        summary = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(trace_dir)))
        result["metrics"] = per_layer(cell, summary, {"pairs": pairs}, counters)
        result["device"]["busy_s"] = summary["busy_s"]
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = {
            "device_ops": trace_reduce.top(summary["op_s"]),
            "idle_gaps": trace_reduce.top(summary["gap_s"]),
        }
    else:
        result["metrics"] = end_to_end(cell, pairs, setup_s)
    open_to_end = max((r["end"] for r in done), default=0.0)
    say("window", pairs=len(pairs), seconds=seconds,
        open_to_last_end_s=f"{open_to_end:.3f}",
        mean_rows_per_s_chip=(f"{cell.pair_rows * len(done) / open_to_end / cell.chips:.1f}"
                              if done else "none"),
        stalled=sum("stalled" in r for r in pairs),
        window_compiles=int(counters["xla_compiles"]),
        d2h_bytes_a_job=int(counters["d2h_bytes"] / max(1, attempted - warm["attempted"])),
        check_s=f"{check_s:.3f}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        devices = require_chips(cell.chips)
    except NoChips as err:
        print(f"benchmarks/run.py: {err}.  No fallback.", file=sys.stderr)
        return 1
    result = run_cell(cell, devices, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
