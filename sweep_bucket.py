"""On-chip A/B sweep for the dense bucket kernel.

Measures the STACKED-PLANE Pallas kernel against the unstacked
(per-term dots) formulation and the scatter path, across the shapes
that matter: count-only (WordCount dense), count + 1 float / 1 int /
2 floats, at K = 512 / 4096 / 16384.  Emits one JSON line per config
and a summary table; each number is the 32-iteration fori_loop
amortized device time ending in a scalar readback (probe_perf.py
pattern).

Usage:  python sweep_bucket.py  (interpret=None: Pallas on TPU, the XLA
scan elsewhere — off-TPU numbers are only for a smoke run of the
harness itself).
"""
import json
import os
import sys
import time

import numpy as np


def log(m):
    print(f"[sweep] {m}", file=sys.stderr, flush=True)


ITERS = 32


def run_case(name, n, K, val_dtypes, stack, strategy=None):
    """Build fresh arrays + a fresh jitted loop (env read at trace
    time, so the stack toggle must precede tracing)."""
    import jax
    import jax.numpy as jnp

    from dryad_tpu.ops import pallas_bucket as pb

    os.environ["DRYAD_TPU_BUCKET_STACK"] = "1" if stack else "0"
    rng = np.random.default_rng(3)
    k = jnp.asarray(rng.integers(0, K, n).astype(np.int32))
    vals = []
    for dt in val_dtypes:
        if np.issubdtype(np.dtype(dt), np.integer):
            vals.append(jnp.asarray(rng.integers(-999, 999, n).astype(dt)))
        else:
            vals.append(jnp.asarray(rng.standard_normal(n).astype(dt)))
    valid = jnp.ones((n,), jnp.bool_)

    @jax.jit
    def run(k, valid, *vals):
        def body(i, acc):
            sums, cnt = pb.bucket_sum_count(
                k ^ i, list(vals), valid, K, strategy=strategy)
            s = jnp.sum(cnt)
            for x in sums:
                s = s + jnp.sum(x)
            return acc + s

        return jax.lax.fori_loop(0, ITERS, body, jnp.float32(0.0))

    t0 = time.perf_counter()
    float(run(k, valid, *vals))
    compile_s = time.perf_counter() - t0
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        float(run(k, valid, *vals))
        dt_s = time.perf_counter() - t0
        best = dt_s if best is None else min(best, dt_s)
    rows_s = n * ITERS / best
    rec = {"case": name, "K": K, "n": n, "vals": [str(np.dtype(d)) for d in val_dtypes],
           "stack": stack, "strategy": strategy or "matmul",
           "rows_per_sec": round(rows_s, 1), "best_s": round(best, 5),
           "compile_s": round(compile_s, 1)}
    print(json.dumps(rec), flush=True)
    log(f"{name}: {rows_s:.3e} rows/s (compile {compile_s:.0f}s)")
    return rec


def main():
    import jax

    d = jax.devices()[0]
    log(f"device={d} platform={d.platform}")
    n = 1 << 22 if d.platform == "tpu" else 1 << 16

    if "--rsweep" in sys.argv:
        # Count-only R-block sweep (round-4 open question:
        # count-only measured SLOWER than count+1float — suspect the
        # VMEM-derived row block).  Each case re-imports nothing; the
        # env must be set before tracing, which run_case guarantees by
        # building a fresh jitted loop per case.
        out = []
        for r_force in (0, 7808, 5888, 3840, 2048, 1024):
            name = f"count_R{r_force or 'auto'}"
            if r_force:
                os.environ["DRYAD_TPU_BUCKET_R"] = str(r_force)
            else:
                os.environ.pop("DRYAD_TPU_BUCKET_R", None)
            try:
                out.append(run_case(name, n, 4096, [], True, "matmul"))
            except Exception as e:  # noqa: BLE001
                log(f"{name} FAILED: {e}")
        os.environ.pop("DRYAD_TPU_BUCKET_R", None)
        log("--- rsweep summary ---")
        for r in out:
            log(f"{r['case']:>16}: {r['rows_per_sec']:.3e} rows/s")
        return

    cases = [
        # flagship shape first;
        # strategy is EXPLICIT — off-TPU the default resolves to
        # scatter, which would silently benchmark the wrong path.
        ("k4096_1f_stacked", n, 4096, [np.float32], True, "matmul"),
        ("k4096_1f_unstacked", n, 4096, [np.float32], False, "matmul"),
        ("k4096_count_stacked", n, 4096, [], True, "matmul"),
        ("k4096_1i_stacked", n, 4096, [np.int32], True, "matmul"),
        ("k4096_2f_stacked", n, 4096, [np.float32, np.float32], True, "matmul"),
        ("k4096_1f_scatter", n, 4096, [np.float32], True, "scatter"),
        ("k512_1f_stacked", n, 512, [np.float32], True, "matmul"),
        ("k16384_1f_stacked", n, 16384, [np.float32], True, "matmul"),
        ("k16384_1f_unstacked", n, 16384, [np.float32], False, "matmul"),
    ]
    out = []
    for c in cases:
        try:
            out.append(run_case(*c))
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"case": c[0], "error": f"{type(e).__name__}: {e}"}),
                  flush=True)
            log(f"{c[0]} FAILED: {e}")
    log("--- summary ---")
    for r in out:
        log(f"{r['case']:>22}: {r['rows_per_sec']:.3e} rows/s")


if __name__ == "__main__":
    main()
