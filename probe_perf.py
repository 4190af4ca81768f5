"""Kernel-strategy probe: measure the group-by implementation
candidates on the current backend (the evidence behind the
sort-vs-scatter decision).

Timing forces a scalar READBACK (float(...)) per call, so the clock
stops after the device does.  Each case reports best-of-5 single calls
(dispatch included) AND a 16-iteration fori_loop amortized time
(dispatch cost /16, the device-side number that decides kernel
strategy):
  A. group_reduce (sort + segmented reduce)  -- the general path
  B. bare 2-operand lax.sort                 -- sort share of A
  C. scatter-add (segment_sum on raw keys)   -- sortless alternative
  D. dense bucket factorized matmul (XLA)    -- MXU path
  E. dense bucket Pallas kernel              -- MXU path, Pallas (TPU)

Usage:
  python probe_perf.py          # on the platform jax gives it
"""
import sys
import time

import numpy as np


def log(m):
    print(f"[probe] {m}", file=sys.stderr, flush=True)


def best_of(fn, reps=5):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts), ts


def main():
    import jax
    import jax.numpy as jnp

    from dryad_tpu.columnar.batch import ColumnBatch
    from dryad_tpu.ops.pallas_bucket import bucket_sum_count
    from dryad_tpu.ops.segmented import AggSpec, group_reduce

    d = jax.devices()[0]
    log(f"device={d} platform={d.platform}")

    for n in (1 << 20, 1 << 22):
        rng = np.random.default_rng(0)
        k = jnp.asarray(rng.integers(0, 4096, n).astype(np.int32))
        v = jnp.asarray(rng.standard_normal(n).astype(np.float32))
        valid = jnp.ones((n,), jnp.bool_)

        # ONE body per case; the single-call variant is jit(body) and
        # the amortized variant wraps the same body in a fori_loop
        # (key mixed with the iteration index to defeat CSE — i < 16
        # only flips low bits, so k ^ i stays inside [0, 4096)).
        def gr_body(k, v, valid):
            b = ColumnBatch({"k": k, "v": v}, valid)
            out = group_reduce(
                b, ["k"],
                [AggSpec("sum", "v", "s"), AggSpec("count", None, "c")],
            )
            return jnp.sum(jnp.where(out.valid, out.data["s"], 0.0))

        def scatter_body(k, v, valid):
            vv = jnp.where(valid, v, 0.0)
            s = jax.ops.segment_sum(vv, k, 4096)
            c = jax.ops.segment_sum(valid.astype(jnp.int32), k, 4096)
            return jnp.sum(s) + jnp.sum(c)

        def dense_body(interp, strat="matmul"):
            def f(k, v, valid):
                s, c = bucket_sum_count(
                    k, [v], valid, 4096, interpret=interp, strategy=strat
                )
                return jnp.sum(s[0]) + jnp.sum(c)

            return f

        @jax.jit
        def bare_sort(k, v):
            a, b = jax.lax.sort((k, v), num_keys=1)
            return a[0] + b[0]

        # F/G: permutation scatter + gather — the reorder primitives a
        # radix/counting sort would pay per pass (ops/segmented.py sort
        # replacement is viable only if one of these runs HBM-bound).
        _MIX = jnp.uint32(2654435761)

        def perm_scatter_body(k, v, valid):
            perm = (k.astype(jnp.uint32) * _MIX + jnp.uint32(12345)) % n
            out = jnp.zeros((n,), v.dtype).at[perm].set(v, mode="drop")
            return out[0] + out[n - 1]

        def perm_gather_body(k, v, valid):
            perm = (k.astype(jnp.uint32) * _MIX + jnp.uint32(12345)) % n
            out = v[perm]
            return out[0] + out[n - 1]

        def looped(body16):
            @jax.jit
            def f(k, v, valid):
                def body(i, acc):
                    return acc + body16(k ^ i, v, valid)

                return jax.lax.fori_loop(0, 16, body, jnp.float32(0.0))

            return f

        def single(body):
            jf = jax.jit(body)
            return lambda: float(jf(k, v, valid))

        cases = [
            ("A group_reduce", single(gr_body), gr_body),
            ("B bare_sort", lambda: float(bare_sort(k, v)), None),
            ("C scatter_add", single(scatter_body), scatter_body),
            ("D dense_xla", single(dense_body(False)), dense_body(False)),
            ("F perm_scatter", single(perm_scatter_body), perm_scatter_body),
            ("G perm_gather", single(perm_gather_body), perm_gather_body),
        ]
        if d.platform == "tpu":
            cases.append(
                ("E dense_pallas", single(dense_body(None)), dense_body(None))
            )
        amortized = {}
        for name, fn, body16 in cases:
            t0 = time.perf_counter()
            fn()
            log(f"n={n} {name}: compile+run {time.perf_counter()-t0:.1f}s")
            b, ts = best_of(fn)
            log(
                f"n={n} {name}: best={b*1e3:.2f}ms reps={['%.1f' % (t*1e3) for t in ts]}ms"
                f" -> {n/b:.3e} rows/s"
            )
            if body16 is None:
                continue
            lf = looped(body16)
            float(lf(k, v, valid))  # compile
            lb, _ = best_of(lambda: float(lf(k, v, valid)), reps=3)
            rows_s = 16 * n / lb
            amortized[name.split()[0]] = rows_s
            log(
                f"n={n} {name}: amortized16 {lb/16*1e3:.2f}ms/iter"
                f" -> {rows_s:.3e} rows/s"
            )
        # The bucket-strategy decision (ops/pallas_bucket._default_strategy
        # and the scatter-vs-sort question of ops/segmented.py): compare
        # the MXU matmul path against the scatter-add on THIS backend.
        mxu = amortized.get("E", amortized.get("D", 0.0))
        scat = amortized.get("C", 0.0)
        if mxu and scat:
            rec = "scatter" if scat > mxu else "matmul"
            import json

            plat_key = d.platform
            record = {
                "probe": "bucket_strategy", "n": n,
                "platform": plat_key,
                "matmul_rows_s": round(mxu, 1),
                "scatter_rows_s": round(scat, 1),
                "recommend": rec,
                "env": f"DRYAD_TPU_BUCKET_STRATEGY={rec}",
            }
            print(json.dumps(record), flush=True)
            # Persist so ops/pallas_bucket._default_strategy picks the
            # measured winner up automatically (env still overrides).
            import os

            out_path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "PROBE_TPU.json"
            )
            try:
                existing = {}
                if os.path.exists(out_path):
                    try:
                        with open(out_path) as fh:
                            existing = json.load(fh)
                    except ValueError:
                        existing = {}  # truncated prior write: start over
                existing[plat_key] = record
                tmp = out_path + ".tmp"
                with open(tmp, "w") as fh:
                    json.dump(existing, fh, indent=1)
                os.replace(tmp, out_path)  # atomic: no torn artifact
                log(f"wrote {out_path}")
            except OSError as e:
                log(f"could not write {out_path}: {e}")
    log("done")


if __name__ == "__main__":
    main()
