"""On-chip probe: fused one-scan + one-scatter sort-path group reduce
(``ops/segmented.py group_reduce_fused``) vs the round-4 default
(per-agg segment ops).  Decides whether DRYAD_TPU_SORT_FUSED becomes
the default (ROADMAP S3).

Run as the only process on the chip: ``python probe_fused.py``.
"""

import json
import sys
import time

import numpy as np


def log(m):
    print(f"[fused] {m}", file=sys.stderr, flush=True)


ITERS = 8


def main():
    import jax
    import jax.numpy as jnp

    from dryad_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # re-runs skip compiles

    from dryad_tpu.columnar.batch import ColumnBatch
    from dryad_tpu.ops.segmented import (
        AggSpec,
        group_reduce,
        group_reduce_fused,
    )

    d = jax.devices()[0]
    log(f"device={d.device_kind} platform={d.platform}")
    n = 4 * 1024 * 1024
    rng = np.random.default_rng(11)
    data = {
        "k": jnp.asarray(rng.integers(0, 4096, n).astype(np.uint32)),
        "v": jnp.asarray(rng.standard_normal(n).astype(np.float32)),
        "i": jnp.asarray(rng.integers(-99, 99, n).astype(np.int32)),
    }
    batch = ColumnBatch(data, jnp.ones((n,), jnp.bool_))

    shapes = {
        # the bench shape (group_reduce_rows_per_sec)
        "sum_count": [AggSpec("sum", "v", "s"),
                      AggSpec("count", None, "c")],
        # wider: the per-output-column floor shows here
        "wide4": [AggSpec("sum", "v", "s"), AggSpec("count", None, "c"),
                  AggSpec("min", "i", "mn"), AggSpec("max", "i", "mx")],
    }
    results = {}
    for sname, aggs in shapes.items():
        for impl_name, impl in (
            ("default", group_reduce), ("fused", group_reduce_fused)
        ):
            @jax.jit
            def run(b, impl=impl, aggs=aggs):
                def body(i, acc):
                    shifted = ColumnBatch(
                        {**b.data, "k": b.data["k"] ^ i.astype(jnp.uint32)},
                        b.valid,
                    )
                    out = impl(shifted, ["k"], aggs)
                    return acc + out.data["s"][0].astype(jnp.float32)

                return jax.lax.fori_loop(0, ITERS, body, jnp.float32(0.0))

            log(f"{sname}/{impl_name}: compiling...")
            t0 = time.perf_counter()
            float(run(batch))
            compile_s = time.perf_counter() - t0
            reps = []
            for _ in range(3):
                t1 = time.perf_counter()
                float(run(batch))
                reps.append(time.perf_counter() - t1)
            per = min(reps) / ITERS
            rate = n / per
            results[f"{sname}/{impl_name}"] = round(rate, 1)
            log(f"{sname}/{impl_name}: {per*1e3:.2f} ms/iter -> "
                f"{rate:.3e} rows/s (compile {compile_s:.1f}s)")
    print(json.dumps({"probe": "fused_sortpath", "n": n,
                      "rows_per_sec": results}))


if __name__ == "__main__":
    main()
