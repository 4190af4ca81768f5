"""On-chip probe: carry value columns THROUGH lax.sort as variadic
operands vs sort an index and gather columns afterwards (the current
``sort_order`` + ``take`` pattern).  Decides the `_segment_layout`
rewrite (round-4 sort-path target)."""
import sys
import time

import numpy as np


def log(m):
    print(f"[sortops] {m}", file=sys.stderr, flush=True)


ITERS = 8


def main():
    import jax
    import jax.numpy as jnp

    d = jax.devices()[0]
    log(f"device={d.device_kind} platform={d.platform}")
    n = 4 * 1024 * 1024
    rng = np.random.default_rng(11)
    k = jnp.asarray(rng.integers(0, 1 << 16, n).astype(np.uint32))
    v1 = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    v2 = jnp.asarray(rng.integers(0, 99, n).astype(np.int32))
    idx = jnp.arange(n, dtype=jnp.int32)

    def sort2(k, v1, v2):
        r = jax.lax.sort((k, idx), num_keys=1, is_stable=True)
        return r[0][0].astype(jnp.float32)

    def sort_idx_gather2(k, v1, v2):
        r = jax.lax.sort((k, idx), num_keys=1, is_stable=True)
        order = r[1]
        a, b = v1[order], v2[order]
        return a[0] + b[0].astype(jnp.float32)

    def sort_carry2(k, v1, v2):
        r = jax.lax.sort((k, v1, v2), num_keys=1, is_stable=True)
        return r[1][0] + r[2][0].astype(jnp.float32)

    def sort_carry2_idx(k, v1, v2):
        r = jax.lax.sort((k, v1, v2, idx), num_keys=1, is_stable=True)
        return r[1][0] + r[2][0].astype(jnp.float32)

    for name, fn in [
        ("bare_sort_key_idx", sort2),
        ("sort_idx_then_gather2", sort_idx_gather2),
        ("sort_carrying_2vals", sort_carry2),
        ("sort_carrying_2vals_idx", sort_carry2_idx),
    ]:
        log(f"{name}: compiling...")

        @jax.jit
        def run(k, v1, v2, fn=fn):
            def body(i, acc):
                return acc + fn(k ^ i, v1, v2)

            return jax.lax.fori_loop(0, ITERS, body, jnp.float32(0.0))

        t0 = time.perf_counter()
        float(run(k, v1, v2))
        compile_s = time.perf_counter() - t0
        reps = []
        for _ in range(3):
            t1 = time.perf_counter()
            float(run(k, v1, v2))
            reps.append(time.perf_counter() - t1)
        per = min(reps) / ITERS
        log(
            f"{name}: {per*1e3:.2f} ms/iter -> {n/per:.3e} rows/s"
            f" (compile {compile_s:.1f}s)"
        )


if __name__ == "__main__":
    main()
