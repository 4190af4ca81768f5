"""On-chip micro-probe for the sort-path segmented-reduce rewrite
(round-4 sort-path optimization target).

Compares, at n=4M sorted-keys shape, the CURRENT post-sort reduction
(scatter-based ``segment_sum`` per agg) against the CANDIDATE
(one shared unique-index scatter of row positions -> count by
adjacent difference; sum by ``cumsum`` + one gather at segment ends),
plus the raw primitive costs (cumsum, gather, scatters) so the
decision constant is measured, not guessed.  fori_loop-amortized with
a scalar readback (probe_perf.py methodology).
"""
import sys
import time

import numpy as np


def log(m):
    print(f"[segprobe] {m}", file=sys.stderr, flush=True)


ITERS = 16


def main():
    import jax
    import jax.numpy as jnp

    d = jax.devices()[0]
    log(f"device={d.device_kind} platform={d.platform}")
    n = 4 * 1024 * 1024
    rng = np.random.default_rng(7)
    # Sorted keys with ~64k segments (the post-sort layout).
    keys = np.sort(rng.integers(0, 1 << 16, n).astype(np.int32))
    vals = rng.standard_normal(n).astype(np.float32)
    k = jnp.asarray(keys)
    v = jnp.asarray(vals)
    valid = jnp.ones((n,), jnp.bool_)
    cap = n

    def layout(k, valid):
        eq = jnp.concatenate(
            [jnp.array([False]), k[1:] == k[:-1]])
        start = valid & ~eq
        seg_id = jnp.cumsum(start.astype(jnp.int32)) - 1
        seg = jnp.where(valid, seg_id, cap)
        return start, seg

    def current(k, v, valid):
        start, seg = layout(k, valid)
        cnt = jax.ops.segment_sum(jnp.ones((cap,), jnp.int32), seg, cap + 1)[:cap]
        s = jax.ops.segment_sum(v, seg, cap + 1)[:cap]
        return jnp.sum(cnt) + jnp.sum(s)

    def candidate(k, v, valid):
        start, seg = layout(k, valid)
        nvalid = jnp.sum(valid.astype(jnp.int32))
        # Same formulation as the shipped kernel (ops/segmented.py):
        # out-of-range sentinel cap+2 so non-start rows are genuinely
        # dropped, and NO unique_indices promise.
        idx = jnp.where(start, seg, cap + 2)
        start_pos = (
            jnp.full((cap + 2,), nvalid, jnp.int32)
            .at[idx].set(jnp.arange(cap, dtype=jnp.int32),
                         mode="drop")[: cap + 1]
        )
        cnt = start_pos[1:] - start_pos[:cap]
        csum = jnp.cumsum(jnp.where(valid, v, 0.0))
        end_pos = jnp.clip(start_pos[1:] - 1, 0, cap - 1)
        pref = csum[end_pos]
        s = jnp.concatenate([pref[:1], pref[1:] - pref[:-1]])
        s = jnp.where(cnt > 0, s, 0.0)
        return jnp.sum(cnt) + jnp.sum(s)

    def prim_cumsum(k, v, valid):
        return jnp.cumsum(v)[-1]

    def prim_scan_flagged(k, v, valid):
        start, _ = layout(k, valid)

        def comb(a, b):
            fa, va = a
            fb, vb = b
            return fa | fb, jnp.where(fb, vb, va + vb)

        _, s = jax.lax.associative_scan(comb, (start, v))
        return s[-1]

    cases = [
        ("current_count_sum", current),
        ("candidate_count_sum", candidate),
        ("prim_cumsum", prim_cumsum),
        ("prim_flagged_scan", prim_scan_flagged),
    ]
    import os
    only = os.environ.get("SEGPROBE_ONLY")
    for name, fn in cases:
        if only and only not in name:
            continue
        log(f"{name}: tracing/compiling...")

        @jax.jit
        def run(k, v, valid, fn=fn):
            def body(i, acc):
                return acc + fn(k ^ (i * 0), v, valid)

            return jax.lax.fori_loop(0, ITERS, body, jnp.float32(0.0))

        t0 = time.perf_counter()
        r = float(run(k, v, valid))
        compile_s = time.perf_counter() - t0
        log(f"{name}: compile+first {compile_s:.1f}s")
        reps = []
        for _ in range(3):
            t1 = time.perf_counter()
            float(run(k, v, valid))
            reps.append(time.perf_counter() - t1)
        per = min(reps) / ITERS
        log(
            f"{name}: {per*1e3:.2f} ms/iter -> {n/per:.3e} rows/s"
            f" (compile {compile_s:.1f}s, result {r:.3e})"
        )


if __name__ == "__main__":
    main()
