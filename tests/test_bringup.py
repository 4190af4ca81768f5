"""Tier-1 tests for the chip bring-up PR: the dense kernel's block fold,
the compile-cache helper, and the sharded ingest edge."""

import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chip_smoke import _plan_kinds  # noqa: E402

from dryad_tpu import DryadContext  # noqa: E402


# -- dense group_by: partitions larger than one kernel call ----------------

@pytest.mark.parametrize("nparts", [1, 8])
@pytest.mark.parametrize("key", ["int", "string"])
@pytest.mark.parametrize("strategy", ["matmul", "scatter"])
def test_dense_block_fold(mesh8, monkeypatch, nparts, key, strategy):
    """With the per-call row limit patched down to 2^10, a partition of
    2500 rows folds in three blocks (the last one ragged): counts stay
    equal to np.bincount, float sums within the split-bf16 bound."""
    from dryad_tpu.exec import kernels
    from dryad_tpu.ops import pallas_bucket

    monkeypatch.setattr(kernels, "_DENSE_BLOCK_ROWS", 1 << 10)
    monkeypatch.setattr(pallas_bucket, "_default_strategy", lambda: strategy)
    rng = np.random.default_rng(nparts)
    n, K = 2500 * nparts, 300
    code = rng.integers(0, K, n).astype(np.int32)
    v = rng.standard_normal(n).astype(np.float32)
    k = code if key == "int" else np.array(
        [f"w{c:03d}" for c in code], object
    )
    ctx = DryadContext(num_partitions_=nparts)
    q = ctx.from_arrays({"k": k, "v": v}).group_by(
        "k", {"c": ("count", None), "s": ("sum", "v")}
    )
    kinds = _plan_kinds(ctx, q)
    assert "group_reduce_dense" in kinds, kinds
    out = q.collect()
    got = (
        out["k"] if key == "int"
        else np.array([int(str(w)[1:]) for w in out["k"]])
    )
    want_c = np.bincount(code, minlength=K)
    assert np.array_equal(np.sort(got), np.flatnonzero(want_c))
    assert np.array_equal(out["c"], want_c[got])
    want_s = np.bincount(code, weights=v, minlength=K)
    bound = 2.0**-16 * np.bincount(code, weights=np.abs(v), minlength=K)
    assert np.all(np.abs(out["s"] - want_s[got]) <= bound[got] + 1e-5)


@pytest.mark.slow
def test_dense_group_by_past_2_24_rows_one_device():
    """ISSUE 21's reproduction: 2^25 rows, keys in [0, 4096), through a
    default context on ONE device — the engine chose the dense path and
    then refused the partition's row count."""
    n = 1 << 25
    rng = np.random.default_rng(0)
    k = rng.integers(0, 4096, n).astype(np.int32)
    v = rng.standard_normal(n).astype(np.float32)
    out = (
        DryadContext(num_partitions_=1)
        .from_arrays({"k": k, "v": v})
        .group_by("k", {"c": ("count", None), "s": ("sum", "v")})
        .collect()
    )
    order = np.argsort(out["k"])
    assert np.array_equal(out["k"][order], np.arange(4096))
    assert np.array_equal(out["c"][order], np.bincount(k, minlength=4096))


# -- compile cache helper ---------------------------------------------------

_REPORT = (
    "import jax;"
    "seen = [];"
    "orig = jax.config.update;"
    "jax.config.update = lambda k, v: (seen.append(k), orig(k, v))[1];"
    "from dryad_tpu.utils.compile_cache import enable_compile_cache;"
    "d, origin = enable_compile_cache();"
    "print(d); print(origin); print(jax.config.jax_compilation_cache_dir);"
    "print('jax_compilation_cache_dir' in seen)"
)


def _report(env_dir, cwd):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = ROOT
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _REPORT], capture_output=True, text=True,
        timeout=120, env=env, cwd=cwd,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()


def test_compile_cache_follows_the_environment(tmp_path):
    """Variable set: jax's configured directory IS the variable's and
    no directory is set in code."""
    want = str(tmp_path / "placed-from-outside")
    directory, origin, configured, set_in_code = _report(want, ROOT)
    assert directory == configured == want
    assert origin == "JAX_COMPILATION_CACHE_DIR"
    assert set_in_code == "False"


def test_compile_cache_default_is_the_checkout(tmp_path):
    """Variable unset: <checkout>/.jax_cache, the same from two fresh
    processes wherever they start."""
    a = _report(None, ROOT)
    b = _report(None, str(tmp_path))
    assert a == b
    assert a[0] == a[2] == os.path.join(ROOT, ".jax_cache")
    assert a[1] == "default" and a[3] == "True"


# -- ingest: no full-size array on one device -------------------------------

def test_from_host_table_never_stages_on_one_device(mesh8, monkeypatch):
    """Every ingested column is placed by one sharded device_put: its
    sharding spans the mesh, and ColumnBatch.from_numpy's jnp.asarray
    (an upload to the default device) is not on the path."""
    from dryad_tpu.columnar import batch as B
    from dryad_tpu.columnar.schema import ColumnType, Schema, StringDictionary
    from dryad_tpu.parallel import distribute as D

    def boom(*a, **k):
        raise AssertionError("jnp.asarray reached from from_host_table")

    monkeypatch.setattr(B.jnp, "asarray", boom)
    n = 1000
    rng = np.random.default_rng(0)
    arrays = {
        "i": rng.integers(0, 9, n).astype(np.int32),
        "f": rng.standard_normal(n).astype(np.float32),
        "w": rng.integers(-(2**40), 2**40, n).astype(np.int64),
        "s": np.array([f"s{j % 7}" for j in range(n)], object),
    }
    schema = Schema([
        ("i", ColumnType.INT32), ("f", ColumnType.FLOAT32),
        ("w", ColumnType.INT64), ("s", ColumnType.STRING),
    ])
    b = D.from_host_table(schema, arrays, mesh8, dictionary=StringDictionary())
    assert set(b.data) == {"i", "f", "w#h0", "w#h1",
                           "s#h0", "s#h1", "s#r0", "s#r1"}
    for name, col in {**b.data, "#valid": b.valid}.items():
        assert len(col.sharding.device_set) == 8, name
        assert not col.sharding.is_fully_replicated, name
        shard_rows = {s.data.shape[0] for s in col.addressable_shards}
        assert shard_rows == {col.shape[0] // 8}, (name, shard_rows)
    monkeypatch.undo()
    got = b.to_numpy(schema, None)
    assert np.array_equal(got["i"], arrays["i"])
    assert np.array_equal(got["w"], arrays["w"])
