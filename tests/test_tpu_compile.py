"""Compiles for the TPU without one: the chip's compiler is installed
here and compiles for a v5e that is described, not attached
(``jax.experimental.topologies``).  Nothing runs, so nothing here is a
time or a result; what the compiler refuses or drowns in is found at
no chip time.  All in this one file, the topology described inside a
fixture (only one process may hold the TPU's library; under several
workers only the one handed this file loads it)."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def uncached():
    """A compile for a described chip is written to the persistent
    cache under the chip's key and cannot be read back without one:
    keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_the_user_defined_combiners_scan_compiles_small_for_the_chip(one_chip, uncached):
    """``ops/segmented.py::segmented_scan`` under the six-channel merge
    of the ``groupby-skew-4c`` cell, 2^20 slots: a program of a few
    megabytes.  The ``lax.associative_scan`` it replaced came to 230 MB
    of generated code at this size (154 s here) and to no program at
    all at the cell's 2^23 slots (PERF.md section 6, PR 41)."""
    from dryad_tpu.ops.segmented import segmented_scan

    spec = importlib.util.spec_from_file_location(
        "bench_job_groupby_skew_tpu",
        os.path.join(ROOT, "benchmarks", "jobs", "groupby_skew.py"))
    job = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(job)
    slots = 1 << 20

    def col(dtype):
        return jax.ShapeDtypeStruct((slots,), dtype, sharding=one_chip)

    state = {"n": col(jnp.int32), "ts": col(jnp.int32), "last": col(jnp.float32),
             "mean": col(jnp.float32), "m2": col(jnp.float32)}
    compiled = jax.jit(
        lambda start, vals: segmented_scan(start, vals, job.merge)
    ).lower(col(jnp.bool_), state).compile()
    memory = compiled.memory_analysis()
    assert memory.generated_code_size_in_bytes < 32 << 20
    # the passes reuse their buffers: a few copies of the state, not one a pass
    assert memory.temp_size_in_bytes < 8 * 21 * slots
