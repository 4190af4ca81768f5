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


def test_the_run_end_compaction_compiles_small_for_the_chip(one_chip, uncached):
    """``ops/segmented.py::compact_rows`` over the ``groupby-skew-4c``
    cell's six columns (the key and five state words), 2^20 slots: the
    passes that put a fold's run-end rows in their slots are ONE loop
    body of elementwise fusions over buffers it reuses (2.5 MB of
    generated code; unrolled, a static slice a pass, 15 MB here and 21
    MB at the cell's 2^25 slots), and the program names no scatter (the
    twelve scatter-sets they replaced were 65% of the cell's device
    time; PERF.md section 6, PR 47)."""
    from dryad_tpu.ops.segmented import compact_rows

    slots = 1 << 20

    def col(dtype):
        return jax.ShapeDtypeStruct((slots,), dtype, sharding=one_chip)

    cols = {"k": col(jnp.int32), "n": col(jnp.int32), "ts": col(jnp.int32),
            "last": col(jnp.float32), "mean": col(jnp.float32), "m2": col(jnp.float32)}
    compiled = jax.jit(compact_rows).lower(col(jnp.bool_), cols).compile()
    memory = compiled.memory_analysis()
    assert memory.generated_code_size_in_bytes < 8 << 20
    # six words and the shift a slot: a copy or two, not one a pass
    assert memory.temp_size_in_bytes < 2 * 28 * slots
    text = compiled.as_text()
    assert "scatter" not in text and " gather(" not in text and " sort(" not in text


def test_the_joins_pair_slots_compile_small_for_the_chip(one_chip, uncached):
    """Everything of ``ops/join.py::hash_join`` after the probe (the
    pair slots' owners, the two calls that gather them, the exact
    match) at the ``join-topk-1c`` cell's widths: 2^23 fact rows x 2^16
    dimension rows, 10,485,760 pair slots.  The stacked gather by ``ri``
    reads a small table, and for one the TPU's compiler pads every
    slot of the result to 128 lanes: over all the slots at once that
    buffer alone is 5.4 GB (``ops/sort.py::STACK_BLOCK_SLOTS``; PERF.md
    section 6, PR 42); a block of 2^20 slots at a time it is 512 MiB."""
    from dryad_tpu.columnar.batch import ColumnBatch
    from dryad_tpu.ops import join as J

    rows, dim_rows, slots = 1 << 23, 1 << 16, 10_485_760

    def col(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    left = ColumnBatch({"key": col(rows, jnp.int32), "payload": col(rows, jnp.float32)},
                       col(rows, jnp.bool_))
    rs = ColumnBatch({"dkey": col(dim_rows, jnp.int32), "weight": col(dim_rows, jnp.float32)},
                     col(dim_rows, jnp.bool_))

    def pair_slots(left, rs, start, counts):
        li, base, pair_valid, overflow, _ = J._expand_pairs(start, counts, slots)
        lcols, rcols = J._materialize_pairs(left.data, rs.data, li, base)
        valid = J._exact_pair_match(lcols, rcols, ["key"], ["dkey"], pair_valid)
        return J._joined_columns(lcols, rcols, ["dkey"], "_r")[0], valid, overflow

    with J.slot_gather_log() as seen:
        compiled = jax.jit(pair_slots).lower(
            left, rs, col(rows, jnp.int32), col(rows, jnp.int32)).compile()
    assert seen == {"slot_gathers": 2, "stacked_words": {"li": 3, "ri": 2}}
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1 << 30  # 626 MB; 5,369 MB in one block
