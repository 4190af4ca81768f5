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


def test_the_64_bit_fold_compiles_small_for_the_chip_at_the_q1_cells_slots(one_chip, uncached):
    """``ops/segmented.py``'s fold of the ``tpch-q1-1c`` cell (five
    ``sum64`` channels from four 32-bit columns and one pair, two key
    columns) at the 2^26 slots SF 10 is bound at: the scan and the
    compaction are each ONE loop body whatever the slots, and the
    temporaries are a few copies of the state (ten words and the flag a
    slot), far under the chip's HBM."""
    from dryad_tpu.columnar.batch import ColumnBatch
    from dryad_tpu.ops.segmented import AggSpec, _agg_channels, _place_groups, segmented_scan

    slots = 1 << 26

    def col(dtype):
        return jax.ShapeDtypeStruct((slots,), dtype, sharding=one_chip)

    narrow = ["l_quantity", "l_extendedprice", "l_discount", "disc_price"]
    data = {"l_returnflag": col(jnp.int32), "l_linestatus": col(jnp.int32),
            **{c: col(jnp.int32) for c in narrow},
            "charge#h0": col(jnp.uint32), "charge#h1": col(jnp.uint32)}
    aggs = [AggSpec("sum64", c, f"s_{c}") for c in narrow]
    aggs += [AggSpec("sum64", "charge#h0", "s_charge"), AggSpec("count", None, "n")]
    keys = ["l_returnflag", "l_linestatus"]

    def fold(sb, start):
        vals, merge = _agg_channels(sb.data, aggs)
        assert len(vals) == 10
        return _place_groups(sb, start, keys, segmented_scan(start, vals, merge))

    compiled = jax.jit(fold).lower(
        ColumnBatch(data, col(jnp.bool_)), col(jnp.bool_)).compile()
    memory = compiled.memory_analysis()
    assert memory.generated_code_size_in_bytes < 16 << 20
    assert memory.temp_size_in_bytes < 6 << 30  # under 6 of the chip's 15.75 GiB
    text = compiled.as_text()
    assert "scatter" not in text and " gather(" not in text and " sort(" not in text


@pytest.mark.parametrize("op", ["sum64", "min64", "max64"])
def test_the_whole_column_64_bit_reduce_compiles_for_the_chip_at_2_26(one_chip, uncached, op):
    """``ops/segmented.py::pair_scalar_reduce`` over 2^26 slots (TPC-H
    Q6's shape: one exact sum of a whole column): a halving tree of 26
    elementwise levels.  The ``lax.associative_scan`` it replaced is the
    form that gave no TPU program at 2^23 slots."""
    from dryad_tpu.ops.segmented import pair_scalar_reduce

    slots = 1 << 26

    def col(dtype):
        return jax.ShapeDtypeStruct((slots,), dtype, sharding=one_chip)

    compiled = jax.jit(lambda lo, hi, valid: pair_scalar_reduce(op, lo, hi, valid)).lower(
        col(jnp.uint32), col(jnp.uint32), col(jnp.bool_)).compile()
    memory = compiled.memory_analysis()
    assert memory.generated_code_size_in_bytes < 8 << 20
    assert memory.temp_size_in_bytes < 4 * 8 * slots  # a few copies of the pair
    assert " while(" not in compiled.as_text()


@pytest.mark.slow
@pytest.mark.parametrize("slots", [None, 1 << 26], ids=["the_cells_slots", "sf10_whole"])
def test_the_q1_cells_stage_program_fits_the_chip(one_chip, uncached, monkeypatch, slots):
    """The ``tpch-q1-1c`` cell's whole stage program
    (``input+where+select+group_by+order_by``: three carried sorts, two
    folds) compiled for the chip at the slots the cell is bound at and
    at SF 10 whole's 2^26: its arguments, its answer and its temporaries
    together stay under the chip's 15.75 GiB (at SF 10 whole, 2^26 slots, 1.95 + 3.83 + 8.86 =
    14.63 GB, which ran on the chip and was set aside for its seconds
    and for this; the cell holds half).  Slow for what it is: the
    program's three carried sorts compile for minutes whatever the size
    (260 s at 2^20 slots, 452 s at 2^26 here; 281 s cold on the chip's
    host)."""
    import json

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from dryad_tpu import DryadContext
    from dryad_tpu.columnar.batch import ColumnBatch
    from dryad_tpu.exec.kernels import build_stage_fn
    from dryad_tpu.ops import pallas_bucket
    from dryad_tpu.parallel.stage import compile_stage
    from dryad_tpu.plan.lower import lower

    # the program the chip traces: its sorts carry their columns
    monkeypatch.setattr(pallas_bucket, "_on_tpu", lambda: True)
    spec = importlib.util.spec_from_file_location(
        "bench_job_tpch_q1_tpu", os.path.join(ROOT, "benchmarks", "jobs", "tpch_q1.py"))
    job = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(job)
    if slots is None:
        with open(os.path.join(ROOT, "benchmarks", "traffic", "tpch_q1.json")) as fh:
            slots = json.load(fh)["slots"]
    params = {"orders": 100, "parts": 40, "slots": slots, "delta_days": 90,
              "partitions": 1}
    table = job.make_table(np.random.default_rng([49, 0]), params, None, 0)
    ctx = DryadContext(num_partitions_=1)
    query = job.bind(ctx, table, params)
    graph = lower([query.node], ctx.config, ctx.dictionary, P=1)
    (stage,) = graph.stages
    (node,) = graph.inputs.values()
    device = next(iter(one_chip.device_set))
    mesh = Mesh(np.array([device]), ("p",))
    sharded = NamedSharding(mesh, PartitionSpec("p"))
    batch = ColumnBatch(
        {n: jax.ShapeDtypeStruct((slots,), d, sharding=sharded)
         for n, d in node.schema.device_dtypes().items()},
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=sharded))
    words = []
    fn = build_stage_fn(stage, 1, ctx.config.shuffle_slack, 1, ("p",), (1,),
                        sort_cell=words)
    memory = compile_stage(mesh, fn).lower((batch,), ()).compile().memory_analysis()
    assert words == [14]
    held = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes)
    assert memory.temp_size_in_bytes < 10 << 30 and held < int(15.75 * 2**30)
