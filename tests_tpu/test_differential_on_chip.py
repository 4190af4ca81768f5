"""Differential validation ON THE CHIP: multi-stage pipelines from the
fuzz grammar run on the real accelerator and diff against the NumPy
LocalDebug oracle — the reference's ``Validate.Check`` pattern
(``DryadLinqTests/Utils.cs``) executed against TPU results (round-4
weakness: the oracle had only ever checked CPU-mesh results).

Pipelines are FIXED (not random) so every chip run covers the shapes
the kernel-level tests miss: inner/left/semi joins, the full GroupJoin
selector (+ rank_limit), range-partition sort, STRING auto-dense, and
f64 total-order extremes.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))

from oracle import check  # noqa: E402
from test_fuzz_differential import _STEPS, _rand_table  # noqa: E402

from dryad_tpu import DryadContext  # noqa: E402


@pytest.fixture(scope="module")
def jaxmod():
    import jax

    assert jax.devices()[0].platform == "tpu"
    return jax


# step-lists chosen for coverage, not sampled: joins, GroupJoin
# selector forms, range sort, string/dense/f64 paths
_PIPELINES = [
    ("map_group", ["select_double", "group_by"]),
    ("range_sort_topk", ["where_pos", "order_take"]),
    ("left_join", ["left_join"]),
    ("semi_join_wide", ["semi_join", "group_wide"]),
    ("gj_selector", ["gj_selector"]),
    ("gj_topk", ["gj_topk"]),
    ("string_group", ["where_kmod", "group_str"]),
    ("f64_sort", ["order_f64"]),
    ("range_part_minmax", ["range_partition", "minmax_f64"]),
]


@pytest.mark.parametrize("name,steps", _PIPELINES,
                         ids=[n for n, _ in _PIPELINES])
def test_pipeline_on_chip_matches_oracle(jaxmod, name, steps):
    import zlib

    rng = np.random.default_rng(zlib.crc32(name.encode()))
    tbl = _rand_table(rng, 300)

    def run(ctx):
        q = ctx.from_arrays(tbl)
        for s in steps:
            q = _STEPS[s](q)
        return q.collect()

    dev = run(DryadContext())  # real chip mesh
    dbg = run(DryadContext(local_debug=True))
    try:
        check(dev, dbg)
    except AssertionError as e:
        raise AssertionError(f"chip pipeline {name} ({steps}): {e}") from e
