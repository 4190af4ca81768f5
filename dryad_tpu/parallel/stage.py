"""Stage compilation: a fused operator pipeline as one SPMD program.

A *stage* is the TPU-native vertex: where the reference runs one
generated C# method per vertex process (``DryadLinqCodeGen.cs:1910``
AddVertexMethod; fused SuperNodes ``DryadLinqQueryGen.cs:406-456``), we
trace one per-partition function and ``shard_map`` + ``jit`` it over the
mesh.  Gang scheduling (``DrCohort.h:23``) is inherent: the SPMD program
launches on every device at once.

Convention: a stage function has signature
    fn(sharded_inputs, replicated_inputs) -> (sharded_outputs, replicated_outputs)
where the sharded pytrees hold per-partition ``ColumnBatch``es / arrays
(leading axis = rows, sharded over mesh axis ``"p"``) and replicated
pytrees hold scalars/small arrays identical on every device (overflow
flags, splitters, global counts).
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P

from dryad_tpu.parallel.mesh import mesh_axes


# The stage program's name (``jit_<name>``: the profiler's ``XLA
# Modules`` line, the head of every operation's name path) is part of
# jax's compilation-cache key; the operator scopes inside
# (``exec/kernels.apply_op`` and the kernels' inner scopes) are not,
# the key strips names.  So the name says which generation of scopes a
# cached program carries: count it up here when a scope is added or
# renamed anywhere, or a stale cache hands back a program without them
# (benchmarks/TRACING.md).  ``dryad_stage``: PR 24's scopes; ``_2``:
# ``dryad.join.{probe,materialize,exact}``; ``_3``:
# ``dryad.group_combine.{layout,scan,emit}``.
PROGRAM_NAME = "dryad_stage_3"


def compile_stage(mesh: Mesh, fn: Callable[[Any, Any], Tuple[Any, Any]]):
    """Compile a per-partition stage fn into a jitted SPMD callable."""
    axes = mesh_axes(mesh)
    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(axes), P()),
        out_specs=(P(axes), P()),
        check_vma=False,
    )
    mapped.__name__ = mapped.__qualname__ = PROGRAM_NAME
    return jax.jit(mapped)


def compile_fused(mesh: Mesh, fn: Callable[[Any, Any], Tuple[Any, Any]]):
    """Compile a whole fused multi-stage REGION as one SPMD program.

    The region fn (``exec.kernels.build_fused_fn``) chains member stage
    bodies with their seam exchanges inside a single ``shard_map``, so
    the sharded inputs are the region's EXTERNAL inputs and the sharded
    outputs its exports — the same (sharded, replicated) calling
    convention as a single stage, which is what lets the executor's
    dispatch, overflow-window, and operand-pool machinery treat a
    region exactly like a stage.  One ``jit`` entry here = one compile
    key and one dispatch per region instead of per stage."""
    return compile_stage(mesh, fn)
