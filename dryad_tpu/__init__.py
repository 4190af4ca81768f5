"""dryad_tpu — a TPU-native distributed dataflow framework.

A brand-new framework with the capabilities of Microsoft Research's
Dryad + DryadLINQ (reference: wycharry/Dryad), re-designed TPU-first:

- A language-integrated, lazily-evaluated dataflow/query API
  (``DryadContext`` / ``Query``) mirroring the DryadLINQ operator surface
  (reference ``LinqToDryad/DryadLinqQueryable.cs``).
- A query planner that lowers the operator DAG to *fused stages*
  (reference 3-phase planner, ``LinqToDryad/DryadLinqQueryGen.cs:236``),
  each stage compiling to a single XLA SPMD program via ``shard_map``
  over a ``jax.sharding.Mesh`` — instead of per-vertex worker processes.
- Hash/range shuffle "channels" are XLA ``all_to_all`` collectives over
  ICI (reference channel stack ``DryadVertex/VertexHost/system/channel/``).
- GroupBy combiner decomposition becomes on-device segmented reduction
  (reference ``LinqToDryad/DryadLinqDecomposition.cs``).
- Records are HBM-resident columnar batches with validity masks
  (reference row format ``LinqToDryad/DryadLinqBinaryReader.cs``).
- A graph executor with versioned stage re-execution, failure budgets,
  adaptive (sampler-driven) resharding, and an append-only job event log
  (reference GraphManager ``GraphManager/vertex/DrGraph.h:75``,
  ``DrDynamicRangeDistributor.cpp``, ``DrCalypsoReporting.cpp``).
"""

from dryad_tpu.utils.config import DryadConfig, StaticConfig
from dryad_tpu.columnar.schema import (
    BYTES,
    DECIMAL,
    ColumnType,
    Schema,
    StringDictionary,
    date,
)
from dryad_tpu.columnar.batch import ColumnBatch

from dryad_tpu.api.decomposable import Decomposable
from dryad_tpu.api.context import DryadContext
from dryad_tpu.api.query import JobHandle, Query

__version__ = "0.1.0"

__all__ = [
    "DryadConfig",
    "StaticConfig",
    "Schema",
    "BYTES",
    "DECIMAL",
    "date",
    "ColumnType",
    "StringDictionary",
    "ColumnBatch",
    "Decomposable",
    "DryadContext",
    "JobHandle",
    "Query",
    "__version__",
]
