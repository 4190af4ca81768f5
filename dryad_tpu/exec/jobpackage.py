"""Job packages — the serialized submission artifact.

The reference ships a job as staged resources: the generated vertex DLL,
the XML query plan, and a serialized object store of client-side objects
captured by lambdas (``LinqToDryad/DryadLinqObjectStore.cs:173``,
resource staging ``DryadLinqQueryGen.cs:950-955``).  The TPU-native
equivalent: the logical plan IS Python objects, so a job package is one
pickle blob holding the node DAG, the input bindings (host tables /
store partitions, as ``exec.inputs`` types them), the string
dictionary, and the config.  A remote
driver process (or a ControlPlane worker told the package path over the
mailbox) loads and executes it against its own mesh.

User functions (including lambdas and ``__main__``-level defs) ship BY
VALUE via cloudpickle when it is available — the analog of the
reference compiling lambdas into the shipped vertex DLL
(``DryadLinqCodeGen.cs:1910``).  Without cloudpickle the stdlib pickler
applies and functions must live in a module importable on the worker.
"""

from __future__ import annotations

import pickle
from typing import Any, Dict, Optional

from dryad_tpu.exec.inputs import Binding
from dryad_tpu.plan.nodes import fresh_id, walk

try:
    import cloudpickle as _pickler
except ImportError:  # pragma: no cover - cloudpickle present in-tree
    _pickler = pickle

PACKAGE_VERSION = 2  # 2: bindings are exec.inputs types, not tuples


def pack_query(
    query, path: str, binding_overrides: Optional[Dict[int, Binding]] = None
) -> Dict[str, Any]:
    """Serialize a lazy Query (plan + reachable input bindings +
    dictionary + config) to ``path``.  Returns the manifest summary.

    ``binding_overrides``: node id -> replacement binding shipped in
    place of the context's (the driver-routed ``RoutedTable`` layouts
    of co-partitioned vertex submissions) — the live context's
    bindings stay untouched."""
    ctx = query.ctx
    nodes = walk([query.node])
    bindings: Dict[int, Binding] = {}
    overrides = binding_overrides or {}
    for n in nodes:
        binding = overrides.get(n.id) or ctx.inputs.get(n.id)
        if binding is not None:
            bindings[n.id] = binding.packed()
    blob = {
        "version": PACKAGE_VERSION,
        "node": query.node,
        "bindings": bindings,
        "dictionary": dict(ctx.dictionary._map),
        "config": ctx.config,
    }
    with open(path, "wb") as fh:
        _pickler.dump(blob, fh, protocol=pickle.HIGHEST_PROTOCOL)
    return {
        "version": PACKAGE_VERSION,
        "nodes": len(nodes),
        "bindings": len(bindings),
        "dict_entries": len(ctx.dictionary._map),
    }


def load_query(path: str, ctx=None, mesh=None):
    """Load a job package into a (possibly provided) context and return
    the lazy Query, NOT yet executed.  ``mesh`` lets a worker process run
    the plan over a specific (e.g. global multi-process) device mesh;
    ``ctx`` defaults to a fresh DryadContext built from the packaged
    config."""
    from dryad_tpu.api.context import DryadContext
    from dryad_tpu.api.query import Query

    if ctx is not None and mesh is not None:
        raise ValueError(
            "pass either ctx or mesh, not both (a provided ctx already "
            "owns its mesh)"
        )
    with open(path, "rb") as fh:
        blob = pickle.load(fh)
    if blob.get("version") != PACKAGE_VERSION:
        raise ValueError(f"unsupported package version {blob.get('version')}")
    if ctx is None:
        ctx = DryadContext(config=blob["config"], mesh=mesh)
    ctx.dictionary._map.update(blob["dictionary"])
    # Re-key the loaded DAG onto THIS process's node-id counter.  Node
    # ids are process-local (plan.nodes._ids), and everything —
    # walk/consumers dedup, lowering cursors, binding lookups — keys on
    # them; a loaded DAG carrying the packer's ids collides with any
    # node built locally (e.g. the topk node _rewrite_topk creates at
    # lower time gets a fresh LOCAL id, which in a young process starts
    # at 0 — exactly where the packer's ids also started), and with a
    # second package from a different packer.  A collision is silent:
    # walk drops one of the twins and the plan lowers wrong or not at
    # all.
    remap: Dict[int, int] = {}
    for n in walk([blob["node"]]):
        remap[n.id] = n.id = fresh_id()
    ctx.inputs.restore(
        {remap[i]: b for i, b in blob["bindings"].items() if i in remap}
    )
    return Query(ctx, blob["node"])


def run_package(path: str, ctx=None):
    """Load a job package and execute it, returning the host table —
    the entry point a worker process calls after learning the package
    path from the control plane."""
    return load_query(path, ctx=ctx).collect()
