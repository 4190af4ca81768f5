"""Checker: the async-dispatch hot path must never block.

``sync-in-dispatch-loop``: the dispatch window (``exec/pipeline.py``
``DispatchWindow`` and the ``_AsyncDispatcher`` that wraps it in
``exec/outofcore.py``) exists so the driver thread only *dispatches*
and the collector thread only *fetches* — the one sanctioned blocking
point is the fetch closure handed to ``submit`` (it resolves to
``fetch_host`` when the collector calls it).  A synchronizing call
anywhere else in a dispatch class silently re-serializes the window:
every dispatch then waits for the previous readback, the depth knob
stops doing anything, and the per-dispatch round-trip latency comes
straight back.  Flagged primitives:

- ``<x>.block_until_ready()`` — the literal re-serializer;
- ``jax.device_get(...)`` / bare ``device_get(...)`` — forces a
  D2H transfer inline;
- ``<x>.item()`` — scalar readback, blocks on the buffer;
- ``np.asarray(...)`` / ``numpy.asarray(...)`` on a device value —
  the sneaky one: looks like a cheap view, is a blocking copy
  (``jnp.asarray`` is a trace op and stays exempt).

The rule scans every class whose name contains "dispatch" (case
insensitive), nested closures included.  As a structural-drift guard,
a real ``exec/pipeline.py`` that no longer defines ``DispatchWindow``
is itself a finding — the rule must not go silent because its anchor
moved.
"""

from __future__ import annotations

import ast
from typing import Iterator, Tuple

from dryad_tpu.analysis import astutil
from dryad_tpu.analysis.core import Checker, Finding, Project, register

PIPELINE_PATH = "dryad_tpu/exec/pipeline.py"

# attribute calls that block the calling thread on device results
_SYNC_ATTRS = ("block_until_ready", "item", "device_get")
# receivers whose .asarray is a blocking host copy (jnp's is traced)
_HOST_NP = ("np", "numpy")


def _dispatch_classes(tree: ast.Module) -> Iterator[ast.ClassDef]:
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and "dispatch" in node.name.lower():
            yield node


def _sync_calls(cls: ast.ClassDef) -> Iterator[Tuple[int, str]]:
    """(lineno, description) for every blocking primitive in the class
    body, nested defs/closures included."""
    for node in ast.walk(cls):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr in ("block_until_ready", "item"):
                yield node.lineno, f".{f.attr}() blocks on the device buffer"
            elif f.attr == "device_get":
                yield node.lineno, "device_get() forces an inline D2H copy"
            elif f.attr == "asarray":
                chain = astutil.dotted(f.value)
                if chain and chain[-1] in _HOST_NP:
                    yield (
                        node.lineno,
                        f"{chain[-1]}.asarray() is a blocking host copy",
                    )
        elif isinstance(f, ast.Name) and f.id == "device_get":
            yield node.lineno, "device_get() forces an inline D2H copy"


@register
class SyncInDispatchLoopChecker(Checker):
    rule = "sync-in-dispatch-loop"
    summary = (
        "no blocking readback primitives inside async-dispatch "
        "classes; the submitted fetch closure is the only drain site"
    )
    hint = (
        "move the readback into the fetch closure handed to "
        "DispatchWindow.submit (the collector's sanctioned blocking "
        "point), or do it after drain() on host data"
    )

    def check(self, project: Project) -> Iterator[Finding]:
        for src in project.package_files():
            classes = list(_dispatch_classes(src.tree))
            if src.rel == PIPELINE_PATH and astutil.find_class(
                src.tree, "DispatchWindow"
            ) is None:
                # structural drift: the anchor class moved or was
                # renamed — fail loudly instead of scanning nothing
                yield self.finding(
                    src.rel,
                    1,
                    "exec/pipeline.py no longer defines DispatchWindow; "
                    "sync-in-dispatch-loop has lost its anchor",
                    hint="re-point the checker at the new async "
                    "dispatch surface",
                )
            for cls in classes:
                for line, what in _sync_calls(cls):
                    yield self.finding(
                        src.rel,
                        line,
                        f"{what} inside dispatch class {cls.name}; "
                        "this re-serializes the dispatch window",
                    )
