"""Device operand pool — content-addressed upload cache for runtime
table operands.

The static-vs-operand split (``stringcode_runtime_tables``) moves the
string coding tables out of the compiled program and into call-time
device inputs.  Something still has to get the table CONTENT onto the
device — and a widening vocabulary produces a new table per widen, so a
naive ``device_put`` per dispatch would trade O(chunks) recompiles for
O(chunks) full re-uploads.  The pool exploits the dictionary's
append-only growth instead: within one shape-palette tier a widened
table differs from its predecessor only at the slots/rows the new
entries filled (``ops/stringcode.py`` builds subset tables in insertion
order precisely to keep this true), so the pool **scatters just the
delta** into the resident device buffer and re-uploads in full only on
a tier change or when the delta stops being small.

One pool per :class:`~dryad_tpu.exec.executor.GraphExecutor` — the
driver's and each worker's executor cache independently (the job
package ships table objects inside the plan; every process uploads its
own copy once).

Participating objects implement the small operand protocol:
``operand_signature()`` (hashable shape-palette tier — everything the
traced program bakes in), ``operand_arrays()`` (the host numpy arrays,
leading axis = scatter axis), ``operand_sha()`` (content digest), and
``operand_arity`` (len of ``operand_arrays()``).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Tuple

import numpy as np


def is_operand_capable(v: Any) -> bool:
    """True when ``v`` implements the operand protocol."""
    return (
        hasattr(v, "operand_signature")
        and hasattr(v, "operand_arrays")
        and hasattr(v, "operand_sha")
    )


class DeviceOperandPool:
    """Per-executor cache: operand tier -> resident device buffers.

    Only the LATEST content per tier stays resident (tiers are the
    power-of-two palette, so the pool holds O(log vocab) buffer sets,
    not O(widenings)); re-requesting the resident sha is free, a new
    sha on a known tier scatters the row delta, an unknown tier
    uploads in full.
    """

    def __init__(self, mesh=None, metrics=None):
        self.mesh = mesh
        self.metrics = metrics
        # tier -> (sha, host array tuple, device array tuple)
        self._tiers: Dict[Tuple, Tuple[str, Tuple, Tuple]] = {}
        # observable behavior (tests / debugging)
        self.full_uploads = 0
        self.delta_scatters = 0
        self.hits = 0
        # The serving tier multiplexes many tenants' dispatches over
        # ONE executor, and the DispatchWindow collector may fetch
        # while the driver dispatches — get() must be safe under that
        # concurrency (tier residency + counters mutate together).
        self._lock = threading.Lock()

    # -- accounting --------------------------------------------------------
    def _account(self, nbytes: int) -> None:
        if self.metrics is not None:
            # operand traffic IS host->device traffic: fold it into the
            # job-level h2d accounting and keep a specific counter too
            self.metrics.add("h2d_bytes", int(nbytes))
            self.metrics.add("operand_h2d_bytes", int(nbytes))

    def _put(self, arr: np.ndarray):
        import jax

        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            return jax.device_put(
                np.asarray(arr), NamedSharding(self.mesh, PartitionSpec())
            )
        return jax.device_put(np.asarray(arr))

    # -- the one entry point -----------------------------------------------
    def get(self, obj) -> Tuple:
        """Device arrays for ``obj`` (uploading/scattering as needed).
        Thread-safe: concurrent sessions racing one tier serialize on
        the pool lock, so residency can never interleave into a state
        where the stored sha and device buffers disagree."""
        sha = obj.operand_sha()
        host = tuple(
            np.ascontiguousarray(a) for a in obj.operand_arrays()
        )
        # Residency keys on the BUFFER layout (type + shapes/dtypes):
        # whatever else a compile signature may hold, buffers of one
        # layout match row for row, so the widen delta still scatters.
        tier = (type(obj).__name__,) + tuple(
            (a.shape, str(a.dtype)) for a in host
        )
        with self._lock:
            cur = self._tiers.get(tier)
            if cur is not None and cur[0] == sha:
                self.hits += 1
                return cur[2]
            dev: Optional[Tuple] = None
            if cur is not None:
                dev = self._scatter_delta(cur[1], cur[2], host)
            if dev is None:
                dev = tuple(self._put(a) for a in host)
                self._account(sum(a.nbytes for a in host))
                self.full_uploads += 1
            else:
                self.delta_scatters += 1
            self._tiers[tier] = (sha, host, dev)
            return dev

    def _scatter_delta(self, prev_host, prev_dev, host) -> Optional[Tuple]:
        """Update resident buffers row-wise to the new content; None
        when a full upload is cheaper (delta > half the rows) or the
        shapes diverged (tier hash collision — never expected)."""
        deltas = []
        total = 0
        for old, new in zip(prev_host, host):
            if old.shape != new.shape or old.dtype != new.dtype:
                return None
            diff = old != new
            if diff.ndim > 1:
                diff = diff.reshape(diff.shape[0], -1).any(axis=1)
            idx = np.nonzero(diff)[0]
            if len(idx) > new.shape[0] // 2:
                return None
            deltas.append(idx)
            total += len(idx)
        out = []
        nbytes = 0
        for old_dev, new, idx in zip(prev_dev, host, deltas):
            if len(idx) == 0:
                out.append(old_dev)
                continue
            vals = np.ascontiguousarray(new[idx])
            nbytes += idx.nbytes + vals.nbytes
            out.append(old_dev.at[idx].set(vals))
        self._account(nbytes)
        return tuple(out)
