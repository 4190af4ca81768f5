"""Observability: structured tracing + cluster-wide metrics.

The reference runs dedicated reporters (Calypso/Artemis) inside the
GraphManager and a JobBrowser GUI over them (PAPER.md "Side column:
Observability").  This package is that subsystem for the TPU-native
framework, layered over the existing ``exec.events.EventLog`` stream:

- :mod:`dryad_tpu.obs.span` — thread-safe hierarchical spans
  (monotonic clocks, context managers, parent ids; a host pass opened
  with ``account=True`` adds the process's CPU seconds) that
  serialize as ``span`` events;
- :mod:`dryad_tpu.obs.metrics` — a counter/histogram registry (rows
  and bytes per stage and partition, compile count/time, transfer
  bytes, padding waste, spill bytes) plus the :class:`JobMetrics`
  snapshot folding events into a compile/execute/stall/spill time
  attribution;
- :mod:`dryad_tpu.obs.trace` — a Chrome-trace (Perfetto) exporter
  rendering prefetch / compute / spill threads as separate tracks;
- :mod:`dryad_tpu.obs.gang` — worker->driver telemetry aggregation
  through the ControlPlane mailbox with clock-offset correction (the
  Calypso-reporter-in-GM analog);
- :mod:`dryad_tpu.obs.telemetry` — the CONTINUOUS plane: live
  resource sampling (device HBM / host RSS / shared flightrec
  probes), the rolling-window SLO metric store behind per-tenant
  p50/p95/p99, the Prometheus/JSON export surface, and the measured
  :class:`HeadroomProvider` the adaptive exchange-window and
  dispatch-depth policies consult.
"""

from dryad_tpu.obs.metrics import JobMetrics, MetricsRegistry
from dryad_tpu.obs.span import Span, Tracer
from dryad_tpu.obs.telemetry import (
    HeadroomProvider,
    ResourceMonitor,
    RollingStore,
)
from dryad_tpu.obs.trace import chrome_trace, write_chrome_trace

__all__ = [
    "HeadroomProvider",
    "JobMetrics",
    "MetricsRegistry",
    "ResourceMonitor",
    "RollingStore",
    "Span",
    "Tracer",
    "chrome_trace",
    "write_chrome_trace",
]
