"""Observability for the serving runtime (docs/observability.md).

Three layers, lock-disciplined and needing nothing beyond JAX:

* :mod:`repro.obs.trace` — per-request span tracing: a sampled trace
  context rides each submitted request through the full serving path
  (admission -> queue -> batch formation -> compile|execute ->
  device_wait -> ack) and lands in a bounded ring.  The per-stage spans
  are *contiguous by construction*, so they sum to the request's
  end-to-end latency — the repo's ground-truth latency budget
  (asserted against the load generator in ``benchmarks/obs.py``).
  :func:`repro.obs.trace.stage` puts each dispatch's stages on the
  profiler's clock as host spans (:data:`HOST_SPANS`), beside the
  device's programs, which :mod:`repro.obs.programs` names.
* :mod:`repro.obs.events` — the structured event flight recorder: a
  second bounded ring of control-plane events (controller rung moves,
  ladder steps, compaction, pool rebalances, WAL fsync/rotate, snapshot
  cut/publish, worker restarts, injected faults) with names drawn from
  a registered catalog, mirroring ``FaultPlan.KNOWN_SITES``.
* :mod:`repro.obs.export` / :mod:`repro.obs.bundle` — exporters:
  Chrome/Perfetto ``trace_event`` JSON, Prometheus text exposition over
  the unified (flattened) metrics registry, and the post-mortem debug
  bundle written on ``RecoveryError`` / lane death / shutdown.
"""

from repro.obs.events import EVENT_CATALOG, FlightRecorder
from repro.obs.programs import PROGRAMS, jit_program, module_name
from repro.obs.trace import (
    HOST_SPANS,
    SPAN_STAGES,
    RequestTrace,
    RequestTracer,
    TraceRing,
    decompose,
    stage,
)

__all__ = [
    "EVENT_CATALOG",
    "FlightRecorder",
    "HOST_SPANS",
    "PROGRAMS",
    "SPAN_STAGES",
    "RequestTrace",
    "RequestTracer",
    "TraceRing",
    "decompose",
    "jit_program",
    "module_name",
    "stage",
]
