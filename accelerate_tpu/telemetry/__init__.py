"""Unified telemetry: twin registry, request trace spans, training
timeline, SLO monitors (docs/observability.md).

Four pillars, one discipline — host-side, bounded, bitwise-invisible to
tokens and loss:

- :mod:`.twins` — every predicted/measured cost-model pair registered
  under a stable name with units + drift tolerance;
  ``twin_registry().drift_report()`` is the unified table of all of them.
- :mod:`.spans` — request-level lifecycle spans and per-serve-step phase
  spans in a bounded ring (``ServingEngine.trace``), exportable as Chrome
  trace-event JSON (Perfetto) or JSONL; :mod:`.timeline` is the training
  counterpart.
- :mod:`.host_ledger` — the serving engine's own account of the host's
  time, always on: seconds per phase of ``step()`` and tick kind, the
  caller's time between ticks, the collector's pauses (one ``gc.callbacks``
  hook a process), a stall log that says which phase of which tick stalled
  (``engine.stalls``, a WARNING on ``accelerate_tpu.serving``), and
  ``engine.warmup()`` by program and by part (``engine.warmup_report``) —
  flat keys of ``engine.metrics``.
- :mod:`.slo` — streaming p50/p99 estimators (P²) against configurable
  warn/trip thresholds, with Prometheus text exposition; the JSONL sink is
  always available through ``tracking.py``.

Knobs: :class:`~accelerate_tpu.utils.dataclasses.TelemetryPlugin` /
``ACCELERATE_TELEMETRY*`` envs.  The recorder measures its own cost
(``SpanRecorder.overhead_frac``); what tracing costs on the chip is in
``PERF.md`` section 6 (PR 25).
"""

from .host_ledger import HostLedger, install_global_gc_hook
from .slo import SLOMonitor, SLOStatus, StreamingQuantile, prometheus_text
from .spans import (
    RequestTracer,
    SpanRecorder,
    VirtualClock,
    validate_chrome_trace,
)
from .timeline import TrainTimeline
from .twins import STANDARD_TWINS, Twin, TwinRegistry, twin_registry

__all__ = [
    "STANDARD_TWINS",
    "Twin",
    "TwinRegistry",
    "twin_registry",
    "SpanRecorder",
    "RequestTracer",
    "HostLedger",
    "install_global_gc_hook",
    "VirtualClock",
    "validate_chrome_trace",
    "TrainTimeline",
    "StreamingQuantile",
    "SLOMonitor",
    "SLOStatus",
    "prometheus_text",
]
