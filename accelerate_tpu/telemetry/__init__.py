"""Unified telemetry: twin registry, request trace spans, training
timeline, SLO monitors (docs/observability.md).

Three pillars, one discipline — host-side, bounded, bitwise-invisible to
tokens and loss:

- :mod:`.twins` — every predicted/measured cost-model pair registered
  under a stable name with units + drift tolerance;
  ``twin_registry().drift_report()`` is the unified table of all of them.
- :mod:`.spans` — request-level lifecycle spans and per-serve-step phase
  spans in a bounded ring (``ServingEngine.trace``), exportable as Chrome
  trace-event JSON (Perfetto) or JSONL; :mod:`.timeline` is the training
  counterpart.
- :mod:`.slo` — streaming p50/p99 estimators (P²) against configurable
  warn/trip thresholds, with Prometheus text exposition; the JSONL sink is
  always available through ``tracking.py``.

Knobs: :class:`~accelerate_tpu.utils.dataclasses.TelemetryPlugin` /
``ACCELERATE_TELEMETRY*`` envs.  The recorder measures its own cost
(``SpanRecorder.overhead_frac``); what tracing costs on the chip is in
``PERF.md`` section 6 (PR 25).
"""

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
    "VirtualClock",
    "validate_chrome_trace",
    "TrainTimeline",
    "StreamingQuantile",
    "SLOMonitor",
    "SLOStatus",
    "prometheus_text",
]
