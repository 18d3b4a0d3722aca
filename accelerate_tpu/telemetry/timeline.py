"""Training step timeline: where each step's wall clock went.

Phase spans recorded at the hook points the training stack already owns —
all host-side, zero added device syncs, no new compiled programs:

- ``data_wait``      — the dataloader blocking on its inner iterable
  (``DataLoaderShard`` / ``DataLoaderDispatcher``)
- ``h2d_staging``    — batch device placement (and ``LayerPrefetcher``
  uploads when generation/offload streaming is active)
- ``step_dispatch``  — the prepared train step's jitted call.  JAX dispatch
  is async: this measures host-side dispatch+enqueue time, NOT device
  compute (a near-zero span under a healthy pipeline; a long one means the
  host fell behind or something synchronized early)
- ``guard_sync``     — the NaN-guard's per-step scalar fetch (the one
  intentional host sync of an armed step)
- ``checkpoint_drain`` — blocking on an in-flight async checkpoint
  (``checkpointing.wait_for_pending_checkpoint``)

The timeline shares the span machinery (:class:`~.spans.SpanRecorder`):
bounded ring, injectable clock for deterministic tests, Chrome-trace/JSONL
export, self-measured ``overhead_s``.  ``summary()`` is the per-phase
digest (count/total/mean); per-step ``step_time_s``
observations can feed an :class:`~.slo.SLOMonitor` (the accelerator wires
this when both are enabled).
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Callable, Optional

from .spans import SpanRecorder

PHASES = ("data_wait", "h2d_staging", "step_dispatch", "guard_sync",
          "checkpoint_drain")


class TrainTimeline:
    """Phase timing of the prepared train loop (host-side only)."""

    def __init__(self, capacity: int = 4096,
                 clock: Optional[Callable[[], float]] = None):
        self.recorder = SpanRecorder(capacity=capacity, clock=clock)
        self._totals: dict[str, float] = {}
        self._counts: dict[str, int] = {}
        self._stack: list[list[float]] = []  # per-open-phase child-time accum

    @property
    def enabled(self) -> bool:
        return self.recorder.enabled

    @contextmanager
    def phase(self, name: str, **args):
        """Record one phase span on the ``train`` track.  Aggregates are
        kept outside the ring so ``summary()`` survives ring wrap; totals
        are EXCLUSIVE time — a phase nested inside another (the prefetch
        path runs ``h2d_staging`` inside ``data_wait``'s blocking ``next``)
        attributes its duration to itself only, so phase totals never sum
        past the wall clock.  The exported spans keep the full (inclusive)
        durations — nesting renders naturally in Perfetto."""
        rec = self.recorder
        if not rec.enabled:
            yield
            return
        frame = [0.0]
        self._stack.append(frame)
        try:
            # a bracketing span: also an annotation in the profiler's trace
            with rec.span(name, "train", cat="train", **args) as times:
                yield
        finally:
            self._stack.pop()
            dur = times[1] - times[0]
            if self._stack:
                self._stack[-1][0] += dur
            self._totals[name] = self._totals.get(name, 0.0) \
                + max(0.0, dur - frame[0])
            self._counts[name] = self._counts.get(name, 0) + 1

    def summary(self) -> dict:
        """``{phase: {count, total_s, mean_s}}`` over the whole run —
        exclusive time (see :meth:`phase`); ring wrap does not lose
        aggregate time, only old span detail."""
        out = {}
        for name in sorted(self._totals):
            n = self._counts[name]
            total = self._totals[name]
            out[name] = {
                "count": n,
                "total_s": round(total, 6),
                "mean_s": round(total / n, 6) if n else 0.0,
            }
        return out

    def overhead_frac(self, wall_s: float) -> float:
        return self.recorder.overhead_frac(wall_s)

    def to_chrome_trace(self) -> dict:
        return self.recorder.to_chrome_trace()

    def write_chrome_trace(self, path) -> None:
        self.recorder.write_chrome_trace(path)

    def write_jsonl(self, path) -> None:
        self.recorder.write_jsonl(path)

    def clear(self) -> None:
        self.recorder.clear()
        self._totals.clear()
        self._counts.clear()
        self._stack.clear()
