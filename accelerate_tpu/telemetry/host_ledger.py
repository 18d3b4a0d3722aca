"""The host ledger: the serving engine's own account of the host's time,
kept whether or not a tracer is armed (docs/observability.md, "The host
ledger").

A :class:`HostLedger` belongs to one ``ServingEngine`` and writes flat numeric
keys into ``engine.metrics`` — the names are built here and nowhere else:

- per tick kind (``decode``, ``prefill``, ``verify``, ``idle``):
  ``ticks.<kind>``, ``tick_wall_s.<kind>``, ``tick_wall_max_s.<kind>`` and,
  per phase of ``step()`` (the part of the span's name before ``:``),
  ``host_s.<kind>.<phase>`` (seconds), ``host_n.<kind>.<phase>`` (ticks that
  ran the phase) and ``host_max_s.<kind>.<phase>`` (most in one tick);
  ``sync_alone_s_sum.<kind>`` / ``sync_alone_n.<kind>``: ``host_sync`` of the
  ticks that waited for their own program alone (no program dispatched
  earlier was still un-fetched);
- ``outside_s_sum`` / ``outside_n`` / ``outside_s_max``: one ``step()``'s
  return to the next one's entry while the engine had work — the caller's
  time; ``add_request_s_sum`` / ``_n`` / ``_s_max`` are the part of it spent
  inside ``add_request``;
- ``gc_pause_s_sum`` / ``gc_pause_n`` / ``gc_pause_s_max`` /
  ``gc_pause_n.gen<g>``: Python's collector, from ONE process-wide
  ``gc.callbacks`` hook (:func:`install_global_gc_hook`) of which every
  ledger reads its own delta;
- ``stall_n`` / ``stall_excess_s_sum`` / ``stall_gc_s_sum`` /
  ``stall_s_by_phase.<phase>``: ticks far over the running median of their
  class (what they took beyond it, the collector's seconds inside them, and
  the excess booked to the phase that grew most);
  ``outside_stall_n`` / ``outside_stall_excess_s_sum``: the same for a gap
  between two ticks; each is a row of ``engine.stalls`` and a WARNING on
  ``accelerate_tpu.serving``, at most one a second
  (``stall_log_suppressed`` counts the rest).

The brackets are the ones ``step()`` has always had: ``HostLedger.phase``
is the one path, and with a tracer armed the same bracket also opens the
engine-track span (a ring event and a ``jax.profiler.TraceAnnotation``) it
opened before, from the same two clock readings.
"""

from __future__ import annotations

import gc
import logging
import time
from collections import deque
from functools import partial
from typing import Optional

import jax

from .slo import StreamingQuantile
from .spans import _ANNOTATED_ARGS, SpanRecorder

logger = logging.getLogger("accelerate_tpu.serving")

TICK_KINDS = ("decode", "prefill", "verify", "idle")

# a tick is a stall when its wall time is over BOTH of these, against the
# running median of its class, once the class has seen a few ticks
STALL_RATIO = 3.0
STALL_FLOOR_S = 0.020
STALL_MIN_TICKS = 8
STALL_LOG_LEN = 64
STALL_WARN_EVERY_S = 1.0

# a collector pause this long is also a `gc` span on the engine track (the
# young generations' pauses are tens of microseconds, one a tick or so: the
# counters keep them, the ring does not)
GC_SPAN_MIN_S = 1e-3

_GENERATIONS = 3
_CARRY_CAP = 3
_FEED_ALL, _FEED_EVERY = 64, 4

# keys every engine starts with (zeros-clean); the per-phase keys appear with
# the first tick of a kind that runs the phase
COUNT_KEYS = (
    *(f"ticks.{k}" for k in TICK_KINDS),
    *(f"sync_alone_n.{k}" for k in TICK_KINDS),
    "outside_n", "add_request_n", "gc_pause_n",
    *(f"gc_pause_n.gen{g}" for g in range(_GENERATIONS)),
    "stall_n", "outside_stall_n", "stall_log_suppressed",
)
SECONDS_KEYS = (
    *(f"tick_wall_s.{k}" for k in TICK_KINDS),
    *(f"sync_alone_s_sum.{k}" for k in TICK_KINDS),
    *(f"tick_wall_max_s.{k}" for k in TICK_KINDS),
    "outside_s_sum", "outside_s_max", "add_request_s_sum", "add_request_s_max",
    "gc_pause_s_sum", "gc_pause_s_max",
    "stall_excess_s_sum", "stall_gc_s_sum", "outside_stall_excess_s_sum",
)


def phase_keys(kind: str, phase: str) -> tuple[str, str, str]:
    """The three ``engine.metrics`` keys of one phase of one tick kind."""
    return (f"host_s.{kind}.{phase}", f"host_n.{kind}.{phase}",
            f"host_max_s.{kind}.{phase}")


def tick_keys(kind: str) -> tuple[str, str, str]:
    return f"ticks.{kind}", f"tick_wall_s.{kind}", f"tick_wall_max_s.{kind}"


def stall_phase_key(phase: str) -> str:
    return f"stall_s_by_phase.{phase}"


# -- the collector ------------------------------------------------------------


class GcPauses:
    """``gc.callbacks`` listener: seconds, count and longest pause of every
    collection of the process, counts per generation, and the newest pauses
    ``(seq, start, seconds, generation, collected)`` on ``time.perf_counter``
    (the hook never reads an engine's clock).  Consumers keep a snapshot and
    read deltas."""

    __slots__ = ("s_sum", "n", "by_gen", "recent", "_t0")

    def __init__(self):
        self.s_sum = 0.0
        self.n = 0
        self.by_gen = [0] * _GENERATIONS
        self.recent: deque = deque(maxlen=256)
        self._t0 = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._t0
        self.n += 1
        self.s_sum += dt
        gen = info.get("generation", 0)
        self.by_gen[min(gen, _GENERATIONS - 1)] += 1
        self.recent.append((self.n, self._t0, dt, gen, info.get("collected", 0)))


_GC_HOOK: Optional[GcPauses] = None


def install_global_gc_hook() -> GcPauses:
    """Install (once) the process-wide collector listener and return it; it
    stays installed, like ``install_global_compile_counter``'s listener."""
    global _GC_HOOK
    if _GC_HOOK is None:
        _GC_HOOK = GcPauses()
        gc.callbacks.append(_GC_HOOK)
    return _GC_HOOK


# -- the bracket ----------------------------------------------------------------

_PHASE_OF: dict[str, str] = {}


class PhaseBracket:
    """``with ledger.phase("stage:decode", step=n) as b:`` — clocks the body on
    the ledger's clock (``b.t0`` / ``b.t1``) into the tick's account.  The
    phases of a tick are siblings, so with no tracer armed ONE bracket serves
    them all, renamed by each call (no allocation on the tick's path; its
    ``t0`` / ``t1`` are then the newest phase's, which nobody reads)."""

    __slots__ = ("ledger", "name", "args", "t0", "t1")

    def __init__(self, ledger, name="", **args):
        self.ledger = ledger
        self.name = name
        self.args = args

    def __call__(self, name, **args):
        self.name = name
        self.args = args
        return self

    def __enter__(self):
        led = self.ledger
        t0 = self.t0 = led.clock()
        if led.t_begin is None:
            led._open_tick(t0, self.args.get("step", 0))
        return self

    def __exit__(self, exc_type, exc, tb):
        led = self.ledger
        t1 = self.t1 = led.t_end = led.clock()
        name = self.name
        try:
            key = _PHASE_OF[name]
        except KeyError:
            key = _PHASE_OF[name] = name.partition(":")[0]
        tick = led.tick
        tick[key] = tick.get(key, 0.0) + (t1 - self.t0)
        return False


class SpanBracket(PhaseBracket):
    """The bracket while a tracer is armed, one a phase: the same two clock
    readings are also the engine-track span of that name — a ring event, and a
    ``jax.profiler.TraceAnnotation`` (with the span's ``step`` / ``uid``) open
    around them, so inside a profiler session the span lies in the profiler's
    own trace too.  ``t0`` of a tick's ``stage`` and ``t1`` of its
    ``host_sync`` are the window its request spans are drawn in."""

    __slots__ = ("_note",)

    def __enter__(self):
        args = self.args
        self._note = jax.profiler.TraceAnnotation(
            self.name, **{k: args[k] for k in _ANNOTATED_ARGS if k in args})
        self._note.__enter__()
        return PhaseBracket.__enter__(self)

    def __exit__(self, exc_type, exc, tb):
        PhaseBracket.__exit__(self, exc_type, exc, tb)
        self.ledger.recorder.complete(self.name, "engine", self.t0, self.t1,
                                      cat="step", **self.args)
        self._note.__exit__(exc_type, exc, tb)
        return False


# -- the ledger -------------------------------------------------------------------


class HostLedger:
    """One engine's account (module docstring).  ``metrics`` is the engine's
    own dict; ``stalls`` its bounded stall log."""

    __slots__ = ("metrics", "stalls", "clock", "recorder", "phase", "tick", "t_begin",
                 "t_end", "busy", "carry", "outside", "_names", "_medians",
                 "_gc", "_gc_base", "_gc_seen", "_gc_begin", "_gc_end", "_gc_max",
                 "_warned_at", "_wall_clock")

    def __init__(self, metrics: dict, clock=time.perf_counter):
        self.metrics = metrics
        for key in COUNT_KEYS:
            metrics.setdefault(key, 0)
        for key in SECONDS_KEYS:
            metrics.setdefault(key, 0.0)
        self.stalls: deque = deque(maxlen=STALL_LOG_LEN)
        self.recorder: Optional[SpanRecorder] = None
        self.tick: dict[str, float] = {}
        self.t_begin: Optional[float] = None
        self.t_end = 0.0
        self.busy = False          # the engine had work when the last tick returned
        self.carry = 0             # ... and so many dispatched programs nobody has waited for
        self.outside = 0.0         # the gap before the open tick
        self._names: dict[str, dict] = {}
        self._medians: dict[tuple, list] = {}   # class -> [estimator, ticks since fed all]
        self._gc = install_global_gc_hook()
        self._gc_base = (self._gc.s_sum, self._gc.n, tuple(self._gc.by_gen))
        self._gc_seen = self._gc.n
        self._gc_begin = self._gc_end = self._gc.s_sum
        self._gc_max = 0.0
        self._warned_at: Optional[float] = None
        self.set_clock(clock)

    def set_clock(self, clock, recorder: Optional[SpanRecorder] = None) -> None:
        """The engine's clock and, with a tracer armed, its recorder."""
        self.clock = clock
        self.recorder = recorder
        # ``with ledger.phase(name, **args):`` — the one bracket of step()
        self.phase = PhaseBracket(self) if recorder is None else partial(SpanBracket, self)
        # the collector's hook stamps time.perf_counter: its pauses can be laid
        # on the ring's timeline only where that is the ring's clock too
        self._wall_clock = clock is time.perf_counter

    # tick boundaries ---------------------------------------------------------

    def begin_tick(self) -> None:
        """``step()`` is about to open its first bracket, whose first clock
        reading is the tick's start (no reading of its own)."""
        self.t_begin = None
        self.tick.clear()

    def _open_tick(self, t0: float, step: int) -> None:
        self.t_begin = t0
        self._gc_begin = self._gc.s_sum
        if not self.busy:
            self.outside = 0.0
            return
        gap = self.outside = t0 - self.t_end
        m = self.metrics
        m["outside_s_sum"] += gap
        m["outside_n"] += 1
        if gap > m["outside_s_max"]:
            m["outside_s_max"] = gap
        median = self._judge(("outside",), gap)
        if median is not None:
            self._stall("outside", 0, gap, median, {}, t0, step=step,
                        gc_s=self._gc_begin - self._gc_end)

    def note_add_request(self, t0: float, t1: float) -> None:
        """``add_request`` ran from ``t0`` to ``t1``.  An idle engine has work
        from ``t0`` on, so the caller's time until the next tick is counted."""
        m = self.metrics
        dt = t1 - t0
        m["add_request_s_sum"] += dt
        m["add_request_n"] += 1
        if dt > m["add_request_s_max"]:
            m["add_request_s_max"] = dt
        if not self.busy:
            self.busy = True
            self.t_end = t0

    def end_tick(self, kind: str, bucket: int = 0, step: int = 0,
                 busy: bool = True) -> Optional[dict]:
        """Close the tick: book its phases under ``kind``, judge its wall time
        against its class, read the collector.  Returns the stall row (already
        in ``stalls``) when the tick was one, for the engine to add what only
        it knows."""
        self.busy = busy
        t0 = self.t_begin
        if t0 is None:          # no bracket ran
            return None
        self.t_begin = None
        tick = self.tick
        wall = self.t_end - t0
        names = self._names.get(kind) or self._kind_names(kind)
        kind = names[""][3]
        if self._gc.n != self._gc_seen:
            self._read_gc()
        self._gc_end = self._gc.s_sum
        row = None
        # a class is what decides a tick's wall time besides a stall: its
        # kind and bucket, how many programs dispatched before it nobody has
        # waited for (a prefill chunk that does not end its prompt: the tick
        # that fetches next waits for them too), and whether it waits at all
        carried = self.carry
        synced = "host_sync" in tick
        m = self.metrics
        if synced:
            self.carry = 0
            if not carried:
                # the wait for the tick's own program alone: less the program's
                # time on the device, the runtime's launch-plus-return latency
                m[names[""][4]] += tick["host_sync"]
                m[names[""][5]] += 1
        elif "dispatch" in tick:
            self.carry = min(carried + 1, _CARRY_CAP)
        if kind != "idle":
            median = self._judge((kind, bucket, carried, synced), wall)
            if median is not None:
                row = self._stall(kind, bucket, wall, median, tick, self.t_end,
                                  step=step, carried=carried, names=names,
                                  gc_s=self._gc_end - self._gc_begin)
        kt, kw, kx = names[""][:3]
        m[kt] += 1
        m[kw] += wall
        if wall > m[kx]:
            m[kx] = wall
        for phase, dt in tick.items():
            try:
                ks, kn, kx = names[phase]
            except KeyError:
                ks, kn, kx = names[phase] = phase_keys(kind, phase)
                m.setdefault(ks, 0.0)
                m.setdefault(kn, 0)
                m.setdefault(kx, 0.0)
            m[ks] += dt
            m[kn] += 1
            if dt > m[kx]:
                m[kx] = dt
        return row

    def _kind_names(self, kind: str) -> dict:
        """The key table of a tick kind; a kind the ledger does not know (a
        ``preempted`` return) is booked as ``idle``."""
        booked = kind if kind in TICK_KINDS else "idle"
        names = self._names.get(booked)
        if names is None:
            names = self._names[booked] = {"": (*tick_keys(booked), booked,
                                                f"sync_alone_s_sum.{booked}",
                                                f"sync_alone_n.{booked}")}
        self._names[kind] = names
        return names

    # stalls -------------------------------------------------------------------

    def _judge(self, cls: tuple, seconds: float) -> Optional[float]:
        """Judge ``seconds`` against the class's running median and feed it;
        the median when it is a stall, else None.  Past its first ticks a
        class's estimator is fed one tick in ``_FEED_EVERY`` (a P-square
        update is the dearest thing the ledger does in a tick, and a median
        does not need every sample)."""
        judge = self._medians.get(cls)
        if judge is None:
            judge = self._medians[cls] = [StreamingQuantile(0.5), 0]
        q = judge[0]
        median = None
        if q.n >= STALL_MIN_TICKS:
            median = q._heights[2]         # == q.value() past five samples
            if not (seconds > STALL_RATIO * median and seconds > median + STALL_FLOOR_S):
                median = None
            if q.n >= _FEED_ALL:
                judge[1] += 1
                if judge[1] % _FEED_EVERY:
                    return median
        q.observe(seconds)
        return median

    def _stall(self, kind, bucket, wall, median, tick, now, step=0,
               carried=0, names=None, gc_s=0.0) -> dict:
        m = self.metrics
        excess = wall - median
        # the phase that grew most over its own mean of this kind so far
        grew, phase = 0.0, kind if kind == "outside" else "none"
        for name, dt in tick.items():
            keys = names.get(name) if names else None
            mean = m[keys[0]] / m[keys[1]] if keys and m.get(keys[1]) else 0.0
            if dt - mean > grew:
                grew, phase = dt - mean, name
        row = {"step": step, "kind": kind, "bucket": bucket, "carried": carried,
               "wall_s": wall, "median_s": median, "phase": phase,
               "phases": dict(tick), "gc_s": gc_s, "outside_s": self.outside}
        self.stalls.append(row)
        if kind == "outside":           # the caller's pause: logged, counted apart from the ticks'
            m["outside_stall_n"] += 1
            m["outside_stall_excess_s_sum"] += excess
        else:
            m["stall_n"] += 1
            m["stall_excess_s_sum"] += excess
            m["stall_gc_s_sum"] += gc_s
            key = stall_phase_key(phase)
            m[key] = m.get(key, 0.0) + excess
        if self.recorder is not None:
            self.recorder.instant("stall", "engine", cat="stall", at=now, step=step,
                                  kind=kind, phase=phase, wall_ms=wall * 1e3,
                                  median_ms=median * 1e3, gc_ms=gc_s * 1e3)
        if self._warned_at is not None and now - self._warned_at < STALL_WARN_EVERY_S:
            m["stall_log_suppressed"] += 1
        else:
            self._warned_at = now
            logger.warning(stall_line(row))
        return row

    # the collector --------------------------------------------------------------

    def _read_gc(self) -> None:
        """The collector ran since the last look: this engine's deltas, and a
        ``gc`` span for each long pause while a tracer is armed."""
        hook, m = self._gc, self.metrics
        s0, n0, gens0 = self._gc_base
        m["gc_pause_s_sum"] = hook.s_sum - s0
        m["gc_pause_n"] = hook.n - n0
        for g in range(_GENERATIONS):
            m[f"gc_pause_n.gen{g}"] = hook.by_gen[g] - gens0[g]
        rec = self.recorder
        # a copy: ``rec.complete`` allocates, a collection may start inside this loop,
        # and the listener then appends to ``recent`` on this very thread
        for seq, start, dt, gen, collected in reversed(tuple(hook.recent)):
            if seq <= self._gc_seen:
                break
            if dt > self._gc_max:
                self._gc_max = m["gc_pause_s_max"] = dt
            if rec is not None and dt >= GC_SPAN_MIN_S:
                # on an injected clock the pause's place in the tick is not
                # known: the span is laid at the tick's end
                at = start if self._wall_clock else self.t_end
                rec.complete("gc", "engine", at, at + dt, cat="gc",
                             generation=gen, collected=collected)
        self._gc_seen = hook.n


def stall_line(row: dict) -> str:
    """``slow tick 1843 decode 138.2 ms (median 36.5): host_sync 101.3 gc 0.0
    stage 1.9 ...`` — the stalled tick's phases, longest first, in ms."""
    parts = sorted(row["phases"].items(), key=lambda kv: -kv[1])
    head = (f"slow gap before tick {row['step']}" if row["kind"] == "outside"
            else f"slow tick {row['step']} {row['kind']}")
    if row["bucket"]:
        head += f"[{row['bucket']}]"
    first = [f"{parts[0][0]} {parts[0][1] * 1e3:.1f}"] if parts else []
    rest = [f"{name} {dt * 1e3:.1f}" for name, dt in parts[1:]]
    body = first + [f"gc {row['gc_s'] * 1e3:.1f}"] + rest + \
        [f"outside {row['outside_s'] * 1e3:.1f}"]
    return (f"{head} {row['wall_s'] * 1e3:.1f} ms (median {row['median_s'] * 1e3:.1f}): "
            + " ".join(body))
