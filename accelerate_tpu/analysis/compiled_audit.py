"""Compiled-artifact auditor: what the lowered XLA executable ACTUALLY does.

The jaxpr auditor (:mod:`.jaxpr_audit`) predicts hazards from the traced
program; this engine reads XLA's decisions off the compiled executable —
``jax.jit(fn).lower().compile()`` (the AOT idiom of ``utils/other.py``'s
``aot_compile``), then ``compiled.memory_analysis()`` /
``compiled.cost_analysis()``:

- **GL301 donation-not-aliased** — ``donate_argnums`` bytes the executable
  provably did not alias (``alias_size_in_bytes`` < donated bytes).  The
  compiled-level twin of GL101: the trace-level rule predicts viability by
  byte-size matching, this one reads the aliasing table XLA actually
  committed to, so it also catches donations declined for layout or
  sharding reasons no trace-level model sees.
- **GL302 hbm-over-budget** — the program's argument+output+temp footprint
  against the device HBM budget (measured from ``memory_stats()`` when the
  backend reports one, or an explicit ``--hbm-gb``).  An over-budget
  program OOMs at first execution — after the deploy took traffic, unless
  preflight catches it here.
- **GL303 recompile-ladder-drift** — the compiled program set against the
  predicted bucket ladder (a serving deploy is exactly
  ``len(prefill_buckets) + 2`` programs: one prefill per bucket, one
  decode, one release), and the backend-compile events observed while
  building it.  Every extra distinct lowering is a mid-traffic recompile
  waiting to happen.

Plus the **cost report**: per-program flops / bytes-accessed from
``cost_analysis()``, the inputs the predicted-MFU arithmetic feeds on.

The compile-event counter (:class:`CompileCounter`) hooks the
``jax.monitoring`` event stream (``/jax/core/compile/
backend_compile_duration`` — one event per trip to the XLA backend for an
executable; jit-call cache hits excluded) and backs the runtime recompile
guard: ``ServingEngine.compile_events`` / ``Accelerator.compile_events`` and
the ``compiles_predicted`` / ``compiles_measured`` twins of the replay
harness.

Everything here is CPU-safe: AOT compilation needs a backend but never
executes the program, so a deploy preflight runs on the CI box.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Optional, Sequence

import jax
import numpy as np

from .report import Finding, Report, apply_suppressions
from .rules import RULES

try:  # the monitoring hooks live in the private namespace on 0.4.x
    from jax._src import monitoring as _monitoring
except Exception:  # pragma: no cover - private-API drift
    _monitoring = None


# one event per trip to the XLA backend for an executable: a compilation
# or, on jax 0.9, a load from the persistent cache (a jit-call cache hit
# fires nothing) — the signal the recompile guard wants
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the other parts of bringing a program up that the same stream reports
# (all present in jax 0.9.0): CompileCounter's field for each timed one
_TIMED_PARTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    COMPILE_EVENT: "backend_s",
}
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
PARTS = ("trace_s", "lower_s", "backend_s", "cache_load_s", "cache_hits", "cache_misses")


@contextlib.contextmanager
def fresh_compile_context():
    """Force REAL backend compiles (no persistent-cache reads) for the scope.

    An executable DESERIALIZED from the cache loses its buffer-donation
    alias table — ``memory_analysis().alias_size_in_bytes`` reads 0 even
    when the original compile aliased everything — so an audit over a
    cache hit would report GL301 on perfectly good donations.  The auditor
    therefore always compiles fresh: a deploy preflight is a one-shot gate,
    and honest stats beat a warm-cache speedup that poisons them.

    Two levers, both needed: the ``jax_enable_compilation_cache`` flag, and
    ``compilation_cache.reset_cache()`` — jax memoizes the is-cache-used
    decision at the process's FIRST compile, so flipping the flag alone is
    ignored once any earlier compile touched the cache.  The reset drops
    that memo (and the cache's in-memory LRU; the on-disk store is
    untouched) so the flag is actually re-read, here and again on exit.
    """
    from jax.experimental.compilation_cache import compilation_cache as _cc

    prev = jax.config.jax_enable_compilation_cache
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        _cc.reset_cache()
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        _cc.reset_cache()


class CompileCounter:
    """Counts real XLA backend compiles via the jax monitoring stream, and
    keeps what the stream hands it: the seconds of tracing, of lowering and in
    the backend, the seconds spent reading the persistent cache, and its hits
    and misses (:data:`PARTS`).

    jax brackets each of the three timed parts (``log_elapsed_time``: a scalar
    at entry, the duration at exit), and a bracket can lie inside another (a
    ``jit`` traced inside a ``jit``: both report).  Only the outermost one is
    booked, so the parts never overlap; a cache read happens inside the
    backend bracket and is taken out of it, so ``backend_s`` is compiling
    (and writing the cache) alone.

    Usable as a context manager (``with CompileCounter() as c: ...``) for
    scoped measurement, or long-lived through
    :func:`install_global_compile_counter` for the per-object
    ``compile_events`` deltas the engine and accelerator expose; consumers
    read deltas of :meth:`parts`.
    """

    def __init__(self):
        self.count = 0
        self.trace_s = 0.0
        self.lower_s = 0.0
        self.backend_s = 0.0
        self.cache_load_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self._depth = 0
        self._active = False
        self._registered = False

    def parts(self) -> tuple:
        """``(trace_s, lower_s, backend_s, cache_load_s, cache_hits,
        cache_misses)`` so far (:data:`PARTS` names them)."""
        return (self.trace_s, self.lower_s, self.backend_s, self.cache_load_s,
                self.cache_hits, self.cache_misses)

    def _on_enter(self, event, value=None, **kwargs):
        if self._active and event in _TIMED_PARTS:
            self._depth += 1

    def _on_event(self, event, duration=None, **kwargs):
        if not self._active:
            return
        if event == COMPILE_EVENT:
            self.count += 1
        field = _TIMED_PARTS.get(event)
        if field is not None:
            self._depth = max(0, self._depth - 1)
            if self._depth == 0:
                setattr(self, field, getattr(self, field) + duration)
        elif event == CACHE_LOAD_EVENT:
            self.cache_load_s += duration
            self.backend_s -= duration     # read inside the backend bracket, which ends later

    def _on_count(self, event, **kwargs):
        if self._active:
            if event == CACHE_HIT_EVENT:
                self.cache_hits += 1
            elif event == CACHE_MISS_EVENT:
                self.cache_misses += 1

    def start(self) -> "CompileCounter":
        self._active = True
        if not self._registered and _monitoring is not None:
            _monitoring.register_event_duration_secs_listener(self._on_event)
            _monitoring.register_scalar_listener(self._on_enter)
            _monitoring.register_event_listener(self._on_count)
            self._registered = True
        return self

    def stop(self) -> "CompileCounter":
        self._active = False
        if self._registered and _monitoring is not None:
            try:
                _monitoring.unregister_event_duration_listener(self._on_event)
                _monitoring.unregister_scalar_listener(self._on_enter)
                _monitoring.unregister_event_listener(self._on_count)
                self._registered = False
            except Exception:  # pragma: no cover - private-API drift
                pass  # listeners stay registered but inert (_active False)
        return self

    def __enter__(self) -> "CompileCounter":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


_GLOBAL_COUNTER: Optional[CompileCounter] = None


def install_global_compile_counter() -> CompileCounter:
    """Install (idempotently) the process-wide compile-event counter and
    return it.  Callers snapshot ``.count`` and report deltas — the counter
    itself is never uninstalled, so overlapping consumers (an engine and an
    accelerator in one process) each get a consistent monotonic stream."""
    global _GLOBAL_COUNTER
    if _GLOBAL_COUNTER is None:
        _GLOBAL_COUNTER = CompileCounter().start()
    return _GLOBAL_COUNTER


def device_hbm_bytes(hbm_gb: Optional[float] = None) -> Optional[int]:
    """The HBM budget for GL302: an explicit ``hbm_gb`` wins; otherwise the
    backend's reported ``bytes_limit`` (TPU/GPU — CPU reports none).  None
    means "no budget known": GL302 is skipped rather than guessed."""
    if hbm_gb is not None:
        return int(hbm_gb * 2**30)
    try:
        stats = jax.devices()[0].memory_stats()
    except Exception:  # pragma: no cover - backend without memory_stats
        return None
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    return None


# ---------------------------------------------------------------------------
# per-program compile + audit
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CompiledProgram:
    """One AOT-compiled production program plus its audit inputs."""

    label: str
    compiled: Any                       # jax.stages.Compiled
    traced: Any = None                  # jax.stages.Traced (jaxpr-audit input)
    compile_s: float = 0.0
    compile_events: int = 0             # real backend compiles this one cost
    path_hint: Optional[tuple] = None


def aot_compile_program(
    fn: Callable,
    *example_args,
    donate_argnums=(),
    static_argnums=(),
    label: str = "program",
    path_hint: Optional[tuple] = None,
) -> CompiledProgram:
    """Trace, lower and compile ``fn`` ahead of time (accepts concrete
    arrays or ``jax.ShapeDtypeStruct`` stand-ins — nothing executes), timing
    the wall and counting the real backend-compile events (a persistent-
    cache hit costs 0)."""
    # A plain function is jitted through a function object of its own: jax
    # keys its in-memory trace, lowering and executable caches on the
    # function's identity, so ``jax.jit(fn)`` would be handed back whatever
    # an earlier jit call of ``fn`` left there — after a persistent-cache
    # hit, the DESERIALIZED executable ``fresh_compile_context`` exists to
    # keep out of the audit.  An already-jitted ``fn`` is the caller's.
    jitted = fn if hasattr(fn, "trace") else jax.jit(
        lambda *args: fn(*args),
        donate_argnums=donate_argnums, static_argnums=static_argnums,
    )
    counter = CompileCounter()
    t0 = time.perf_counter()
    with counter, fresh_compile_context():
        traced = jitted.trace(*example_args)
        compiled = traced.lower().compile()
    return CompiledProgram(
        label=label, compiled=compiled, traced=traced,
        compile_s=time.perf_counter() - t0, compile_events=counter.count,
        path_hint=path_hint,
    )


def _finding(rule_id: str, message: str, path_hint=None) -> Finding:
    r = RULES[rule_id]
    return Finding(
        rule=rule_id, severity=r.severity, message=message, fix_hint=r.fix_hint,
        path=path_hint[0] if path_hint else None,
        line=path_hint[1] if path_hint else None,
        engine="compiled",
    )


def _donated_bytes(compiled) -> int:
    """Total bytes the caller donated, read off the compiled signature."""
    leaves = jax.tree_util.tree_leaves(
        compiled.args_info, is_leaf=lambda x: hasattr(x, "donated")
    )
    total = 0
    for leaf in leaves:
        if not getattr(leaf, "donated", False):
            continue
        shape = getattr(leaf, "shape", ())
        n = int(np.prod(shape)) if shape else 1
        try:
            total += n * np.dtype(leaf.dtype).itemsize
        except TypeError:
            total += n * int(getattr(leaf.dtype, "itemsize", 8) or 8)
    return total


def _cost_dict(compiled) -> dict:
    try:
        cost = compiled.cost_analysis()
    except Exception:  # pragma: no cover - backend without cost analysis
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost or {})


def audit_compiled(
    compiled,
    *,
    label: str = "program",
    hbm_budget_bytes: Optional[int] = None,
    donation_slack_bytes: int = 1024,
    path_hint: Optional[tuple] = None,
) -> tuple[list[Finding], dict]:
    """Audit one compiled executable; returns ``(findings, report_row)``.

    ``donation_slack_bytes`` tolerates tiny non-aliased donated members
    (scalar step counters and the like XLA reasonably declines) before
    GL301 fires; ``hbm_budget_bytes=None`` skips GL302 rather than guess.
    """
    findings: list[Finding] = []
    mem = None
    try:
        mem = compiled.memory_analysis()
    except Exception:  # pragma: no cover - backend without memory analysis
        pass

    donated = _donated_bytes(compiled)
    # None (attribute absent on this jaxlib) means "unknown", not "nothing
    # aliased" — GL301 is then skipped, not guessed, like GL302 without a
    # budget; the footprint math conservatively counts outputs in full
    alias_known = mem is not None and hasattr(mem, "alias_size_in_bytes")
    aliased = int(mem.alias_size_in_bytes or 0) if alias_known else 0
    row: dict = {"program": label, "compile_events": None}
    if mem is not None:
        args_b = int(mem.argument_size_in_bytes)
        out_b = int(mem.output_size_in_bytes)
        temp_b = int(mem.temp_size_in_bytes)
        # aliased output bytes live in the donated argument buffers — they
        # must not be double-counted in the resident footprint
        total = args_b + max(out_b - aliased, 0) + temp_b
        row["hbm"] = {
            "arguments": args_b, "outputs": out_b, "temps": temp_b,
            "aliased": aliased, "total": total,
            "total_gib": round(total / 2**30, 6),
        }
        if alias_known and donated - aliased > max(donation_slack_bytes, 0):
            findings.append(
                _finding(
                    "GL301",
                    f"{label}: {donated - aliased} of {donated} donated "
                    "bytes were NOT aliased by the compiled executable "
                    f"(aliased {aliased} B) — the donation frees nothing "
                    "and the caller still loses the buffer",
                    path_hint,
                )
            )
        if hbm_budget_bytes is not None and total > hbm_budget_bytes:
            findings.append(
                _finding(
                    "GL302",
                    f"{label}: compiled footprint {total / 2**30:.3f} GiB "
                    f"(args {args_b} + outputs {max(out_b - aliased, 0)} + "
                    f"temps {temp_b} B) exceeds the HBM budget "
                    f"{hbm_budget_bytes / 2**30:.3f} GiB",
                    path_hint,
                )
            )
    row["donated_bytes"] = donated
    row["aliased_bytes"] = aliased
    cost = _cost_dict(compiled)
    row["flops"] = float(cost.get("flops", 0.0))
    row["bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
    return findings, row


def audit_program_set(
    rows: Sequence[dict],
    predicted_count: int,
    *,
    measured_compile_events: Optional[int] = None,
    path_hint: Optional[tuple] = None,
) -> list[Finding]:
    """GL303: the compiled program set against the predicted ladder.

    ``rows`` are the per-program report rows actually compiled;
    ``predicted_count`` is what the bucket ladder implies (serving:
    ``len(prefill_buckets) + 2``).  ``measured_compile_events`` (when the
    caller counted them) may legitimately be LOWER than the program count —
    persistent-cache hits — but higher means some program lowered more than
    once: a recompile waiting to happen."""
    findings = []
    if len(rows) != predicted_count:
        findings.append(
            _finding(
                "GL303",
                f"compiled {len(rows)} distinct programs where the bucket "
                f"ladder predicts exactly {predicted_count} "
                f"({', '.join(r['program'] for r in rows)})",
                path_hint,
            )
        )
    if measured_compile_events is not None and measured_compile_events > len(rows):
        findings.append(
            _finding(
                "GL303",
                f"{measured_compile_events} backend compile events for "
                f"{len(rows)} programs: some program lowered more than "
                "once during preflight — a mid-traffic recompile shape",
                path_hint,
            )
        )
    return findings


def audit_aot(
    fn: Callable,
    *example_args,
    donate_argnums=(),
    label: str = "program",
    hbm_budget_bytes: Optional[int] = None,
    donation_slack_bytes: int = 1024,
    path_hint: Optional[tuple] = None,
) -> tuple[Report, dict]:
    """One-shot convenience: AOT-compile ``fn`` and audit the executable
    (GL301/GL302 + the cost row).  Returns ``(Report, report_row)`` — the
    jaxpr-level audit of the same program is :func:`.jaxpr_audit.audit_fn`;
    a full deploy preflight composes both (``commands/preflight.py``)."""
    if path_hint is None:
        code = getattr(fn, "__code__", None)
        if code is not None:
            path_hint = (code.co_filename, code.co_firstlineno)
    prog = aot_compile_program(
        fn, *example_args, donate_argnums=donate_argnums, label=label,
        path_hint=path_hint,
    )
    findings, row = audit_compiled(
        prog.compiled, label=label, hbm_budget_bytes=hbm_budget_bytes,
        donation_slack_bytes=donation_slack_bytes, path_hint=path_hint,
    )
    row["compile_s"] = round(prog.compile_s, 4)
    row["compile_events"] = prog.compile_events
    return Report(apply_suppressions(findings)), row
