"""Jaxpr auditor: trace a step/decode function abstractly and flag the
hazard classes that only show up in the traced program.

No device execution anywhere: the engine runs on ``jax.jit(fn).trace(...)``
(the AOT path — accepts :class:`jax.ShapeDtypeStruct` stand-ins), so a 7B
train step audits on a CPU-only CI box.  The hazards, and why each is
invisible to a source-level linter:

- **GL101 wasted donation** — ``donate_argnums`` promises XLA an input
  buffer to reuse for an output of the same byte size.  Whether any output
  qualifies is a property of the traced avals, not the source.  A donation
  nothing can alias frees zero HBM while still invalidating the caller's
  buffer — the worst of both worlds.
- **GL102 const capture** — a closed-over array becomes a jaxpr constant:
  baked per executable, duplicated across retraces, exempt from donation
  and the sharding plan.  Only the trace knows what actually closed over.
- **GL103 transfer in trace** — a ``device_put`` to a different memory kind
  inside traced code is a host<->device copy serialized into the step,
  bypassing the ``ops/streaming.py`` overlap discipline.
- **GL104 key reuse** — one PRNG key consumed by two random primitives
  yields identical streams; the auditor tracks key identity through
  ``pjit``/``scan``/``cond`` sub-jaxprs, which no regex can.
- **GL105 unsharded large output** — an output above the size threshold
  whose producer is not a sharding constraint may be resolved fully
  replicated by GSPMD.
- **GL106 collective-matmul hint** (info) — an ``all_gather`` consumed by
  exactly one ``dot_general`` is the monolithic gather-then-matmul pipe
  that ``ops/collective_matmul.py`` decomposes into a latency-hiding ring;
  only the traced program shows the consumer fan-out.
- **GL107 collective-matmul reduce-scatter hint** (info) — the row-parallel
  mirror: a ``dot_general`` whose result feeds exactly one
  ``reduce_scatter`` serializes the monolithic scatter behind the matmul
  that produced it (``ring_matmul_reduce_scatter`` is the decomposition).
- **GL108 hierarchical-reduction hint** (info) — a large psum spanning the
  ``dcn`` mesh axis jointly with intra-slice axes: the flat reduction's
  cross-slice leg carries one redundant full-size copy per intra-slice
  device over DCN; ``parallel/hierarchical.py`` is the decomposition
  (reduce-scatter over ICI, slab all-reduce over dcn, all-gather back).
- **GL110 unscaled fp8 dot** — a ``dot_general`` over float8 operands whose
  result reaches downstream math with no dequantizing ``mul``/``div`` in
  the chain: fp8 codes are meaningless without their scale, and only the
  traced program shows whether the accumulator was rescaled before use.
- **GL304 donated promotion drift** — a donated input whose only same-shape
  outputs differ in dtype or weak_type (a python/numpy scalar promoted the
  update): feeding the result back re-keys the jit cache every step, and
  the widened output can no longer alias the donated buffer.

Suppression is source-anchored (see :mod:`.report`): each finding resolves
its file/line from the flagged equation's ``source_info``, so the same
inline ``# graft-lint: disable=GLxxx -- reason`` marker works for both
engines.
"""

from __future__ import annotations

from typing import Any, Iterable, Optional

import jax
import numpy as np
from jax.extend import core as jex_core

from .report import Finding, Report, apply_suppressions
from .rules import RULES

# jaxpr equations carry their user-code provenance here
from jax._src import source_info_util as _src_info


# random primitives that CONSUME a key (produce bits or a derived stream).
# Structural ops on key arrays (slice/squeeze/broadcast of a split result)
# produce distinct subkeys and are not consumptions.
_KEY_CONSUMERS = frozenset({"random_split", "random_bits", "random_fold_in"})

# producers that satisfy GL105: the output's layout was pinned on purpose
_SHARDING_PRODUCERS = frozenset({"sharding_constraint", "device_put"})


# ---------------------------------------------------------------------------
# small jaxpr helpers
# ---------------------------------------------------------------------------


def _is_key_aval(aval) -> bool:
    try:
        return jax.numpy.issubdtype(aval.dtype, jax.dtypes.prng_key)
    except Exception:
        return False


def _aval_bytes(aval) -> int:
    """Byte size of an abstract value; extended dtypes (PRNG keys) fall back
    to their impl's key size (threefry: 2x uint32)."""
    shape = getattr(aval, "shape", ())
    n = int(np.prod(shape)) if shape else 1
    dtype = getattr(aval, "dtype", None)
    try:
        return n * np.dtype(dtype).itemsize
    except TypeError:
        itemsize = getattr(dtype, "itemsize", None)
        return n * int(itemsize if itemsize else 8)


def _eqn_location(eqn):
    """``(path, line)`` of the user frame that emitted ``eqn``, if any."""
    if eqn is None:
        return None, None
    frame = _src_info.user_frame(eqn.source_info.traceback)
    if frame is None:
        return None, None
    return frame.file_name, frame.start_line


def _sub_jaxprs(eqn) -> list:
    """Every (closed) sub-jaxpr carried in an equation's params, normalized
    to ``ClosedJaxpr``-likes with ``.jaxpr`` access."""
    subs = []
    for val in eqn.params.values():
        for item in val if isinstance(val, (list, tuple)) else (val,):
            if hasattr(item, "jaxpr") and hasattr(item, "consts"):
                subs.append(item)  # ClosedJaxpr
            elif hasattr(item, "eqns") and hasattr(item, "invars"):
                subs.append(jex_core.ClosedJaxpr(item, ()))
    return subs


def _walk_eqns(jaxpr) -> Iterable:
    """Depth-first over every equation, including sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub.jaxpr)


def iter_eqns(closed_or_jaxpr) -> Iterable:
    """Public depth-first equation iterator over a ``ClosedJaxpr`` (or bare
    jaxpr), descending into every sub-jaxpr — the one place the sub-jaxpr
    packaging convention lives (callers checking for a primitive, e.g. the
    dryrun's ppermute-engagement probe, should use this rather than
    re-rolling the recursion)."""
    jaxpr = getattr(closed_or_jaxpr, "jaxpr", closed_or_jaxpr)
    return _walk_eqns(jaxpr)


def _finding(rule_id: str, message: str, *, path=None, line=None) -> Finding:
    r = RULES[rule_id]
    return Finding(
        rule=rule_id, severity=r.severity, message=message, fix_hint=r.fix_hint,
        path=path, line=line, engine="jaxpr",
    )


# ---------------------------------------------------------------------------
# the individual audits
# ---------------------------------------------------------------------------


def _audit_donation(jaxpr, donated: list[bool], path_hint) -> list[Finding]:
    """GL101: greedy byte-size matching of donated inputs against outputs —
    the same viability criterion XLA's buffer-donation aliasing applies
    (an input buffer can only be reused by an output of equal size)."""
    findings = []
    out_vars = [v for v in jaxpr.outvars if not isinstance(v, jex_core.Literal)]
    # a donated input returned unchanged IS its own output buffer
    passthrough = {id(v) for v in jaxpr.invars} & {id(v) for v in out_vars}
    out_sizes: dict[int, int] = {}
    for v in out_vars:
        if id(v) in passthrough:
            continue
        size = _aval_bytes(v.aval)
        out_sizes[size] = out_sizes.get(size, 0) + 1
    for i, (var, is_donated) in enumerate(zip(jaxpr.invars, donated)):
        if not is_donated or id(var) in passthrough:
            continue
        size = _aval_bytes(var.aval)
        if out_sizes.get(size, 0) > 0:
            out_sizes[size] -= 1
            continue
        aval = var.aval
        findings.append(
            _finding(
                "GL101",
                f"donated argument {i} ({getattr(aval, 'dtype', '?')}"
                f"{list(getattr(aval, 'shape', ()))}, {size} B) aliases no "
                "output: no un-aliased output of the same byte size remains",
                path=path_hint[0] if path_hint else None,
                line=path_hint[1] if path_hint else None,
            )
        )
    return findings


def _audit_donation_promotion(jaxpr, donated: list[bool], path_hint) -> list[Finding]:
    """GL304: a donated input with no exact-aval output but a same-shape
    output whose dtype or weak_type drifted — the promotion signature of a
    python/numpy scalar mixed into the donated tree.  The drifted result
    re-keys the jit cache when fed back (a recompile every step) and can no
    longer alias the donated buffer."""
    out_vars = [v for v in jaxpr.outvars if not isinstance(v, jex_core.Literal)]
    passthrough = {id(v) for v in jaxpr.invars} & {id(v) for v in out_vars}

    def _sig(aval):
        return (
            tuple(getattr(aval, "shape", ())),
            str(getattr(aval, "dtype", "?")),
            bool(getattr(aval, "weak_type", False)),
        )

    exact: dict[tuple, int] = {}
    by_shape: dict[tuple, list] = {}
    for v in out_vars:
        if id(v) in passthrough:
            continue
        shape, dtype, weak = _sig(v.aval)
        exact[(shape, dtype, weak)] = exact.get((shape, dtype, weak), 0) + 1
        by_shape.setdefault(shape, []).append((dtype, weak))
    findings = []
    for i, (var, is_donated) in enumerate(zip(jaxpr.invars, donated)):
        if not is_donated or id(var) in passthrough:
            continue
        shape, dtype, weak = _sig(var.aval)
        if exact.get((shape, dtype, weak), 0) > 0:
            exact[(shape, dtype, weak)] -= 1
            continue
        drifted = [
            (d, w) for d, w in by_shape.get(shape, []) if (d, w) != (dtype, weak)
        ]
        if not drifted:
            continue  # no same-shape output at all: GL101's case, not drift
        d, w = drifted[0]
        what = f"dtype {dtype} -> {d}" if d != dtype else f"weak_type {weak} -> {w}"
        findings.append(
            _finding(
                "GL304",
                f"donated argument {i} ({dtype}{list(shape)}) only matches "
                f"an output of the same shape with promoted aval ({what}): "
                "a python scalar in the update re-keys the jit cache every "
                "step and breaks the donation alias",
                path=path_hint[0] if path_hint else None,
                line=path_hint[1] if path_hint else None,
            )
        )
    return findings


def _audit_consts(closed, threshold: int, path_hint) -> list[Finding]:
    """GL102: closed-over constants above the size threshold."""
    findings = []
    const_first_use = {}
    for eqn in closed.jaxpr.eqns:
        for v in eqn.invars:
            if not isinstance(v, jex_core.Literal) and id(v) not in const_first_use:
                const_first_use[id(v)] = eqn
    for var, const in zip(closed.jaxpr.constvars, closed.consts):
        nbytes = getattr(const, "nbytes", None)
        if nbytes is None:
            nbytes = _aval_bytes(const) if hasattr(const, "shape") else 0
        if nbytes < threshold:
            continue
        path, line = _eqn_location(const_first_use.get(id(var)))
        if path is None and path_hint:
            path, line = path_hint
        findings.append(
            _finding(
                "GL102",
                f"closed-over constant {getattr(const, 'dtype', '?')}"
                f"{list(getattr(const, 'shape', ()))} ({nbytes / 2**20:.1f} MiB) "
                "is baked into the jaxpr",
                path=path, line=line,
            )
        )
    return findings


def _dst_memory_kinds(eqn) -> list:
    kinds = []
    for dst in eqn.params.get("devices", ()) or ():
        kind = getattr(dst, "memory_kind", None)
        if kind is not None:
            kinds.append(kind)
    return kinds


def _default_memory_kind() -> Optional[str]:
    try:
        return jax.devices()[0].default_memory().kind
    except Exception:  # pragma: no cover - no backend
        return None


def _audit_transfers(jaxpr, default_kind: Optional[str]) -> list[Finding]:
    """GL103: in-trace device_put that crosses memory kinds."""
    if default_kind is None:
        return []
    findings = []
    for eqn in _walk_eqns(jaxpr):
        if eqn.primitive.name != "device_put":
            continue
        crossing = sorted({k for k in _dst_memory_kinds(eqn) if k != default_kind})
        if not crossing:
            continue
        path, line = _eqn_location(eqn)
        findings.append(
            _finding(
                "GL103",
                f"device_put to memory kind {'/'.join(crossing)} inside "
                f"traced code (program default: {default_kind}) — an "
                "implicit transfer serialized into the step",
                path=path, line=line,
            )
        )
    return findings


def _audit_key_reuse(closed) -> list[Finding]:
    """GL104: a key var consumed by >1 random primitive.  Key identity is
    threaded through sub-jaxprs by positional invar mapping (pjit/scan align
    exactly; ``cond`` skips its branch index); scopes whose arity doesn't
    align conservatively start fresh roots (documented miss, never a false
    positive)."""
    consumptions: dict[int, list] = {}
    next_root = [0]

    def root_of(var, env: dict) -> int:
        if var not in env:
            env[var] = next_root[0]
            next_root[0] += 1
        return env[var]

    def walk(jaxpr, env: dict, loc_eqn=None):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in _KEY_CONSUMERS:
                for v in eqn.invars:
                    if isinstance(v, jex_core.Literal) or not _is_key_aval(v.aval):
                        continue
                    # report at the outermost enclosing call site: inner
                    # jaxprs are deduplicated across identical calls, so an
                    # inner eqn's source_info may point at the wrong one
                    consumptions.setdefault(root_of(v, env), []).append(loc_eqn or eqn)
            for sub in _sub_jaxprs(eqn):
                inner = sub.jaxpr
                operands = list(eqn.invars)
                if eqn.primitive.name == "cond" and len(operands) == len(inner.invars) + 1:
                    operands = operands[1:]
                sub_env: dict = {}
                if len(operands) == len(inner.invars):
                    for outer, v in zip(operands, inner.invars):
                        if not isinstance(outer, jex_core.Literal):
                            sub_env[v] = root_of(outer, env)
                walk(inner, sub_env, loc_eqn or eqn)

    walk(closed.jaxpr, {})
    findings = []
    for eqns in consumptions.values():
        if len(eqns) < 2:
            continue
        first_path, first_line = _eqn_location(eqns[0])
        path, line = _eqn_location(eqns[1])
        where = f" (first consumed at {first_path}:{first_line})" if first_path else ""
        findings.append(
            _finding(
                "GL104",
                f"PRNG key consumed by {len(eqns)} random primitives "
                f"({', '.join(e.primitive.name for e in eqns)}){where}: "
                "identical streams",
                path=path, line=line,
            )
        )
    return findings


def _audit_collective_matmul(closed) -> list[Finding]:
    """GL106/GL107 (hints): the two monolithic collective-matmul pipes the
    ring schedules decompose — an ``all_gather`` whose result is consumed by
    exactly one ``dot_general`` (GL106, column-parallel), and a
    ``dot_general`` whose result feeds exactly one ``reduce_scatter``
    (GL107, the row-parallel mirror).  Scope-local: jaxpr vars never cross
    sub-jaxpr boundaries except through invars, so consumers are counted
    within each (sub-)jaxpr; a value that escapes the scope or feeds
    anything else (norms, residuals, multiple consumers) is not a pure
    pipe and stays quiet."""
    findings = []

    def scan(jaxpr):
        consumers: dict[int, list] = {}
        gathers = []
        dots = []
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if not isinstance(v, jex_core.Literal):
                    consumers.setdefault(id(v), []).append(eqn)
            if eqn.primitive.name == "all_gather":
                gathers.append(eqn)
            elif eqn.primitive.name == "dot_general":
                dots.append(eqn)
            for sub in _sub_jaxprs(eqn):
                scan(sub.jaxpr)
        escaped = {id(v) for v in jaxpr.outvars if not isinstance(v, jex_core.Literal)}
        for g in gathers:
            out = g.outvars[0]
            cons = consumers.get(id(out), [])
            if id(out) in escaped or len(cons) != 1:
                continue
            if cons[0].primitive.name != "dot_general":
                continue
            path, line = _eqn_location(g)
            aval = out.aval
            findings.append(
                _finding(
                    "GL106",
                    f"all_gather result {getattr(aval, 'dtype', '?')}"
                    f"{list(getattr(aval, 'shape', ()))} feeds exactly one "
                    "dot_general: a collective-matmul candidate (the gather "
                    "could ride a ppermute ring hidden under the partial "
                    "matmuls — ops/collective_matmul.py)",
                    path=path, line=line,
                )
            )
        for d in dots:
            out = d.outvars[0]
            cons = consumers.get(id(out), [])
            if id(out) in escaped or len(cons) != 1:
                continue
            if cons[0].primitive.name != "reduce_scatter":
                continue
            path, line = _eqn_location(d)
            aval = out.aval
            findings.append(
                _finding(
                    "GL107",
                    f"dot_general result {getattr(aval, 'dtype', '?')}"
                    f"{list(getattr(aval, 'shape', ()))} feeds exactly one "
                    "reduce_scatter: the row-parallel collective-matmul "
                    "candidate (the scatter could ride a ppermute ring "
                    "hidden under the partial matmuls — "
                    "ops/collective_matmul.py ring_matmul_reduce_scatter)",
                    path=path, line=line,
                )
            )

    scan(closed.jaxpr)
    return findings


def _audit_hierarchical_reduce(closed, threshold: int) -> list[Finding]:
    """GL108 (hint): a large all-reduce whose named axes span ``dcn``
    JOINTLY with intra-slice axes.  A flat joint-axis psum decomposes (in
    XLA or in the runtime) into per-axis reductions where the cross-slice
    leg operates on the FULL operand for every intra-slice device — p
    redundant full-size copies over the slow DCN link.  A psum over
    ``('dcn',)`` alone stays quiet: that is the hierarchical path's own
    slab hop (reduce-scatter first, then the dcn-only all-reduce).  Walks
    sub-jaxprs (shard_map/pjit/scan) via :func:`iter_eqns`."""
    findings = []
    for eqn in iter_eqns(closed):
        if eqn.primitive.name != "psum":
            continue
        axes = eqn.params.get("axes") or ()
        if isinstance(axes, str):
            axes = (axes,)
        named = tuple(a for a in axes if isinstance(a, str))
        if "dcn" not in named or len(named) < 2:
            continue
        nbytes = sum(
            int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
            for v in eqn.invars
            if hasattr(v.aval, "shape") and hasattr(v.aval, "dtype")
        )
        if nbytes < threshold:
            continue
        path, line = _eqn_location(eqn)
        ici = tuple(a for a in named if a != "dcn")
        findings.append(
            _finding(
                "GL108",
                f"psum of {nbytes / 2**20:.1f} MiB over joint axes {named}: "
                f"the cross-slice leg moves one full-size copy per "
                f"{'x'.join(ici)} device over DCN — a hierarchical-reduction "
                "candidate (reduce-scatter over ICI, slab all-reduce over "
                "dcn, all-gather back; parallel/hierarchical.py)",
                path=path, line=line,
            )
        )
    return findings


_FP8_DTYPES = ("float8_e4m3fn", "float8_e5m2", "float8_e4m3fnuz",
               "float8_e5m2fnuz", "float8_e4m3b11fnuz")

# ops that pass a dot result through without changing its VALUES — the
# dequantizing multiply may legitimately sit on the far side of them
_FP8_TRANSPARENT = frozenset({
    "convert_element_type", "transpose", "reshape", "broadcast_in_dim",
    "squeeze", "slice", "stop_gradient",
})


def _is_fp8_aval(aval) -> bool:
    return str(getattr(aval, "dtype", "")) in _FP8_DTYPES


def _audit_fp8_scaling(closed) -> list[Finding]:
    """GL110: a ``dot_general`` with a float8 operand whose result reaches a
    non-multiplicative consumer with no ``mul``/``div`` anywhere in the
    chain.  fp8 codes are fixed-point residue — ``q = x * scale`` cast to
    e4m3/e5m2 — so a correct fp8 matmul ALWAYS dequantizes its accumulator
    (``out * (1 / (x_scale * w_scale))``) before
    downstream math sees it.  The chain is followed through value-preserving
    ops (convert/transpose/reshape/...); a result that escapes its scope
    stays quiet (conservative, the GL106 discipline) since the consumer is
    not visible here."""
    findings = []

    def scan(jaxpr):
        consumers: dict[int, list] = {}
        fp8_dots = []
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if not isinstance(v, jex_core.Literal):
                    consumers.setdefault(id(v), []).append(eqn)
            if eqn.primitive.name == "dot_general" and any(
                _is_fp8_aval(v.aval) for v in eqn.invars
                if not isinstance(v, jex_core.Literal)
            ):
                fp8_dots.append(eqn)
            for sub in _sub_jaxprs(eqn):
                scan(sub.jaxpr)
        escaped = {id(v) for v in jaxpr.outvars
                   if not isinstance(v, jex_core.Literal)}

        def chain_is_scaled(var, depth=0) -> Optional[bool]:
            """True: a mul/div consumes the value (possibly through
            transparent ops).  False: a value-consuming primitive reads it
            unscaled.  None: undecidable (escapes scope / no consumers) —
            stays quiet."""
            if id(var) in escaped or depth > 16:
                return None
            cons = consumers.get(id(var), [])
            if not cons:
                return None
            verdicts = []
            for c in cons:
                if c.primitive.name in ("mul", "div"):
                    verdicts.append(True)
                elif c.primitive.name in _FP8_TRANSPARENT:
                    verdicts.append(chain_is_scaled(c.outvars[0], depth + 1))
                else:
                    verdicts.append(False)
            if any(v is False for v in verdicts):
                return False  # at least one consumer reads raw codes
            if any(v is None for v in verdicts):
                return None
            return True

        for d in fp8_dots:
            if chain_is_scaled(d.outvars[0]) is not False:
                continue
            path, line = _eqn_location(d)
            dts = "x".join(
                str(getattr(v.aval, "dtype", "?")) for v in d.invars
                if not isinstance(v, jex_core.Literal)
            )
            findings.append(
                _finding(
                    "GL110",
                    f"dot_general over fp8 operands ({dts}) feeds a "
                    "non-multiplicative consumer with no dequantizing "
                    "mul/div in the chain: downstream math runs on raw fp8 "
                    "codes, off by the combined scale factor",
                    path=path, line=line,
                )
            )

    scan(closed.jaxpr)
    return findings


def _audit_output_sharding(jaxpr, threshold: int, path_hint) -> list[Finding]:
    """GL105: large outputs whose producing equation is not a sharding pin."""
    producer = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            producer[id(v)] = eqn
    invar_ids = {id(v) for v in jaxpr.invars} | {id(v) for v in jaxpr.constvars}
    findings = []
    seen: set = set()
    for v in jaxpr.outvars:
        if isinstance(v, jex_core.Literal) or id(v) in invar_ids or id(v) in seen:
            continue  # literals / pass-throughs keep their committed layout
        seen.add(id(v))
        size = _aval_bytes(v.aval)
        if size < threshold:
            continue
        eqn = producer.get(id(v))
        if eqn is not None and eqn.primitive.name in _SHARDING_PRODUCERS:
            continue
        path, line = _eqn_location(eqn)
        if path is None and path_hint:
            path, line = path_hint
        findings.append(
            _finding(
                "GL105",
                f"output {getattr(v.aval, 'dtype', '?')}"
                f"{list(getattr(v.aval, 'shape', ()))} ({size / 2**20:.1f} MiB) "
                "has no sharding constraint on its producer",
                path=path, line=line,
            )
        )
    return findings


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def audit_traced(
    traced,
    *,
    donated: Optional[list[bool]] = None,
    const_bytes_threshold: int = 1 << 20,
    output_bytes_threshold: int = 1 << 20,
    dcn_reduce_bytes_threshold: int = 1 << 20,
    default_memory_kind: Optional[str] = None,
    path_hint: Optional[tuple] = None,
) -> Report:
    """Audit a ``jax.jit(fn).trace(*args)`` result (or a bare
    ``ClosedJaxpr`` plus an explicit per-flat-input ``donated`` mask).

    Pure jaxpr walking — nothing executes.  ``default_memory_kind``
    overrides the backend's default for the GL103 comparison (useful for
    auditing TPU-shaped programs from a CPU box); thresholds are in bytes.
    """
    if hasattr(traced, "jaxpr") and hasattr(traced, "args_info"):
        closed = traced.jaxpr
        if donated is None:
            leaves = jax.tree_util.tree_leaves(
                traced.args_info, is_leaf=lambda x: hasattr(x, "donated")
            )
            donated = [bool(getattr(l, "donated", False)) for l in leaves]
    else:
        closed = traced
    if donated is None:
        donated = [False] * len(closed.jaxpr.invars)
    if len(donated) != len(closed.jaxpr.invars):
        raise ValueError(
            f"donated mask has {len(donated)} entries for "
            f"{len(closed.jaxpr.invars)} flat inputs"
        )
    if default_memory_kind is None:
        default_memory_kind = _default_memory_kind()

    findings = []
    findings += _audit_donation(closed.jaxpr, donated, path_hint)
    findings += _audit_donation_promotion(closed.jaxpr, donated, path_hint)
    findings += _audit_consts(closed, const_bytes_threshold, path_hint)
    findings += _audit_transfers(closed.jaxpr, default_memory_kind)
    findings += _audit_key_reuse(closed)
    findings += _audit_collective_matmul(closed)
    findings += _audit_fp8_scaling(closed)
    findings += _audit_hierarchical_reduce(closed, dcn_reduce_bytes_threshold)
    findings += _audit_output_sharding(closed.jaxpr, output_bytes_threshold, path_hint)
    return Report(apply_suppressions(findings))


def _path_hint_of(fn) -> Optional[tuple]:
    code = getattr(fn, "__code__", None)
    if code is None:
        inner = getattr(fn, "__wrapped__", None)
        code = getattr(inner, "__code__", None)
    if code is None:
        return None
    return code.co_filename, code.co_firstlineno


def audit_fn(fn, *example_args, donate_argnums=(), **audit_kwargs) -> Report:
    """Jit ``fn`` with ``donate_argnums``, trace it abstractly against
    ``example_args`` (concrete arrays or ``jax.ShapeDtypeStruct``), and
    audit the result."""
    traced = jax.jit(fn, donate_argnums=donate_argnums).trace(*example_args)
    audit_kwargs.setdefault("path_hint", _path_hint_of(fn))
    return audit_traced(traced, **audit_kwargs)


def audit_jitted(jitted, *example_args, **audit_kwargs) -> Report:
    """Audit an already-jitted callable — a raw ``jax.jit`` wrapper or a
    prepared train step (``Accelerator.prepare_train_step`` results expose
    their inner jit as ``._jitted``)."""
    inner = getattr(jitted, "_jitted", jitted)
    if not hasattr(inner, "trace"):
        raise TypeError(
            f"{jitted!r} is not a jitted callable (no .trace); pass the "
            "jax.jit wrapper or a prepared step exposing ._jitted"
        )
    audit_kwargs.setdefault("path_hint", _path_hint_of(inner))
    return audit_traced(inner.trace(*example_args), **audit_kwargs)


def summarize(report: Report) -> dict[str, Any]:
    """Compact digest for bench/tracker embedding."""
    return report.summary()
