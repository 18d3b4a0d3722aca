"""Distributed-contract auditor (GL4xx): static analysis over PAIRS/SETS
of programs — the cross-role hazards a single-jaxpr audit cannot see.

The GL1xx/2xx/3xx engines each audit ONE artifact (a trace, a source file,
a compiled executable).  The multi-host fabric (ROADMAP item 1: the
prefill→decode slice of ``serving/transfer.py`` promoted to real DCN
streaming) fails in ways that only exist BETWEEN artifacts: two mesh roles
whose collective schedules diverge deadlock the gang at the first
mismatched rendezvous; a resharded tensor GSPMD silently materializes
costs a full cross-link copy nobody requested; a prefill-role wire payload
the decode role parses with different geometry corrupts the KV pool; a
role that can be handed a program it never warmed recompiles mid-traffic.
All four manifest at launch time on real hardware — this module proves (or
refutes) the contracts before any process spawns, CPU-safe and trace-only
(``jax.jit(fn).trace`` / ``jax.eval_shape``: zero backend compiles, zero
allocation).

- **GL401 collective-schedule mismatch** — :func:`collective_schedule`
  extracts the ordered sequence of collective equations (psum /
  all_gather / reduce_scatter / ppermute / all_to_all, with axis names and
  payload bytes) from a role's jaxpr via the shared :func:`~.jaxpr_audit
  .iter_eqns` walk; :func:`audit_collective_schedules` flags any cross-role
  divergence in order, axis, or byte count.  Honest miss: a collective
  under ``lax.cond`` executes data-dependently — such entries are REPORTED
  (marked ``conditional``) but the schedule equality is not a proof there.
- **GL402 implicit-reshard blowup** — :func:`audit_resharding` walks a
  sharding-annotated jaxpr for >= 1 MiB tensors pinned to one spec and
  re-pinned to a different one (the shape GSPMD resolves with an
  un-requested all-gather + re-slice), reporting the predicted extra bytes
  against the ``dcn_comm_accounting``/``tp_comm_accounting`` models, which
  count no such hop.  :func:`audit_compiled_resharding` is the compiled
  twin off ``memory_analysis()``/sharding metadata (``compiled_audit.py``
  plumbing).
- **GL403 wire-schema incompatibility** — :func:`wire_schema` derives the
  static schema of the ``PagedKVTransport`` handoff (page geometry,
  ``kv_dtype`` codes + scales, payload shapes/dtypes, per-page wire bytes,
  prefix/adapter conventions) from a role's plugin + model config;
  :func:`audit_wire_schema` fails the pair when the roles disagree.  The
  transport's own runtime ``ValueError`` consults the SAME derivation
  (:func:`check_wire_schemas`), so gate and runtime can never drift.
- **GL404 role-asymmetric warmup** — :func:`warmup_plan` models the set of
  programs a role's ``ServingEngine.warmup()`` (+ transport warmup) warms;
  :func:`role_programs` models the set the pair schedule can dispatch to
  that role; :func:`audit_warmup_coverage` proves coverage statically (the
  ``strict_compiles`` contract, per role, before anything compiles).

Surfaces: ``preflight --serve --disaggregate`` (:func:`pair_preflight`
audits both roles as a pair), ``lint`` (the same pair contract on every
sweep), and the multichip dryrun's ``_distributed_audit_leg``.  Suppression is source-anchored like
every other engine; findings carry ``engine="distributed"``.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Optional

from .jaxpr_audit import _aval_bytes, _eqn_location, _sub_jaxprs
from .report import Finding
from .rules import RULES


def _finding(rule_id: str, message: str, *, path=None, line=None) -> Finding:
    r = RULES[rule_id]
    return Finding(
        rule=rule_id, severity=r.severity, message=message, fix_hint=r.fix_hint,
        path=path, line=line, engine="distributed",
    )


# ---------------------------------------------------------------------------
# GL401 — collective-schedule extraction + cross-role comparison
# ---------------------------------------------------------------------------

# primitive name -> normalized op name (psum_scatter traces as its own
# primitive in some jax versions and as reduce_scatter in others — one
# wire name so two roles on skewed toolchains still compare equal)
_COLLECTIVE_PRIMS = {
    "psum": "psum",
    "all_gather": "all_gather",
    "reduce_scatter": "reduce_scatter",
    "psum_scatter": "reduce_scatter",
    "ppermute": "ppermute",
    "all_to_all": "all_to_all",
}


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One entry of a role's collective schedule: what rendezvouses, over
    which named axes, moving how many payload bytes.  ``conditional`` marks
    an op found under a ``lax.cond`` branch — executed data-dependently,
    so it is reported but its presence/absence at runtime is not proven
    (the documented GL401 miss)."""

    op: str
    axes: tuple
    nbytes: int
    path: Optional[str] = None
    line: Optional[int] = None
    conditional: bool = False

    def describe(self) -> str:
        cond = ", data-dependent under cond" if self.conditional else ""
        return f"{self.op} over {self.axes} ({self.nbytes / 2**20:.2f} MiB{cond})"

    def key(self) -> tuple:
        return (self.op, self.axes, self.nbytes)


def _collective_axes(eqn) -> tuple:
    axes = eqn.params.get("axes", None)
    if axes is None:
        axes = eqn.params.get("axis_name", None)
    if axes is None:
        return ()
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def collective_schedule(closed_or_traced) -> list:
    """The ordered :class:`CollectiveOp` sequence of a traced program (a
    ``jax.jit(fn).trace`` result, a ``ClosedJaxpr``, or a bare jaxpr) —
    depth-first through every sub-jaxpr, so shard_map/pjit/scan bodies
    contribute in program order.  This IS the gang's rendezvous schedule:
    two roles whose sequences diverge in op, axis set, or byte count meet
    different collectives at the same rendezvous index and deadlock (or
    silently corrupt the reduction)."""
    obj = closed_or_traced
    if hasattr(obj, "jaxpr") and hasattr(obj, "args_info"):  # a Traced
        obj = obj.jaxpr
    jaxpr = getattr(obj, "jaxpr", obj)
    schedule: list = []

    def collect(jaxpr, conditional: bool):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            op = _COLLECTIVE_PRIMS.get(name)
            if op is not None:
                path, line = _eqn_location(eqn)
                nbytes = sum(
                    _aval_bytes(v.aval) for v in eqn.invars
                    if hasattr(v, "aval")
                )
                schedule.append(CollectiveOp(
                    op=op, axes=_collective_axes(eqn), nbytes=nbytes,
                    path=path, line=line, conditional=conditional,
                ))
            for sub in _sub_jaxprs(eqn):
                collect(sub.jaxpr, conditional or name == "cond")

    collect(jaxpr, False)
    return schedule


def audit_collective_schedules(schedules: dict, *, context: str = "",
                               path_hint: Optional[tuple] = None) -> list:
    """GL401: compare each role's collective schedule against the first
    role's (insertion order; the reference role is the contract).  One
    finding per diverging role, located at the first rendezvous index
    where the (op, axes, bytes) triple differs — the exact point the gang
    would deadlock.  ``schedules`` maps role name -> list[CollectiveOp]
    (or a traced program, extracted via :func:`collective_schedule`)."""
    items = [
        (role, s if isinstance(s, list) else collective_schedule(s))
        for role, s in schedules.items()
    ]
    if len(items) < 2:
        return []
    findings = []
    ref_role, ref = items[0]
    where = f" [{context}]" if context else ""
    for role, sched in items[1:]:
        diverge = None
        for i, (a, b) in enumerate(zip(ref, sched)):
            if a.key() != b.key():
                diverge = (i, a.describe(), b.describe())
                break
        if diverge is None and len(ref) != len(sched):
            i = min(len(ref), len(sched))
            longer_role, longer = (ref_role, ref) if len(ref) > len(sched) \
                else (role, sched)
            diverge = (
                i,
                f"{len(ref)} collective(s) on {ref_role!r}",
                f"{len(sched)} on {role!r} — {longer_role!r} blocks in "
                f"{longer[i].describe()} with no counterpart",
            )
        if diverge is None:
            continue
        i, a_desc, b_desc = diverge
        cond_note = ""
        if any(op.conditional for op in (ref + sched)):
            cond_note = (
                " (note: schedule includes data-dependent collectives under "
                "lax.cond — reported, not proven)"
            )
        loc = None
        for op in sched[i:i + 1] or ref[i:i + 1]:
            loc = (op.path, op.line)
        if (loc is None or loc[0] is None) and path_hint:
            loc = path_hint
        findings.append(_finding(
            "GL401",
            f"collective schedule diverges between roles {ref_role!r} and "
            f"{role!r} at rendezvous {i}{where}: {a_desc} vs {b_desc} — a "
            "launched gang meets mismatched collectives at this index and "
            f"deadlocks or corrupts the payload{cond_note}",
            path=loc[0] if loc else None, line=loc[1] if loc else None,
        ))
    return findings


# ---------------------------------------------------------------------------
# GL402 — implicit-reshard blowup
# ---------------------------------------------------------------------------


def _sharding_of(eqn):
    s = eqn.params.get("sharding", None)
    if s is None:
        shardings = eqn.params.get("shardings", None)
        if isinstance(shardings, (list, tuple)) and shardings:
            s = shardings[0]
    return s


def audit_resharding(closed_or_traced, *, bytes_threshold: int = 1 << 20,
                     dcn_gbps: float = 25.0,
                     path_hint: Optional[tuple] = None) -> list:
    """GL402: a >= ``bytes_threshold`` tensor pinned to one sharding and
    re-pinned to a DIFFERENT one downstream — the shape GSPMD resolves by
    materializing an un-requested all-gather + re-slice between the two
    pins.  The predicted extra bytes (one full copy of the operand over
    the interconnect) are reported against the comm models
    (``dcn_comm_accounting`` / ``tp_comm_accounting``), which account no
    such hop: the reshard is invisible to every byte twin until the
    profile shows it.  Scope-local like GL106: the constraint pair must be
    visible in one (sub-)jaxpr."""
    obj = closed_or_traced
    if hasattr(obj, "jaxpr") and hasattr(obj, "args_info"):
        obj = obj.jaxpr
    jaxpr = getattr(obj, "jaxpr", obj)
    findings: list = []

    def scan(jaxpr):
        pinned: dict = {}  # id(var) -> (sharding_str, eqn)
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "sharding_constraint":
                sharding = _sharding_of(eqn)
                spec = str(sharding)
                invar = eqn.invars[0]
                prior = pinned.get(id(invar))
                nbytes = _aval_bytes(invar.aval) if hasattr(invar, "aval") else 0
                if (prior is not None and prior[0] != spec
                        and nbytes >= bytes_threshold):
                    path, line = _eqn_location(eqn)
                    if path is None and path_hint:
                        path, line = path_hint
                    mib = nbytes / 2**20
                    stream_ms = nbytes * 8 / (dcn_gbps * 1e9) * 1e3
                    findings.append(_finding(
                        "GL402",
                        f"tensor {getattr(invar.aval, 'dtype', '?')}"
                        f"{list(getattr(invar.aval, 'shape', ()))} "
                        f"({mib:.1f} MiB) is pinned to {prior[0]} and "
                        f"re-pinned to {spec}: GSPMD materializes an "
                        f"un-requested reshard (~{mib:.1f} MiB extra over "
                        f"the interconnect, ~{stream_ms:.2f} ms at "
                        f"{dcn_gbps} Gb/s DCN reference) that no comm "
                        "accounting model counts",
                        path=path, line=line,
                    ))
                for out in eqn.outvars:
                    pinned[id(out)] = (spec, eqn)
            for sub in _sub_jaxprs(eqn):
                scan(sub.jaxpr)

    scan(jaxpr)
    return findings


def audit_compiled_resharding(compiled, *, label: str = "",
                              bytes_threshold: int = 1 << 20,
                              path_hint: Optional[tuple] = None) -> list:
    """GL402 (compiled side, ``compiled_audit.py`` plumbing): read the
    executable's input/output shardings and flag a donated-style feedback
    pair — an input and an output of identical aval whose shardings
    differ.  Feeding such an output back as next step's input reshards the
    tensor every iteration.  Conservative: avals must match exactly and
    both shardings must be readable; anything else stays quiet (XLA-side
    layout detail, not provable here)."""
    try:
        in_avals = list(getattr(compiled, "in_avals", None) or ())
        out_avals = list(getattr(compiled, "out_avals", None) or ())
        in_sh = list(compiled.input_shardings[0]) if compiled.input_shardings else []
        out_sh = list(compiled.output_shardings) if compiled.output_shardings \
            is not None else []
    except Exception:  # pragma: no cover - executable without metadata
        return []
    if not in_avals or not out_avals:
        return []
    findings = []
    out_index = {}
    for aval, sh in zip(out_avals, out_sh):
        key = (tuple(getattr(aval, "shape", ())), str(getattr(aval, "dtype", "")))
        out_index.setdefault(key, []).append(sh)
    for aval, sh in zip(in_avals, in_sh):
        nbytes = _aval_bytes(aval)
        if nbytes < bytes_threshold:
            continue
        key = (tuple(getattr(aval, "shape", ())), str(getattr(aval, "dtype", "")))
        outs = out_index.get(key, [])
        if len(outs) != 1:
            continue  # ambiguous pairing: stay quiet
        if str(outs[0]) == str(sh):
            continue
        findings.append(_finding(
            "GL402",
            f"{label or 'compiled program'}: input "
            f"{getattr(aval, 'dtype', '?')}{list(getattr(aval, 'shape', ()))} "
            f"({nbytes / 2**20:.1f} MiB) comes back as an output with a "
            f"different sharding ({sh} -> {outs[0]}): feeding it back "
            "reshards the tensor every step",
            path=path_hint[0] if path_hint else None,
            line=path_hint[1] if path_hint else None,
        ))
    return findings


# ---------------------------------------------------------------------------
# GL403 — wire-schema derivation + cross-role comparison
# ---------------------------------------------------------------------------


def wire_schema(model_config, plugin) -> dict:
    """The static schema of the prefill→decode handoff for one role: what
    the ``PagedKVTransport`` send/recv programs put on (expect off) the
    wire, derived from the role's plugin + model config alone — nothing is
    allocated or traced.  Two roles with equal schemas parse each other's
    payloads bit-exactly; ANY differing field corrupts the decode-side KV
    pool, which is why both the GL403 gate (:func:`audit_wire_schema`) and
    the transport's runtime check (:func:`check_wire_schemas`) compare
    this same dict."""
    import jax.numpy as jnp

    from ..serving.paged_cache import kv_page_bytes

    kvd = getattr(plugin, "kv_dtype", "") or "bf16"
    kvd = kvd if kvd in ("int8", "fp8") else "bf16"
    quantized = kvd in ("int8", "fp8")
    cfg = model_config
    L = cfg.num_hidden_layers
    hkv = cfg.num_key_value_heads
    d = cfg.head_dim
    ps = plugin.page_size
    pps = plugin.pages_per_slot
    if quantized:
        from ..ops.paged_cache import KV_QUANT_DTYPES

        page_dtype = str(jnp.dtype(KV_QUANT_DTYPES[kvd]))
    else:
        page_dtype = str(jnp.dtype(cfg.dtype))
    payload = {
        "k": ((L, hkv, pps, ps, d), page_dtype),
        "v": ((L, hkv, pps, ps, d), page_dtype),
    }
    if quantized:
        payload["k_scales"] = ((L, hkv, pps), "float32")
        payload["v_scales"] = ((L, hkv, pps), "float32")
    return {
        "page_size": ps,
        "pages_per_slot": pps,
        "kv_dtype": kvd,
        "page_dtype": page_dtype,
        "layers": L,
        "kv_heads": hkv,
        "head_dim": d,
        "payload": payload,
        "page_bytes": kv_page_bytes(
            cfg, ps, jnp.dtype(cfg.dtype).itemsize, kvd if quantized else ""
        ),
        # conventions that must agree for adopted pages to stay meaningful
        # across the pair: the prefix hash chain folds the page dtype in,
        # and adapters key the per-slot program selection
        "prefix_cache": getattr(plugin, "prefix_cache", "off"),
        "adapters": bool(getattr(plugin, "lora", None)),
    }


def schema_mismatches(src_schema: dict, dst_schema: dict) -> list:
    """``[(field, src_value, dst_value), ...]`` for every differing field."""
    keys = sorted(set(src_schema) | set(dst_schema))
    return [
        (k, src_schema.get(k), dst_schema.get(k))
        for k in keys
        if src_schema.get(k) != dst_schema.get(k)
    ]


def audit_wire_schema(src_schema: dict, dst_schema: dict, *,
                      src_role: str = "prefill", dst_role: str = "decode",
                      path_hint: Optional[tuple] = None) -> list:
    """GL403: fail the pair when the two roles' wire schemas disagree —
    one finding listing every mismatched field, so a mis-deployed pair is
    rejected by the gate instead of corrupting pages at the first
    handoff."""
    diffs = schema_mismatches(src_schema, dst_schema)
    if not diffs:
        return []
    detail = "; ".join(
        f"{field}: {src_role}={sv!r} vs {dst_role}={dv!r}"
        for field, sv, dv in diffs
    )
    return [_finding(
        "GL403",
        f"wire schema of the {src_role}-role engine is incompatible with "
        f"the {dst_role}-role engine ({detail}): the decode side would "
        "scatter the payload into a pool with different geometry/encoding "
        "— KV corruption at the first page handoff",
        path=path_hint[0] if path_hint else None,
        line=path_hint[1] if path_hint else None,
    )]


def check_wire_schemas(src_schema: dict, dst_schema: dict) -> None:
    """Runtime twin of :func:`audit_wire_schema` — raises ``ValueError``
    on any schema mismatch.  ``PagedKVTransport.__init__`` calls this, so
    the transport's runtime rejection and the preflight gate read the SAME
    derivation and can never drift apart.  Messages keep the historical
    phrasing ("page geometry must match" / "KV page dtypes must match") so
    operators grepping logs find the same contract either way."""
    geom_src = (src_schema["page_size"], src_schema["pages_per_slot"])
    geom_dst = (dst_schema["page_size"], dst_schema["pages_per_slot"])
    if geom_src != geom_dst:
        raise ValueError(
            "prefill/decode page geometry must match for the in-process "
            f"handoff: src={geom_src} vs dst={geom_dst}"
        )
    if src_schema["kv_dtype"] != dst_schema["kv_dtype"]:
        raise ValueError(
            "prefill/decode KV page dtypes must match for the handoff "
            "(the wire payload is the raw page codes + scales): "
            f"src={src_schema['kv_dtype']!r} vs dst={dst_schema['kv_dtype']!r}"
        )
    diffs = schema_mismatches(src_schema, dst_schema)
    if diffs:
        raise ValueError(
            "prefill/decode wire schemas must match for the handoff: "
            + "; ".join(f"{f}: src={sv!r} vs dst={dv!r}" for f, sv, dv in diffs)
        )


def handoff_schedule(model_config, plugin, *, axis: str = "dcn") -> list:
    """The handoff's wire legs as a synthetic collective schedule: one
    :class:`CollectiveOp` per payload member (``k``, ``v``, and the scales
    when quantized), in wire order, with the exact byte counts the send
    gathers and the recv scatters.  On a real fabric each leg is a matched
    cross-slice send/recv over the ``dcn`` axis — so the GL401 comparator
    applies verbatim: roles whose leg sequences diverge in order or bytes
    wedge the stream exactly like mismatched collectives wedge a gang."""
    import numpy as np

    schema = wire_schema(model_config, plugin)
    legs = []
    for name, (shape, dtype) in schema["payload"].items():
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        legs.append(CollectiveOp(op=f"wire:{name}", axes=(axis,), nbytes=nbytes))
    return legs


# ---------------------------------------------------------------------------
# GL404 — role-asymmetric warmup coverage
# ---------------------------------------------------------------------------


def warmup_plan(plugin, *, adapters: bool = False,
                transport: bool = False, role: str = "") -> frozenset:
    """The static set of program labels a role's ``ServingEngine.warmup()``
    warms (mirrors the warmup body in ``serving/engine.py`` — decode first
    and steady-state, one prefill per bucket, the sampler, the verify
    ladder + draft when speculating, the prefix triple or the plain
    release, the adapter insert) plus — when ``transport`` is set — the
    wire program ``PagedKVTransport.warmup()`` compiles on this role
    (send on the prefill role, recv on the decode role, both when the role
    is unspecified)."""
    progs = {"decode", "sample_first"}
    progs |= {f"prefill[{b}]" for b in plugin.prefill_buckets}
    if getattr(plugin, "speculate", "off") != "off":
        progs |= {f"verify[{b}]" for b in plugin.speculate_buckets}
        progs |= {"draft_provider"}
    if str(getattr(plugin, "prefix_cache", "off")) == "on":
        progs |= {"prefix_adopt", "prefix_release_cow", "prefix_push_free"}
    else:
        progs |= {"release"}
    if adapters:
        progs |= {"adapter_insert"}
    if transport:
        if role in ("", "prefill"):
            progs |= {"wire_send"}
        if role in ("", "decode"):
            progs |= {"wire_recv"}
    return frozenset(progs)


def role_programs(plugin, role: str, *, adapters: bool = False,
                  transport: bool = True) -> frozenset:
    """The set of program labels the disaggregated-pair schedule can
    dispatch to ``role`` (the ground truth GL404 checks warmup coverage
    against).  The prefill role runs the bucket ladder, samples the first
    token, releases/COW-releases held slots, and gathers wire payloads;
    the decode role runs decode ticks, adopts + scatters incoming pages,
    verifies when speculating, and releases finished slots."""
    if role == "prefill":
        progs = {f"prefill[{b}]" for b in plugin.prefill_buckets}
        progs |= {"sample_first"}
        if transport:
            progs |= {"wire_send"}
    elif role == "decode":
        progs = {"decode"}
        if getattr(plugin, "speculate", "off") != "off":
            progs |= {f"verify[{b}]" for b in plugin.speculate_buckets}
            progs |= {"draft_provider"}
        if transport:
            progs |= {"wire_recv"}
    else:
        raise ValueError(f"unknown role {role!r} (expected 'prefill' or 'decode')")
    if str(getattr(plugin, "prefix_cache", "off")) == "on":
        progs |= {"prefix_adopt", "prefix_release_cow", "prefix_push_free"}
    else:
        progs |= {"release"}
    if adapters:
        progs |= {"adapter_insert"}
    return frozenset(progs)


def audit_warmup_coverage(role: str, warmed: Iterable[str],
                          dispatchable: Iterable[str], *,
                          path_hint: Optional[tuple] = None) -> list:
    """GL404: every program the schedule can dispatch to ``role`` must be
    in the role's warmed set — a dispatchable-but-cold program is a
    guaranteed mid-traffic compile on that role (the ``strict_compiles``
    contract, proven statically).  One finding listing every missing
    program."""
    missing = sorted(frozenset(dispatchable) - frozenset(warmed))
    if not missing:
        return []
    return [_finding(
        "GL404",
        f"role {role!r} warmup does not cover its dispatchable program "
        f"set: {', '.join(missing)} can be dispatched but are never "
        "warmed — a guaranteed mid-traffic compile (strict_compiles "
        "contract) on this role",
        path=path_hint[0] if path_hint else None,
        line=path_hint[1] if path_hint else None,
    )]


# ---------------------------------------------------------------------------
# the pair preflight — GL401-404 over a prefill/decode role pair
# ---------------------------------------------------------------------------


def _transfer_path_hint():
    from ..serving import transfer

    return (transfer.__file__, 1)


def pair_preflight(model_config, prefill_plugin, decode_plugin, *,
                   adapters: bool = False, trace_wire: bool = True) -> tuple:
    """Audit a disaggregated prefill/decode pair BEFORE anything compiles
    or allocates: GL403 wire-schema agreement, GL401 over the handoff's
    wire-leg schedule (and, when ``trace_wire`` and the schemas agree,
    over the abstractly traced send/recv programs — ``jax.jit(...).trace``
    on ``eval_shape`` stand-ins: zero backend compiles), GL402 resharding
    on those traces, and GL404 warmup coverage per role.  Returns
    ``(findings, summary)`` — the summary is the JSON-able digest
    the dryrun leg embeds."""
    import jax

    path_hint = _transfer_path_hint()
    findings: list = []
    schema_src = wire_schema(model_config, prefill_plugin)
    schema_dst = wire_schema(model_config, decode_plugin)
    findings += audit_wire_schema(schema_src, schema_dst, path_hint=path_hint)

    legs = {
        "prefill": handoff_schedule(model_config, prefill_plugin),
        "decode": handoff_schedule(model_config, decode_plugin),
    }
    findings += audit_collective_schedules(
        legs, context="wire handoff", path_hint=path_hint
    )

    schemas_agree = schema_src == schema_dst
    traced_collectives = {}
    if trace_wire and schemas_agree:
        import jax.numpy as jnp

        from ..models.llama import init_paged_cache
        from ..serving.transfer import _transfer_step_fns

        send_step, recv_step = _transfer_step_fns()
        sds = jax.ShapeDtypeStruct
        kvd = schema_src["kv_dtype"]

        def cache_sds(plugin):
            return jax.eval_shape(lambda: init_paged_cache(
                model_config, plugin.num_pages, plugin.page_size,
                plugin.num_slots, plugin.pages_per_slot,
                kv_dtype=kvd if kvd in ("int8", "fp8") else None,
            ))

        traced_send = jax.jit(send_step).trace(
            cache_sds(prefill_plugin), sds((), jnp.int32)
        )
        payload_sds = jax.eval_shape(
            lambda c, s: send_step(c, s), cache_sds(prefill_plugin),
            sds((), jnp.int32),
        )
        traced_recv = jax.jit(recv_step).trace(
            cache_sds(decode_plugin), sds((), jnp.int32), payload_sds,
            sds((), jnp.int32), sds((), jnp.int32),
        )
        for role, traced in (("prefill", traced_send), ("decode", traced_recv)):
            findings += audit_resharding(traced, path_hint=path_hint)
            traced_collectives[role] = collective_schedule(traced)
        # the in-process wire programs are local gathers/scatters: any
        # collective appearing in ONE role's trace but not the other's is
        # a schedule split the fabric port would deadlock on
        findings += audit_collective_schedules(
            traced_collectives, context="wire programs", path_hint=path_hint
        )

    role_summaries = {}
    for role, plugin in (("prefill", prefill_plugin), ("decode", decode_plugin)):
        warmed = warmup_plan(plugin, adapters=adapters, transport=True, role=role)
        dispatchable = role_programs(plugin, role, adapters=adapters)
        findings += audit_warmup_coverage(
            role, warmed, dispatchable, path_hint=path_hint
        )
        role_summaries[role] = {
            "warmed": sorted(warmed),
            "dispatchable": sorted(dispatchable),
            "page_bytes": wire_schema(model_config, plugin)["page_bytes"],
        }

    if schemas_agree:
        # static-vs-runtime telemetry twin: the gate's predicted wire unit;
        # PagedKVTransport records the measured side at construction
        from ..telemetry import twin_registry

        twin_registry().record_predicted(
            "distributed.wire_bytes_per_page", schema_src["page_bytes"],
            source="analysis/distributed_audit.pair_preflight",
        )

    summary = {
        "roles": role_summaries,
        "schema_ok": schemas_agree,
        "kv_dtype": schema_dst["kv_dtype"],
        "wire_legs": [
            {"leg": op.op, "bytes": op.nbytes} for op in legs["decode"]
        ],
        "traced_wire_collectives": {
            role: len(s) for role, s in traced_collectives.items()
        },
        "rules": sorted({f.rule for f in findings}),
        "findings": len(findings),
    }
    return findings, summary


__all__ = [
    "CollectiveOp",
    "audit_collective_schedules",
    "audit_compiled_resharding",
    "audit_resharding",
    "audit_warmup_coverage",
    "audit_wire_schema",
    "check_wire_schemas",
    "collective_schedule",
    "handoff_schedule",
    "pair_preflight",
    "role_programs",
    "schema_mismatches",
    "warmup_plan",
    "wire_schema",
]
