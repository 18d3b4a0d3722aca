"""``accelerate-tpu preflight`` — deploy preflight: audit the artifacts a
deploy will actually run, BEFORE taking traffic.

The go-live discipline (docs/serving.md): ``lint`` checks the source and the
trace, ``preflight`` checks the lowered XLA executables and the shape
discipline that keeps them stable —

1. the graft-lint sweep over the given paths (same target resolver as the
   ``lint`` command: a typo'd path is a loud GL002 failure in both, never a
   silently skipped target);
2. AOT ``lower().compile()`` of every production program — the canonical
   train step through the real ``prepare_train_step`` machinery
   (``--train``), and the serving ladder (``--serve``): one prefill per
   ``ServingPlugin.prefill_buckets`` entry plus the decode and release
   programs, exactly ``len(buckets) + 2`` executables — plus one
   speculative verify program per ``speculate_buckets`` entry when
   ``ACCELERATE_SERVE_SPECULATE`` is on;
3. the compiled audit of each executable: GL301 donation-not-aliased,
   GL302 HBM-over-budget (``--hbm-gb`` or the backend's measured limit),
   GL303 program count vs the predicted bucket ladder, plus the per-program
   flops/bytes cost report the predicted-MFU arithmetic feeds on;
4. the jaxpr audit of each traced program rides along (GL1xx + GL304), so
   a hazard visible at either level fails the same run.

Exit code 1 when any unsuppressed finding at or above ``--fail-on``
severity (default: error — GL301/GL302 are errors) remains.  All CPU-safe:
AOT compilation needs a backend but executes nothing, so the preflight runs
on the CI box with ``ShapeDtypeStruct`` stand-ins (the serving params and
KV pool are never allocated).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import sys

from ..utils.dataclasses import PreflightConfig


def preflight_command_parser(subparsers=None) -> argparse.ArgumentParser:
    description = (
        "Deploy preflight: graft-lint sweep + AOT compile of every "
        "production program + compiled-artifact audit (GL301-GL303; see "
        "docs/static_analysis.md, 'Deploy preflight')."
    )
    if subparsers is not None:
        parser = subparsers.add_parser(
            "preflight", description=description, help=description
        )
    else:
        parser = argparse.ArgumentParser(
            "accelerate-tpu preflight", description=description
        )
    parser.add_argument(
        "paths", nargs="*", default=["."],
        help="files/directories for the lint sweep (default: .)",
    )
    parser.add_argument(
        "--serve", action="store_true",
        help="preflight the serving ladder: one prefill program per "
             "ServingPlugin.prefill_buckets entry (ACCELERATE_SERVE_* env "
             "sets the geometry) + decode + release — exactly "
             "len(buckets)+2 executables (+ one speculative verify program "
             "per speculate bucket when ACCELERATE_SERVE_SPECULATE is on)",
    )
    parser.add_argument(
        "--disaggregate", action="store_true",
        help="with --serve: audit the prefill-role / decode-role pair as a "
             "unit (GL401-GL404 — wire schema, handoff schedule, traced "
             "wire programs, per-role warmup coverage).  The prefill role "
             "starts from the same ACCELERATE_SERVE_* geometry and applies "
             "ACCELERATE_SERVE_PREFILL_{PAGE_SIZE,PAGES_PER_SLOT,KV_DTYPE} "
             "overrides on top.  Trace-only: adds zero backend compiles",
    )
    parser.add_argument(
        "--train", action="store_true",
        help="preflight the canonical train step (the real "
             "prepare_train_step machinery, donation on; --optimizer "
             "selects the recipe)",
    )
    parser.add_argument(
        "--program", action="append", default=[], metavar="FILE::FN[::donate=I,J]",
        help="additionally preflight FN from FILE (the fixture convention: "
             "the module's example_args()[FN] supplies the inputs); "
             "repeatable.  donate= lists donated positional indices",
    )
    parser.add_argument("--json", action="store_true", help="machine-readable report")
    parser.add_argument(
        "--hbm-gb", type=float, default=None,
        help="HBM budget in GiB for GL302 (default: the backend's measured "
             "bytes_limit; CPU reports none, so GL302 is skipped there "
             "unless this is set)",
    )
    parser.add_argument(
        "--fail-on", choices=["error", "warning", "info"], default=None,
        help="lowest severity that fails the run (default: error)",
    )
    parser.add_argument(
        "--optimizer", default=None,
        help="optimizer recipe for the train-step program (default: lion)",
    )
    parser.add_argument(
        "--no-lint", action="store_true",
        help="skip the source sweep (compiled audit only)",
    )
    parser.add_argument(
        "--show-suppressed", action="store_true",
        help="include suppressed findings (with their rationales) in the output",
    )
    if subparsers is not None:
        parser.set_defaults(func=preflight_command)
    return parser


# ---------------------------------------------------------------------------
# program builders
# ---------------------------------------------------------------------------


def _audit_program(prog, config: PreflightConfig, hbm_budget_bytes=None):
    """Both audits of one AOT-compiled program (the
    :class:`~..analysis.compiled_audit.CompiledProgram` carries the traced
    handle precisely so the jaxpr audit rides the same single trace):
    GL1xx/GL304 off ``prog.traced``, GL301/GL302 + the cost row off
    ``prog.compiled``.  Returns ``(findings, [row])``."""
    from ..analysis import audit_compiled, audit_compiled_resharding, audit_traced

    findings = list(
        audit_traced(prog.traced, path_hint=prog.path_hint).findings
    )
    # GL402 compiled side: XLA's actual input/output sharding decisions,
    # read off the executable's metadata (quiet when the backend exposes
    # none — single-device CPU runs)
    findings += audit_compiled_resharding(
        prog.compiled, label=prog.label, path_hint=prog.path_hint
    )
    f, row = audit_compiled(
        prog.compiled, label=prog.label, hbm_budget_bytes=hbm_budget_bytes,
        donation_slack_bytes=config.donation_slack_bytes,
        path_hint=prog.path_hint,
    )
    row["compile_s"] = round(prog.compile_s, 4)
    row["compile_events"] = prog.compile_events
    findings += f
    return findings, [row]


def preflight_train(config: PreflightConfig, hbm_budget_bytes=None):
    """AOT-compile and audit the canonical train step.  Returns
    ``(findings, rows)`` — jaxpr + compiled findings and one report row."""
    from ..analysis.compiled_audit import audit_program_set, aot_compile_program
    from ..state import AcceleratorState, GradientState
    from .lint import build_canonical_step

    try:
        acc, step, state, batch = build_canonical_step(config.optimizer)
        jitted = step._jitted
        path_hint = None
        code = getattr(getattr(jitted, "__wrapped__", None), "__code__", None)
        if code is not None:
            path_hint = (code.co_filename, code.co_firstlineno)
        prog = aot_compile_program(
            jitted, state, batch, label=f"train_step[{config.optimizer}]",
            path_hint=path_hint,
        )
        findings, rows = _audit_program(prog, config, hbm_budget_bytes)
        findings += audit_program_set(
            rows, 1, measured_compile_events=prog.compile_events,
            path_hint=path_hint,
        )
        return findings, rows
    finally:
        # the canonical step builds a real Accelerator: reset the singletons
        # so in-process callers (tests, bench) start clean afterwards — even
        # when the compile or audit raises
        AcceleratorState._reset_state(reset_partial_state=True)
        GradientState._reset_state()


def _serve_setup():
    """The serving model/plugin the preflight audits: geometry from the
    ``ACCELERATE_SERVE_*`` env family (the ServingPlugin contract), the
    tiny model on CPU and the 600m-class decode shape on TPU."""
    import jax
    import jax.numpy as jnp

    from ..generation import GenerationConfig
    from ..models import LlamaConfig
    from ..utils.dataclasses import ServingPlugin

    if jax.default_backend() == "tpu":
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1536, intermediate_size=4096,
            num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=8,
            max_position_embeddings=4096, attn_implementation="flash",
            dtype=jnp.bfloat16,
        )
    else:
        cfg = LlamaConfig.tiny()
    return cfg, ServingPlugin(), GenerationConfig()


def preflight_serve(config: PreflightConfig, hbm_budget_bytes=None,
                    model=None, plugin=None, gen_config=None):
    """AOT-compile and audit the serving ladder: one prefill per bucket +
    decode + release (exactly ``len(prefill_buckets) + 2`` programs), plus
    — when ``ServingPlugin.speculate`` is on (``ACCELERATE_SERVE_SPECULATE``)
    — one speculative **verify** program per ``speculate_buckets`` entry, so
    GL301-303 and the compile-count prediction hold for a speculative
    deploy exactly as for a plain one.

    Everything compiles from ``ShapeDtypeStruct`` stand-ins — the params
    and the KV pool are never allocated, so a production-sized ladder
    preflights on a CPU box.  Returns ``(findings, rows)``.
    """
    import jax
    import jax.numpy as jnp

    from ..analysis.compiled_audit import audit_program_set, aot_compile_program
    from ..models import LlamaForCausalLM
    from ..models.llama import init_paged_cache
    from ..serving.engine import fresh_engine_jits

    if model is None or plugin is None or gen_config is None:
        cfg, env_plugin, env_gen = _serve_setup()
        model = model or LlamaForCausalLM(cfg)
        plugin = plugin or env_plugin
        gen_config = gen_config or env_gen
    p = plugin
    # fresh wrappers on purpose: an engine-shared wrapper may hold an
    # executable deserialized from the persistent cache, which has no
    # donation alias table (every donation would read as GL301)
    decode, prefill, release, _sample, verify = fresh_engine_jits(
        model, gen_config, p.page_size
    )

    cache_sds = jax.eval_shape(
        lambda: init_paged_cache(
            model.config, p.num_pages, p.page_size, p.num_slots, p.pages_per_slot
        )
    )
    params_sds = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))
    )
    rng_sds = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    n = p.num_slots
    sds = jax.ShapeDtypeStruct

    specs = [
        ("decode", decode,
         (params_sds, cache_sds, sds((n,), jnp.int32), sds((n,), jnp.bool_),
          rng_sds)),
        ("release", release, (cache_sds, sds((n,), jnp.bool_))),
    ]
    for bucket in p.prefill_buckets:
        specs.append((
            f"prefill[{bucket}]", prefill,
            (params_sds, cache_sds, sds((), jnp.int32), sds((bucket,), jnp.int32),
             sds((), jnp.int32), sds((), jnp.int32)),
        ))
    expected = len(p.prefill_buckets) + 2
    if p.speculate != "off":
        for bucket in p.speculate_buckets:
            specs.append((
                f"verify[{bucket}]", verify,
                (params_sds, cache_sds, sds((n, bucket + 1), jnp.int32),
                 sds((n,), jnp.int32), sds((n,), jnp.bool_), rng_sds),
            ))
        expected += len(p.speculate_buckets)

    findings, rows, events = [], [], 0
    for label, jitted, args in specs:
        prog = aot_compile_program(jitted, *args, label=label)
        events += prog.compile_events
        f, r = _audit_program(prog, config, hbm_budget_bytes)
        findings += f
        rows += r
    findings += audit_program_set(
        rows, expected, measured_compile_events=events
    )
    return findings, rows


def _prefill_role_plugin(decode_plugin):
    """The prefill-role geometry for the pair audit: the decode role's
    plugin with ``ACCELERATE_SERVE_PREFILL_{PAGE_SIZE,PAGES_PER_SLOT,
    KV_DTYPE}`` overrides applied on top.  With no overrides set the two
    roles share one geometry — the in-tree :class:`DisaggregatedPair`
    shape — and the pair audit is expected green."""
    import dataclasses
    import os

    overrides = {}
    for field, env, cast in (
        ("page_size", "ACCELERATE_SERVE_PREFILL_PAGE_SIZE", int),
        ("pages_per_slot", "ACCELERATE_SERVE_PREFILL_PAGES_PER_SLOT", int),
        ("kv_dtype", "ACCELERATE_SERVE_PREFILL_KV_DTYPE", str),
    ):
        raw = os.environ.get(env, "")
        if raw:
            overrides[field] = cast(raw)
    if not overrides:
        return decode_plugin
    return dataclasses.replace(decode_plugin, **overrides)


def preflight_disaggregate(config: PreflightConfig, model_config=None,
                           plugin=None, prefill_plugin=None):
    """The GL4xx pair audit of a disaggregated prefill→decode deployment:
    wire-schema agreement (GL403), the handoff's collective schedule
    (GL401), the traced wire programs' sharding pins (GL402), and each
    role's warmup coverage of its dispatchable set (GL404).

    Trace-only — ``jax.jit(...).trace`` + ``eval_shape`` — so it adds
    ZERO backend compiles to the preflight and sits outside the tier-1
    compile budget.  Returns ``(findings, summary)``."""
    from ..analysis.distributed_audit import pair_preflight

    if model_config is None or plugin is None:
        cfg, env_plugin, _ = _serve_setup()
        model_config = model_config or cfg
        plugin = plugin or env_plugin
    if prefill_plugin is None:
        prefill_plugin = _prefill_role_plugin(plugin)
    return pair_preflight(model_config, prefill_plugin, plugin)


def _parse_program_spec(spec: str):
    parts = spec.split("::")
    if len(parts) < 2:
        raise ValueError(
            f"--program {spec!r}: expected FILE::FN[::donate=I,J]"
        )
    path, fn_name = parts[0], parts[1]
    donate = ()
    for extra in parts[2:]:
        if extra.startswith("donate="):
            donate = tuple(int(i) for i in extra[len("donate="):].split(",") if i)
    return path, fn_name, donate


def preflight_program(spec: str, config: PreflightConfig, hbm_budget_bytes=None):
    """Preflight one user-named program: ``FILE::FN`` with the fixture
    convention (``example_args()[FN]`` supplies inputs).  A bad file or
    function name is a GL002 finding — the shared loud-failure contract."""
    from ..analysis import Finding, RULES
    from ..analysis.compiled_audit import aot_compile_program

    path, fn_name, donate = _parse_program_spec(spec)
    try:
        module_spec = importlib.util.spec_from_file_location("preflight_target", path)
        mod = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(mod)
        fn = getattr(mod, fn_name)
        args = mod.example_args()[fn_name]
    except Exception as e:
        r = RULES["GL002"]
        return [Finding(
            rule="GL002", severity=r.severity, fix_hint=r.fix_hint,
            message=f"preflight target {spec!r} could not be loaded: {e}",
            path=path, line=1, engine="compiled",
        )], []
    code = getattr(fn, "__code__", None)
    prog = aot_compile_program(
        fn, *args, donate_argnums=donate, label=f"{path}::{fn_name}",
        path_hint=(code.co_filename, code.co_firstlineno) if code else None,
    )
    return _audit_program(prog, config, hbm_budget_bytes)


# ---------------------------------------------------------------------------
# the command
# ---------------------------------------------------------------------------


def preflight_command(args) -> None:
    from ..analysis import Report, Severity, apply_suppressions, lint_paths
    from ..analysis.compiled_audit import device_hbm_bytes

    config = PreflightConfig(
        hbm_gb=args.hbm_gb,
        fail_on=args.fail_on or "",
        optimizer=args.optimizer or "",
    )
    budget = device_hbm_bytes(config.hbm_gb)

    findings, rows = [], []
    if not args.no_lint:
        findings += lint_paths(args.paths).findings
    flavors = []
    explicit = (args.serve or args.train or args.program
                or getattr(args, "disaggregate", False))
    run_train = args.train or not explicit
    run_serve = args.serve or not explicit
    if run_train:
        f, r = preflight_train(config, budget)
        findings += f
        rows += r
        flavors.append("train")
    if run_serve:
        f, r = preflight_serve(config, budget)
        findings += f
        rows += r
        flavors.append("serve")
    distributed = None
    if getattr(args, "disaggregate", False):
        f, distributed = preflight_disaggregate(config)
        findings += f
        flavors.append("disaggregate")
    for spec in args.program:
        f, r = preflight_program(spec, config, budget)
        findings += f
        rows += r
        flavors.append(spec)

    report = Report(apply_suppressions(findings))
    if args.json:
        payload = {
            "flavors": flavors,
            "hbm_budget_bytes": budget,
            "programs": rows,
            "findings": [f.to_dict() for f in report.findings],
            "summary": report.summary(),
        }
        if distributed is not None:
            payload["distributed"] = distributed
        print(json.dumps(payload, indent=2))
    else:
        print(report.render(show_suppressed=args.show_suppressed))
        if distributed is not None:
            roles = distributed.get("roles", {})
            print(
                "preflight pair: schema_ok="
                f"{distributed.get('schema_ok')} kv_dtype="
                f"{distributed.get('kv_dtype')} wire_legs="
                f"{len(distributed.get('wire_legs', []))} "
                + " ".join(
                    f"{role}[warmed={r['warmed']} dispatch={r['dispatchable']}]"
                    for role, r in roles.items()
                )
            )
        for row in rows:
            hbm = row.get("hbm") or {}
            print(
                f"preflight {row['program']}: compile {row.get('compile_s', 0)}s, "
                f"hbm {hbm.get('total', 0) / 2**20:.2f} MiB "
                f"(args {hbm.get('arguments', 0)} B, temps {hbm.get('temps', 0)} B, "
                f"aliased {hbm.get('aliased', 0)} B), "
                f"flops {row.get('flops', 0):.3g}, "
                f"bytes {row.get('bytes_accessed', 0):.3g}"
            )
        print(f"preflight: {len(rows)} program(s) compiled [{', '.join(flavors)}]")
    raise SystemExit(report.exit_code(Severity.parse(config.fail_on)))


def main():
    preflight_command(preflight_command_parser().parse_args())


if __name__ == "__main__":
    sys.exit(main())
