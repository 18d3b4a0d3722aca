"""graft-lint: static analysis for donation, transfer, and sharding hazards.

Three engines over one report model (all CPU-safe, nothing executes on
device):

- :mod:`.jaxpr_audit` — traces a step/decode function abstractly
  (``jax.jit(fn).trace``) and walks the ClosedJaxpr for hazards only the
  traced program shows: wasted donations (GL101), const-capture HBM
  blowups (GL102), in-trace memory-kind transfers (GL103), PRNG key reuse
  (GL104), unsharded large outputs (GL105), collective-matmul candidates
  (GL106/GL107), donated promotion drift (GL304).
- :mod:`.ast_rules` — repo-wide source linter for hazards only the caller's
  source shows: donated-name reuse after a ``donate_argnums`` call site
  (GL201, the PR 2 async-checkpoint race shape), host syncs in jitted code
  (GL202), wall-clock/stdlib randomness under trace (GL204), non-atomic checkpoint
  writes (GL205), shape-dependent traces (GL305), jit-in-hot-loop (GL306).
- :mod:`.compiled_audit` — AOT ``lower().compile()`` every production
  program and read XLA's decisions off the executable: donation that did
  not alias (GL301), HBM footprint over budget (GL302), compiled program
  set vs the predicted bucket ladder (GL303), plus the flops/bytes cost
  report and the runtime compile-event counter.
- :mod:`.distributed_audit` — cross-program, cross-role contracts over
  PAIRS/SETS of programs (trace-only, zero compiles): collective-schedule
  divergence between mesh roles (GL401), implicit-reshard blowups
  (GL402), prefill/decode wire-schema incompatibility (GL403), and
  role-asymmetric warmup coverage (GL404) — the ``preflight --serve
  --disaggregate`` pair gate and the multichip dryrun's distributed leg.

Surfaces: ``python -m accelerate_tpu lint`` / ``preflight``
(``commands/lint.py``, ``commands/preflight.py``),
``Accelerator.audit_step()`` / ``ACCELERATE_LINT=1``, ``make lint`` /
``make preflight``.  Rule catalog and
suppression syntax: ``docs/static_analysis.md``.
"""

from .ast_rules import (
    DEFAULT_EXCLUDE_DIRS,
    DEFAULT_EXCLUDES,
    iter_python_files,
    lint_paths,
    lint_source,
    resolve_targets,
)
from .compiled_audit import (
    CompileCounter,
    audit_aot,
    audit_compiled,
    audit_program_set,
    aot_compile_program,
    device_hbm_bytes,
    install_global_compile_counter,
)
from .distributed_audit import (
    CollectiveOp,
    audit_collective_schedules,
    audit_compiled_resharding,
    audit_resharding,
    audit_warmup_coverage,
    audit_wire_schema,
    check_wire_schemas,
    collective_schedule,
    handoff_schedule,
    pair_preflight,
    role_programs,
    warmup_plan,
    wire_schema,
)
from .jaxpr_audit import audit_fn, audit_jitted, audit_traced, iter_eqns
from .report import Finding, Report, Severity, apply_suppressions, parse_marker
from .rules import RULES, Rule, rule

__all__ = [
    "CollectiveOp",
    "CompileCounter",
    "DEFAULT_EXCLUDE_DIRS",
    "DEFAULT_EXCLUDES",
    "Finding",
    "Report",
    "RULES",
    "Rule",
    "Severity",
    "aot_compile_program",
    "apply_suppressions",
    "audit_aot",
    "audit_collective_schedules",
    "audit_compiled",
    "audit_compiled_resharding",
    "audit_fn",
    "audit_jitted",
    "audit_program_set",
    "audit_resharding",
    "audit_traced",
    "audit_warmup_coverage",
    "audit_wire_schema",
    "check_wire_schemas",
    "collective_schedule",
    "device_hbm_bytes",
    "handoff_schedule",
    "install_global_compile_counter",
    "iter_eqns",
    "iter_python_files",
    "lint_paths",
    "lint_source",
    "pair_preflight",
    "parse_marker",
    "resolve_targets",
    "role_programs",
    "rule",
    "warmup_plan",
    "wire_schema",
]
