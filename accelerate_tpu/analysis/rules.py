"""The graft-lint rule catalog — one registry all engines and the docs
draw from.

Numbering: GL0xx meta (the linter linting its own markers), GL1xx jaxpr
rules (hazards visible only in the traced program; GL106-109 are the
suppressible INFO *hints* — GL109 is source-level but rides the hint
block), GL2xx AST rules (hazards visible only in the source — caller-side
reuse, impure calls the trace would bake silently), GL3xx
compiled/recompile rules (hazards visible only in the lowered XLA
executable — did the donation actually alias, does the footprint fit —
plus the trace- and source-level shapes that cause mid-traffic
recompiles), GL4xx distributed rules (cross-program, cross-role contracts
— collective schedules, reshard blowups, wire schemas, warmup coverage —
audited over PAIRS/SETS of programs by
:mod:`.distributed_audit`).  ``docs/static_analysis.md`` renders this
table (generated from this registry by ``docs/gen_api.py``);
``tests/test_analysis.py`` pins that every finding any engine can emit
carries an id registered here.
"""

from __future__ import annotations

import dataclasses

from .report import Severity


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    name: str
    severity: Severity
    engine: str  # "jaxpr" | "ast" | "meta" | "compiled" | "distributed"
    summary: str
    fix_hint: str


RULES: dict[str, Rule] = {
    r.id: r
    for r in [
        Rule(
            "GL001", "bare-suppression", Severity.WARNING, "meta",
            "a `graft-lint: disable=` marker without a rationale",
            "append `-- <why this hazard is intentional>` to the marker",
        ),
        Rule(
            "GL002", "engine-error", Severity.ERROR, "meta",
            "graft-lint could not analyze a target: an explicitly named "
            "path that does not exist / cannot be read, or a module that "
            "does not parse — reported loudly so a typo'd CI target can "
            "never pass as a clean run",
            "fix the path or the syntax error; a file that should not be "
            "linted belongs in the excludes, not in the sweep",
        ),
        # ------------------------------------------------------------------
        # jaxpr engine — hazards read off the traced program
        # ------------------------------------------------------------------
        Rule(
            "GL101", "wasted-donation", Severity.WARNING, "jaxpr",
            "a donated input buffer that no output can alias (no output of "
            "the same byte size remains after greedy matching): the donation "
            "frees nothing, and the caller still loses the buffer",
            "drop the argument from donate_argnums, or return an update of "
            "the same shape/dtype so XLA can reuse the buffer",
        ),
        Rule(
            "GL102", "const-capture", Severity.WARNING, "jaxpr",
            "a large closed-over constant baked into the jaxpr: it is "
            "re-uploaded per compiled executable, duplicated across "
            "retraces, and invisible to donation/sharding",
            "pass the array as an explicit argument (donate or shard it), "
            "or hoist it with the host-constant idiom",
        ),
        Rule(
            "GL103", "transfer-in-trace", Severity.WARNING, "jaxpr",
            "a device_put inside traced code whose destination memory kind "
            "differs from the program's default: an implicit host<->device "
            "transfer serialized into the step, invisible to the "
            "ops/streaming.py overlap accounting",
            "move the transfer outside the jit, or route it through the "
            "streaming pipeline stages so it overlaps compute",
        ),
        Rule(
            "GL104", "key-reuse", Severity.ERROR, "jaxpr",
            "a PRNG key consumed by more than one random primitive: the "
            "streams are identical, which silently correlates what should "
            "be independent randomness (and breaks the SR hash-stream "
            "determinism contract)",
            "jax.random.split (or fold_in) once per consumer and retire "
            "the parent key",
        ),
        Rule(
            "GL106", "collective-matmul-hint", Severity.INFO, "jaxpr",
            "an all_gather whose result feeds exactly one dot_general: the "
            "gather serializes ICI against the matmul it exists to feed — "
            "the canonical shape the ring collective-matmul "
            "(ops/collective_matmul.py) decomposes into ppermute ticks "
            "hidden under partial matmuls (a hint, not a defect: "
            "suppressible, and never fails a run)",
            "route the pair through ops/collective_matmul.py "
            "(ring_all_gather_matmul / dense_collective_matmul), or enable "
            "FullyShardedDataParallelPlugin.collective_matmul",
        ),
        Rule(
            "GL107", "collective-matmul-rs-hint", Severity.INFO, "jaxpr",
            "a dot_general whose result feeds exactly one reduce_scatter: "
            "the row-parallel mirror of GL106 — the matmul finishes before a "
            "single monolithic scatter starts, serializing ICI against the "
            "compute that produced it (a hint, not a defect: suppressible, "
            "and never fails a run)",
            "route the pair through ops/collective_matmul.py "
            "(ring_matmul_reduce_scatter), or enable "
            "FullyShardedDataParallelPlugin.collective_matmul",
        ),
        Rule(
            "GL108", "hierarchical-reduction-hint", Severity.INFO, "jaxpr",
            "a large (>= 1 MiB per-device operand) all-reduce spanning the "
            "`dcn` mesh axis JOINTLY with intra-slice axes — a flat "
            "reduction whose cross-slice hop carries one redundant "
            "full-size copy per intra-slice device over the slow DCN link "
            "(a hint, not a defect: suppressible, and never fails a run)",
            "decompose it hierarchically: reduce-scatter over the ICI axes, "
            "all-reduce only the sharded slab over `dcn`, all-gather back "
            "(parallel/hierarchical.py hierarchical_sync — the prepared "
            "train step does this automatically when the mesh has a dcn "
            "axis and GradSyncKwargs.hierarchical is not disabled)",
        ),
        Rule(
            "GL109", "timing-without-block", Severity.INFO, "ast",
            "a perf_counter()/monotonic() delta bracketing a jitted call "
            "with no block_until_ready()/materialization in between: jax "
            "dispatch is async, so the delta measures host-side enqueue "
            "time, not device compute — the resulting 'speedup' is a "
            "measurement artifact (a hint, not a defect: suppressible, and "
            "never fails a run)",
            "materialize before reading the clock: "
            "jax.block_until_ready(out) (or float(loss)/np.asarray) between "
            "the jitted call and the closing perf_counter()",
        ),
        Rule(
            "GL110", "unscaled-fp8-dot", Severity.ERROR, "jaxpr",
            "a dot_general with a float8 operand whose result is consumed "
            "with no dequantizing multiply/divide in the chain: fp8 CODES "
            "are only meaningful next to their scale, so the downstream "
            "math silently runs on values off by the (x_scale * w_scale) "
            "factor — the loss still goes down, just slower, which is why "
            "nothing else catches it",
            "multiply the dot result by the combined inverse scale before "
            "anything else consumes it (the quantised-page kernels of "
            "ops/flash_attention.py, which fold each page's scale into the "
            "scores and the weighted sum, are the model)",
        ),
        Rule(
            "GL105", "unsharded-output", Severity.WARNING, "jaxpr",
            "a large output with no sharding constraint on its producer: "
            "GSPMD may resolve it fully replicated, costing a full copy of "
            "the array per device",
            "pin it with jax.lax.with_sharding_constraint (or out_shardings "
            "on the jit) like the accelerator's pinned_step_fn does",
        ),
        # ------------------------------------------------------------------
        # AST engine — hazards read off the source
        # ------------------------------------------------------------------
        Rule(
            "GL201", "donated-reuse", Severity.ERROR, "ast",
            "a name passed in a donated position of a donate_argnums call "
            "site is read again afterwards: the buffer may already be "
            "overwritten in place by the compiled program (the PR 2 "
            "async-checkpoint race shape)",
            "rebind the name to the call's result, or snapshot the value "
            "(sharding-preserving jit identity copy) before the call",
        ),
        Rule(
            "GL202", "host-sync-in-step", Severity.ERROR, "ast",
            "a host-synchronizing call (.item()/.tolist()/float()/int()/"
            "np.asarray/np.array) on a traced value inside jitted code: "
            "either a trace-time ConcretizationTypeError or, via callbacks, "
            "a hidden device->host sync that serializes the step",
            "keep the value abstract (jnp ops) and read metrics outside "
            "the jit",
        ),
        Rule(
            "GL204", "impure-in-jit", Severity.ERROR, "ast",
            "a call to time.time()/perf_counter()/random.*/np.random.* "
            "inside jitted code: the value is baked in at trace time, so "
            "every execution silently reuses the first call's result",
            "thread timestamps/randomness in as arguments (jax.random for "
            "in-trace randomness)",
        ),
        Rule(
            "GL205", "non-atomic-checkpoint", Severity.ERROR, "ast",
            "a checkpoint-durability hazard: (a) a write into a live "
            "`checkpoint_*` path with no tmp-stage + os.replace in scope — "
            "a crash mid-write leaves a directory that LOOKS like a "
            "checkpoint and resumes garbage; or (b) a bare "
            "`except Exception: pass` in resilience/checkpoint code — a "
            "swallowed save/restore failure is indistinguishable from "
            "success until the restore that needed it",
            "stage every file under `<dir>.tmp` and publish with one "
            "os.replace (checkpointing._finalize_checkpoint is the model); "
            "never silently swallow exceptions on the save/restore spine — "
            "log, re-raise, or route through resilience.retry.with_retries",
        ),
        Rule(
            "GL206", "donate-under-pending-snapshot", Severity.ERROR, "ast",
            "a TrainState name handed to an async checkpoint initiator "
            "(async_save=True) is later passed in a donated position with "
            "no rebind or drain in between: the background write may still "
            "be reading the very buffers the compiled program overwrites "
            "in place — the snapshot-aliasing race the sharding-preserving "
            "copy in save_accelerator_state exists to close, re-opened by "
            "user code",
            "drain first (wait_for_checkpoint / wait_for_pending_checkpoint"
            ") or snapshot the state (sharding-preserving copy) before "
            "donating it",
        ),
        # ------------------------------------------------------------------
        # compiled engine (GL301-303) + recompile-cause rules (GL304-306):
        # what the lowered XLA executable actually does, and the trace- and
        # source-level shapes that re-key the jit cache mid-traffic
        # ------------------------------------------------------------------
        Rule(
            "GL301", "donation-not-aliased", Severity.ERROR, "compiled",
            "a donate_argnums input the compiled executable provably did "
            "NOT alias (compiled memory analysis: aliased bytes < donated "
            "bytes): the compiled-level twin of GL101 — the jaxpr auditor "
            "predicts viability, this reads XLA's actual decision off the "
            "executable, so it also catches donations the compiler declined "
            "for layout/sharding reasons no trace-level model sees",
            "return an update with the donated input's exact aval (shape, "
            "dtype, weak_type, sharding) or drop the argument from "
            "donate_argnums; re-run `accelerate_tpu preflight` to confirm "
            "the alias landed",
        ),
        Rule(
            "GL302", "hbm-over-budget", Severity.ERROR, "compiled",
            "a compiled program whose argument+output+temp footprint "
            "exceeds the device HBM budget (measured or --hbm-gb): the "
            "program OOMs at first execution — after the deploy took "
            "traffic, unless preflight catches it here",
            "shrink the KV pool / batch / bucket ladder, enable offload, "
            "or raise --hbm-gb if the budget was a stale estimate",
        ),
        Rule(
            "GL303", "recompile-ladder-drift", Severity.WARNING, "compiled",
            "the compiled program set does not match the predicted bucket "
            "ladder (exactly len(prefill_buckets)+2 serving programs, or "
            "extra backend compiles observed during preflight): every "
            "extra distinct lowering is a mid-traffic recompile waiting "
            "to happen",
            "pin every device program to a fixed shape from the bucket "
            "ladder (ServingPlugin.prefill_buckets); dedupe buckets; chase "
            "stray compiles with JAX_LOG_COMPILES=1",
        ),
        Rule(
            "GL304", "donated-promotion-drift", Severity.WARNING, "jaxpr",
            "a donated input whose only same-shape outputs differ in dtype "
            "or weak_type by promotion (a python scalar mixed into the "
            "donated tree): feeding the result back re-keys the jit cache "
            "— a recompile every step — and the widened output can no "
            "longer alias the donated buffer",
            "match the update's dtype to the state's (jnp.asarray(c, "
            "x.dtype) / x.dtype-typed literals) so the output aval equals "
            "the donated input aval",
        ),
        Rule(
            "GL305", "shape-dependent-trace", Severity.WARNING, "ast",
            "a traced-shape read (`arg.shape[i]` of a non-static jit "
            "argument) flowing directly into a shape-constructing call "
            "(jnp.arange/zeros/ones/full/reshape/broadcast_to) inside "
            "jitted code: the program re-specializes per input shape, so "
            "every unbucketed arrival is a fresh compile",
            "pad inputs to a fixed bucket ladder before the jit boundary "
            "(ServingPlugin.prefill_buckets is the model), or mark the "
            "driving argument static (static_argnums/static_argnames)",
        ),
        # ------------------------------------------------------------------
        # distributed engine (GL401-404): cross-program, cross-role
        # contracts — what the multi-host fabric would discover at launch
        # time, proven (or refuted) before any process spawns
        # ------------------------------------------------------------------
        Rule(
            "GL401", "collective-schedule-mismatch", Severity.ERROR,
            "distributed",
            "two mesh roles' traced programs disagree on the ordered "
            "collective schedule (op, axis names, or payload bytes at some "
            "rendezvous index): a launched gang meets mismatched "
            "collectives at that index and deadlocks — or silently "
            "corrupts the reduction.  Collectives under lax.cond execute "
            "data-dependently and are reported, not proven (the "
            "documented miss)",
            "make every role trace the identical collective sequence: one "
            "shared step builder per gang (parallel/hierarchical.py's "
            "hierarchical_sync is the model), no role-conditional "
            "collectives outside lax.cond branches every role shares",
        ),
        Rule(
            "GL402", "implicit-reshard-blowup", Severity.WARNING,
            "distributed",
            "a >= 1 MiB tensor pinned to one sharding and re-pinned to a "
            "different one (or fed back as an input with a drifted "
            "compiled sharding): GSPMD materializes an un-requested "
            "all-gather + re-slice between the pins — extra interconnect "
            "bytes no comm accounting model (dcn_comm_accounting / "
            "tp_comm_accounting) counts",
            "make consecutive sharding pins agree (or drop the redundant "
            "inner pin); for step feedback, pin the output to the input's "
            "sharding so the loop is reshard-free",
        ),
        Rule(
            "GL403", "wire-schema-incompatibility", Severity.ERROR,
            "distributed",
            "the prefill-role and decode-role engines derive different "
            "static wire schemas for the KV page handoff (page geometry, "
            "kv_dtype codes+scales, payload shapes/dtypes, per-page "
            "bytes, prefix/adapter conventions): the decode side scatters "
            "the payload into a pool that cannot parse it — KV corruption "
            "at the first handoff",
            "deploy both roles from one ServingPlugin geometry (page_size, "
            "pages_per_slot, kv_dtype must agree; see "
            "analysis/distributed_audit.wire_schema) — the same check the "
            "transport enforces at runtime, moved before launch",
        ),
        Rule(
            "GL404", "role-asymmetric-warmup", Severity.WARNING,
            "distributed",
            "a role's warmed program set does not cover the programs the "
            "pair schedule can dispatch to it: the first dispatch of a "
            "cold program is a guaranteed mid-traffic compile on that "
            "role (the strict_compiles contract, checked statically per "
            "role)",
            "warm every dispatchable program per role "
            "(ServingEngine.warmup() + PagedKVTransport.warmup(); "
            "analysis/distributed_audit.role_programs is the ground "
            "truth), or remove the program from the role's schedule",
        ),
        Rule(
            "GL306", "jit-in-hot-loop", Severity.WARNING, "ast",
            "a jax.jit(...) call expression constructed inside a for/while "
            "body: each iteration builds a fresh jit wrapper with a fresh "
            "cache, so the XLA program recompiles (or at best re-hashes) "
            "every pass through the loop",
            "hoist the jax.jit(...) call above the loop and call the "
            "wrapper inside it",
        ),
    ]
}


def rule(rule_id: str) -> Rule:
    return RULES[rule_id]
