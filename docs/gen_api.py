"""Generate docs/api/*.md from live signatures + docstrings.

Role of reference ``docs/source/package_reference/`` (~15 autodoc pages):
a per-API reference. Autodoc'd rather than handwritten so it cannot drift —
``tests/test_docs.py`` regenerates and diffs.

Run: ``python docs/gen_api.py``
"""

from __future__ import annotations

import importlib
import inspect
import sys
from pathlib import Path

OUT = Path(__file__).parent / "api"
STATIC_ANALYSIS_DOC = Path(__file__).parent / "static_analysis.md"
RULE_TABLE_BEGIN = "<!-- rule-table:begin (generated by docs/gen_api.py — do not edit) -->"
RULE_TABLE_END = "<!-- rule-table:end -->"

# page -> (module, [names])  (None = every public callable/class in __all__
# or module order)
PAGES: dict[str, tuple[str, list[str] | None]] = {
    "accelerator": ("accelerate_tpu.accelerator", ["Accelerator", "TrainState", "global_norm"]),
    "analysis": ("accelerate_tpu.analysis", [
        "Severity", "Finding", "Report", "Rule", "audit_fn", "audit_jitted",
        "audit_traced", "lint_source", "lint_paths", "iter_python_files",
        "resolve_targets", "apply_suppressions", "parse_marker",
        "CompileCounter", "install_global_compile_counter",
        "aot_compile_program", "audit_compiled", "audit_aot",
        "audit_program_set", "device_hbm_bytes",
        "CollectiveOp", "collective_schedule", "audit_collective_schedules",
        "audit_resharding", "audit_compiled_resharding", "wire_schema",
        "audit_wire_schema", "check_wire_schemas", "handoff_schedule",
        "warmup_plan", "role_programs", "audit_warmup_coverage",
        "pair_preflight",
    ]),
    "state": ("accelerate_tpu.state", ["PartialState", "AcceleratorState", "GradientState"]),
    "parallelism_config": ("accelerate_tpu.parallelism_config", ["ParallelismConfig"]),
    "data_loader": ("accelerate_tpu.data_loader", [
        "prepare_data_loader", "DataLoaderShard", "DataLoaderDispatcher",
        "BatchSamplerShard", "IterableDatasetShard", "SeedableRandomSampler",
        "skip_first_batches", "SkipDataLoader",
    ]),
    "big_modeling": ("accelerate_tpu.big_modeling", [
        "init_empty_weights", "abstract_init", "init_params_leafwise",
        "infer_auto_placement", "load_checkpoint_in_model",
        "load_checkpoint_and_dispatch", "load_checkpoint_and_serve",
        "serve_model", "dispatch_model", "OffloadStore",
        "offload_store_params",
    ]),
    "pipeline": ("accelerate_tpu.parallel.pipeline_parallel", [
        "prepare_pipeline", "PipelinedModel",
    ]),
    "checkpointing": ("accelerate_tpu.checkpointing", [
        "save_accelerator_state", "load_accelerator_state", "save_model",
        "load_model_params", "merge_weights", "verify_checkpoint",
        "write_checkpoint_manifest", "CheckpointCorruptError",
    ]),
    "generation": ("accelerate_tpu.generation", [
        "generate", "beam_search", "generate_streamed", "generate_paged",
        "place_params_host", "GenerationConfig",
    ]),
    "serving": ("accelerate_tpu.serving", [
        "ServingEngine", "ContinuousBatchingScheduler", "Request", "SlotState",
        "AdapterStore", "LoraTrainer", "adapter_pool_accounting",
        "predicted_adapter_hit_rate",
        "allocate", "release", "push_pages", "pages_for", "kv_pool_accounting",
        "cache_accounting",
        "synthesize_trace", "replay", "chaos_replay", "static_batching_report",
        "predicted_pool_utilization", "DegradationLadder",
        "verify_serving_invariants",
        "PagedKVTransport", "DisaggregatedPair", "transfer_accounting",
        "page_bytes",
    ]),
    "router": ("accelerate_tpu.serving.router", [
        "FleetRouter", "fleet_replay", "fleet_chaos_replay",
    ]),
    "prefix_cache": ("accelerate_tpu.serving.prefix_cache", [
        "PrefixCache", "block_hashes", "unbounded_prefix_hit_rate",
        "prefix_cache_accounting",
    ]),
    "speculate": ("accelerate_tpu.serving.speculate", [
        "NgramDraft", "DraftModelDraft", "Speculator", "make_draft_provider",
        "predicted_acceptance", "speculative_page_need",
    ]),
    "lora": ("accelerate_tpu.ops.lora", [
        "lora_apply", "lora_apply_sequential", "bgmv", "lora_spec",
        "init_lora_pool", "init_adapter_params", "adapter_param_count",
        "adapter_state_accounting", "set_lora_kernel", "lora_kernel",
        "lora_kernel_mode", "normalize_lora_kernel",
    ]),
    "tracking": ("accelerate_tpu.tracking", [
        "GeneralTracker", "JSONLTracker", "TensorBoardTracker", "WandBTracker",
        "MLflowTracker", "filter_trackers",
    ]),
    "telemetry": ("accelerate_tpu.telemetry", [
        "Twin", "TwinRegistry", "twin_registry", "SpanRecorder",
        "RequestTracer", "VirtualClock", "validate_chrome_trace",
        "HostLedger", "install_global_gc_hook", "TrainTimeline", "StreamingQuantile", "SLOMonitor", "SLOStatus",
        "prometheus_text",
    ]),
    "operations": ("accelerate_tpu.ops.operations", [
        "gather", "gather_object", "broadcast", "broadcast_object_list",
        "reduce", "pad_across_processes", "recursively_apply", "map_pytree",
        "send_to_device", "concatenate",
    ]),
    "kernels": ("accelerate_tpu.ops.flash_attention", None),
    "paged_cache": ("accelerate_tpu.ops.paged_cache", None),
    "page_walk": ("accelerate_tpu.ops.page_walk", None),
    "quantization": ("accelerate_tpu.utils.quantization", [
        "QuantizationConfig", "QuantizedTensor", "quantize", "dequantize",
        "quantize_params", "quantized_apply",
    ]),
    "powersgd": ("accelerate_tpu.parallel.powersgd", None),
    "hierarchical": ("accelerate_tpu.parallel.hierarchical", [
        "hierarchical_sync", "init_dcn_powersgd_state", "slab_geometry",
        "slab_eligible", "dcn_comm_accounting", "measure_dcn_bytes",
        "ring_reduce_factor",
    ]),
    "streaming": ("accelerate_tpu.ops.streaming", [
        "StreamStats", "LayerPrefetcher", "chunk_groups", "slice_congruent",
        "merge_congruent", "stage_put", "tree_bytes", "predicted_overlap",
        "offload_transfer_accounting",
    ]),
    "stochastic_rounding": ("accelerate_tpu.ops.stochastic_rounding", [
        "lion_bf16_sr", "adamw_bf16_sr", "stochastic_round_to_bf16",
        "stochastic_round_to_bf16_hashed",
    ]),
    "collective_matmul": ("accelerate_tpu.ops.collective_matmul", [
        "ring_all_gather_matmul", "ring_matmul_reduce_scatter",
        "all_gather_matmul_monolithic", "matmul_reduce_scatter_monolithic",
        "make_collective_dense", "dense_collective_matmul",
        "ulysses_sp_boundary", "ring_supported", "set_collective_matmul",
        "collective_matmul", "collective_matmul_mode", "normalize_mode",
        "tp_comm_accounting",
    ]),
    "profiler": ("accelerate_tpu.utils.profiler", ["TPUProfiler"]),
    "resilience": ("accelerate_tpu.resilience", [
        "FaultPlan", "FaultEvent", "install_fault_plan", "fault_plan",
        "fault_point", "maybe_fail_transfer", "poison_batch",
        "corrupt_checkpoint", "PreemptionHandler", "RetryPolicy",
        "with_retries", "TransientIOError", "NanGuardAbort",
        "init_guard_state", "select_tree", "update_guard_counters",
        "GoodputTracker", "goodput_accounting",
    ]),
    "dataclasses": ("accelerate_tpu.utils.dataclasses", [
        "GradSyncKwargs", "ProfileKwargs", "GradientAccumulationPlugin",
        "FullyShardedDataParallelPlugin", "ResiliencePlugin", "ServingPlugin",
        "LoraPlugin", "ProjectConfiguration", "DataLoaderConfiguration",
        "InitProcessGroupKwargs",
    ]),
    "memory": ("accelerate_tpu.utils.memory", None),
}


def _doc_first_block(obj) -> str:
    doc = inspect.getdoc(obj) or "*(undocumented)*"
    return doc.strip()


def _signature(obj) -> str:
    import re

    try:
        sig = str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"
    # default-value reprs carry memory addresses; scrub for reproducibility
    return re.sub(r" at 0x[0-9a-f]+", "", sig)


def _public_members(mod):
    for name, obj in vars(mod).items():
        if name.startswith("_"):
            continue
        if inspect.isclass(obj) or inspect.isfunction(obj):
            if getattr(obj, "__module__", None) == mod.__name__:
                yield name


def render_page(page: str, module_name: str, names) -> str:
    mod = importlib.import_module(module_name)
    if names is None:
        names = list(_public_members(mod))
    lines = [f"# `{module_name}`", ""]
    mod_doc = (mod.__doc__ or "").strip().splitlines()
    if mod_doc:
        lines += [mod_doc[0], ""]
    for name in names:
        obj = getattr(mod, name)
        if inspect.isclass(obj):
            lines += [f"## class `{name}{_signature(obj)}`", "", _doc_first_block(obj), ""]
            for mname, m in sorted(vars(obj).items()):
                if mname.startswith("_") or not (inspect.isfunction(m) or isinstance(m, property)):
                    continue
                target = m.fget if isinstance(m, property) else m
                if not (target.__doc__ or "").strip():
                    continue
                kind = "property " if isinstance(m, property) else ""
                sig = "" if isinstance(m, property) else _signature(target)
                lines += [f"### {kind}`{name}.{mname}{sig}`", "", _doc_first_block(target), ""]
        else:
            lines += [f"## `{name}{_signature(obj)}`", "", _doc_first_block(obj), ""]
    return "\n".join(lines).rstrip() + "\n"


def generate() -> dict[str, str]:
    return {
        page: render_page(page, module_name, names)
        for page, (module_name, names) in PAGES.items()
    }


def render_rule_table() -> str:
    """Markdown summary table of every registered graft-lint rule.

    Sourced from ``analysis.rules.RULES`` so the catalog cannot drift from
    the registry — the per-engine prose tables below it in
    ``static_analysis.md`` stay hand-written commentary."""
    from accelerate_tpu.analysis.rules import RULES

    lines = [
        "| Id | Name | Engine | Sev | Summary |",
        "|---|---|---|---|---|",
    ]
    for rule_id in sorted(RULES):
        r = RULES[rule_id]
        summary = " ".join(r.summary.split())
        summary = summary[0].upper() + summary[1:] if summary else summary
        if summary and not summary.endswith("."):
            summary += "."
        lines.append(
            f"| {r.id} | {r.name} | {r.engine} | {r.severity.name.lower()} | {summary} |"
        )
    return "\n".join(lines)


def inject_rule_table(text: str) -> str:
    """Replace the marker-delimited block in ``static_analysis.md`` with a
    freshly rendered rule table.  Raises if the markers are missing or
    malformed — a silently skipped injection is how tables drift."""
    begin = text.index(RULE_TABLE_BEGIN)
    end = text.index(RULE_TABLE_END)
    if end < begin:
        raise ValueError("rule-table markers out of order in static_analysis.md")
    head = text[: begin + len(RULE_TABLE_BEGIN)]
    tail = text[end:]
    return head + "\n" + render_rule_table() + "\n" + tail


def main():
    OUT.mkdir(exist_ok=True)
    pages = generate()
    index = ["# API reference", "", "Generated by `docs/gen_api.py` — do not edit by hand.", ""]
    for page in sorted(pages):
        (OUT / f"{page}.md").write_text(pages[page])
        index.append(f"- [{page}]({page}.md)")
    (OUT / "index.md").write_text("\n".join(index) + "\n")
    STATIC_ANALYSIS_DOC.write_text(inject_rule_table(STATIC_ANALYSIS_DOC.read_text()))
    print(f"wrote {len(pages) + 1} pages to {OUT} + rule table in {STATIC_ANALYSIS_DOC.name}")


if __name__ == "__main__":
    sys.exit(main())
