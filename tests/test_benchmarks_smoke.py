"""The measurement harnesses in benchmarks/ back every number in the docs
(benchmarks/README.md maps each doc figure to its script); these smokes pin
that the CPU-runnable ones stay executable — the TPU-only paths are gated
inside the scripts themselves."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(args):
    # inherit the full environment (HOME, JAX/XLA vars, any rig-specific
    # site dirs ride along via PYTHONPATH) and prepend the repo root so the
    # subprocess imports THIS checkout — portable across machines/CI,
    # unlike a hardcoded site path with a stripped env
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    out = subprocess.run(
        [sys.executable, *args], cwd=REPO, capture_output=True, text=True,
        timeout=600, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sr_quality_harness_runs():
    rep = _run(["benchmarks/sr_quality.py", "--cpu", "--steps", "4",
                "--eval-every", "2", "--optimizer", "adamw-sr"])
    assert rep["metric"] == "sr_quality_shuffled_stream"
    assert rep["sr"]["optimizer"] == "adamw-sr" and rep["ref"]["optimizer"] == "adamw"
    assert rep["final_held_out_gap_pct"] is not None
    # smoke mode reports the EFFECTIVE config, not the requested TPU model
    assert rep["model"] == "tiny-cpu" and rep["backend"] == "cpu"


@pytest.mark.slow
def test_sr_quality_harness_runs_sr8():
    rep = _run(["benchmarks/sr_quality.py", "--cpu", "--steps", "4",
                "--eval-every", "2", "--optimizer", "lion-sr8"])
    assert rep["sr"]["optimizer"] == "lion-sr8" and rep["ref"]["optimizer"] == "lion"
    assert rep["final_held_out_gap_pct"] is not None


@pytest.mark.slow
def test_bench_streaming_pipeline_smoke():
    """Tiny-CPU smoke of the double-buffered offload streaming pipeline:
    bench.py --offload with a chunk budget small enough to force multiple
    groups runs end-to-end with the pipeline on, and the report ALWAYS
    carries the overlap-accounting fields (overlap_frac/h2d_bytes/d2h_bytes)
    so BENCH_*.json tracks them across rounds."""
    rep = _run(["bench.py", "--iters", "2", "--batch", "8", "--offload",
                "--chunk-gib", "1e-6", "--pipeline", "on"])
    extra = rep["extra"]
    for field in ("overlap_frac", "h2d_bytes", "d2h_bytes"):
        assert field in extra, field
    assert extra["h2d_bytes"] > 0 and extra["d2h_bytes"] > 0
    assert extra["host_update_pipeline"] is True
    assert extra["streaming"]["kind"] == "predicted"

    # the serialized A/B baseline reports zero overlap, same fields
    rep_off = _run(["bench.py", "--iters", "2", "--batch", "8", "--offload",
                    "--chunk-gib", "1e-6", "--pipeline", "off"])
    assert rep_off["extra"]["overlap_frac"] == 0.0
    assert rep_off["extra"]["streaming"]["kind"] == "serialized-baseline"

    # non-offload runs still emit the fields (zeros — nothing streams)
    rep_res = _run(["bench.py", "--iters", "2", "--batch", "8"])
    assert rep_res["extra"]["overlap_frac"] == 0.0
    assert rep_res["extra"]["h2d_bytes"] == 0
    assert rep_res["extra"]["d2h_bytes"] == 0


@pytest.mark.slow
def test_bench_collective_matmul_flag():
    """CPU-tiny smoke of ``--collective-matmul on|off``: the report ALWAYS
    carries ``tp_overlap_frac`` next to ``overlap_frac`` (0.0 on this
    bench's dp-only mesh — the TP axis is trivial) and echoes the ring
    state in ``extra`` so BENCH_*.json can track A/B runs across rounds."""
    rep_on = _run(["bench.py", "--iters", "2", "--batch", "8",
                   "--collective-matmul", "on"])
    extra = rep_on["extra"]
    assert extra["collective_matmul"] == "ring"
    assert extra["tp_overlap_frac"] == 0.0  # dp-only mesh: trivial tp axis
    assert "overlap_frac" in extra  # rides alongside the streaming fields

    rep_off = _run(["bench.py", "--iters", "2", "--batch", "8",
                    "--collective-matmul", "off"])
    assert rep_off["extra"]["collective_matmul"] == "off"
    assert rep_off["extra"]["tp_overlap_frac"] == 0.0

    # the field is present even when the flag is never passed
    rep_default = _run(["bench.py", "--iters", "2", "--batch", "8"])
    assert rep_default["extra"]["tp_overlap_frac"] == 0.0
    assert rep_default["extra"]["collective_matmul"] == "off"

    # loss parity: the ring cannot change this mesh's numbers (trivial tp
    # axis -> both runs take the identical XLA path)
    assert rep_on["extra"]["loss"] == rep_off["extra"]["loss"]


@pytest.mark.slow
def test_bench_resilience_fields_always_emitted():
    """The resilience counters ride EVERY bench report (the CI contract for
    BENCH_*.json cross-round tracking): nan_skips/restarts at zero and
    goodput_frac at 1.0 when the run is clean, with the full measured
    digest under extra["goodput"]."""
    rep = _run(["bench.py", "--iters", "2", "--batch", "8"])
    extra = rep["extra"]
    assert extra["nan_skips"] == 0
    assert extra["restarts"] == 0
    assert extra["goodput_frac"] == 1.0
    goodput = extra["goodput"]
    assert goodput["kind"] == "measured"
    assert goodput["steps"] > 0 and goodput["preemptions"] == 0
    # recompile-guard twins ride EVERY train report: after the warmup step
    # the steady-state loop predicts zero compiles, and a clean run measures
    # exactly that (the zeros-clean contract)
    assert extra["compiles_predicted"] == 0
    assert extra["compiles_measured"] == extra["compiles_predicted"] == 0

    # the fields ride the offload flavor too (next to the streaming fields)
    rep_off = _run(["bench.py", "--iters", "2", "--batch", "8", "--offload",
                    "--chunk-gib", "1e-6"])
    for field in ("nan_skips", "restarts", "goodput_frac", "overlap_frac"):
        assert field in rep_off["extra"], field


@pytest.mark.slow
def test_bench_serve_smoke():
    """CPU-tiny smoke of ``--serve`` (the serving-core traffic replay): the
    report ALWAYS carries the serving fields — tokens/s/chip, p50/p99
    per-token latency, KV-pool utilization (measured + predicted twin),
    padding-waste fraction, scheduler occupancy — and on the seeded replay
    trace continuous batching beats the static-batching twin on padding
    waste and scheduled-token efficiency (the CPU-measurable acceptance
    proxies)."""
    rep = _run(["bench.py", "--serve", "--batch", "8"])
    assert rep["metric"] == "serving_tokens_per_sec_per_chip"
    extra = rep["extra"]
    for field in ("tokens_per_sec_per_chip", "p50_token_latency_ms",
                  "p99_token_latency_ms", "kv_pool_utilization",
                  "kv_pool_utilization_predicted", "padding_waste_frac",
                  "scheduled_token_efficiency", "scheduler_occupancy",
                  "evictions", "static_baseline", "kv_pool",
                  "kv_dtype", "kv_pool_capacity_ladder",
                  "fp8_amax_history_len"):
        assert field in extra, field
    # quantized-KV fields ride every serve report zeros-clean: bf16 pool
    # by default, the capacity ladder always present (pure arithmetic),
    # the quant twin idle
    assert extra["kv_dtype"] == "bf16"
    assert extra["kv_pool_capacity_ladder"]["bf16"] == 1.0
    assert extra["kv_pool_capacity_ladder"]["int8"] > 1.5
    assert extra["fp8_amax_history_len"] == 0
    assert extra["twins"]["kv_quant.page_bytes"]["status"] == "idle"
    assert extra["completed"] == extra["requests"] > 0
    assert extra["tokens_per_sec_per_chip"] > 0
    assert extra["kv_pool_utilization"] > 0
    static = extra["static_baseline"]
    assert extra["padding_waste_frac"] < static["padding_waste_frac"]
    assert extra["scheduled_token_efficiency"] > static["scheduled_token_efficiency"]
    # the predicted KV-HBM ladder rides every serve report
    assert extra["kv_pool"]["bytes_per_page"] > 0
    assert "v5e_16GiB" in extra["kv_pool"]["hbm_frac"]
    # the seeded replay's recompile-guard twins: warmup compiles every
    # fixed-shape program up front, then the replay measures ZERO compile
    # events — compiles_measured == compiles_predicted pins that no
    # mid-traffic recompile fired (the harness raises if one does)
    assert extra["compiles_predicted"] == 0
    assert extra["compiles_measured"] == extra["compiles_predicted"] == 0
    assert extra["programs_predicted"] == len(extra["prefill_buckets"]) + 3

    # the multi-tenant adapter fields ride EVERY serve report, zeros-clean
    # when no adapters are configured (the always-emitted contract)
    for field in ("adapters", "adapter_requests", "adapter_pool_hit_rate",
                  "adapter_pool_hit_rate_predicted", "adapter_swaps",
                  "adapter_swap_bytes", "per_adapter_loop",
                  "batched_speedup_vs_loop", "adapter_pool"):
        assert field in extra, field
    assert extra["adapters"] == 0
    assert extra["adapter_pool_hit_rate"] == 0.0
    assert extra["adapter_swap_bytes"] == 0
    assert extra["per_adapter_loop"]["groups"] == 0
    assert extra["batched_speedup_vs_loop"] == 0.0
    assert extra["adapter_pool"]["pool_slots"] == 0

    # the overload-control block rides EVERY serve report, zeros-clean on a
    # clean replay (ISSUE 14: sheds/misses/cancels zero, request goodput
    # 1.0, no transfer retries, ladder at normal) — with the serving.*
    # twin rows pinned to the clean-run model
    for field in ("requests_shed", "deadline_misses", "cancelled",
                  "pages_reclaimed_on_cancel", "request_goodput_frac",
                  "transfer_retries", "ladder_stage", "ladder_engagements"):
        assert field in extra, field
    assert extra["requests_shed"] == extra["deadline_misses"] == 0
    assert extra["cancelled"] == extra["pages_reclaimed_on_cancel"] == 0
    assert extra["request_goodput_frac"] == 1.0
    assert extra["transfer_retries"] == 0
    assert extra["ladder_stage"] == "normal"
    assert extra["ladder_engagements"] == 0
    for name in ("serving.requests_shed", "serving.deadline_misses",
                 "serving.cancelled", "serving.pages_reclaimed_on_cancel",
                 "serving.request_goodput_frac"):
        row = extra["twins"][name]
        assert row["status"] == "ok", (name, row)

    # the speculative-decode fields ride EVERY serve report, zeros-clean
    # with speculation off — tokens_per_step sits exactly at the plain-
    # decode 1.0 floor a speculative run must beat
    for field in ("speculate", "speculate_k", "accept_rate",
                  "accept_rate_predicted", "tokens_per_step",
                  "tokens_per_step_predicted", "draft_overhead_frac",
                  "speculative_rollbacks", "verify_steps"):
        assert field in extra, field
    assert extra["speculate"] == "off" and extra["speculate_k"] == 0
    assert extra["accept_rate"] == 0.0
    assert extra["tokens_per_step"] == 1.0
    assert extra["draft_overhead_frac"] == 0.0
    assert extra["speculative_rollbacks"] == 0

    # the prefix-cache + disaggregation block rides EVERY serve report,
    # zeros-clean with the cache off and no transport attached (ISSUE 15:
    # the always-emitted idle contract)
    for field in ("prefix_cache", "prefix_hit_rate",
                  "prefix_hit_rate_predicted", "pages_shared_peak",
                  "cow_forks", "prefill_tokens_skipped", "prefix_evictions",
                  "page_transfers", "page_transfer_bytes", "ttft_p50_ticks",
                  "disaggregated"):
        assert field in extra, field
    assert extra["prefix_cache"] == "off"
    assert extra["prefix_hit_rate"] == 0.0
    assert extra["pages_shared_peak"] == 0 and extra["cow_forks"] == 0
    assert extra["prefill_tokens_skipped"] == 0
    assert extra["page_transfer_bytes"] == 0
    assert extra["disaggregated"]["page_transfers"] == 0
    assert extra["twins"]["prefix_cache.hit_rate"]["status"] == "idle"
    assert extra["twins"]["transfer.page_bytes"]["status"] == "idle"

    # the fleet block rides EVERY serve report, zeros-clean without
    # --fleet (ISSUE 19: the always-emitted contract — no replicas, no
    # routing, parity vacuously true)
    fleet = extra["fleet"]
    for field in ("replicas", "alive", "policy", "requests", "completed",
                  "goodput_frac", "ttft_p50_ticks", "prefix_hit_rate",
                  "adapter_pool_hit_rate", "page_transfer_bytes",
                  "compiles_warmup_by_role", "compiles_measured",
                  "routed_by_prefix", "routed_by_adapter", "routed_by_load",
                  "drain_events", "per_replica", "token_parity_vs_fused"):
        assert field in fleet, field
    assert fleet["replicas"] == fleet["alive"] == 0
    assert fleet["goodput_frac"] == 0.0
    assert fleet["page_transfer_bytes"] == 0
    assert fleet["compiles_measured"] == 0
    assert fleet["routed_by_prefix"] == fleet["routed_by_adapter"] == 0
    assert fleet["drain_events"] == [] and fleet["per_replica"] == []
    assert fleet["token_parity_vs_fused"] is True

    # idle trace: every field still present, zeros (the always-emitted
    # contract BENCH_*.json relies on)
    rep_idle = _run(["bench.py", "--serve", "--batch", "8",
                     "--serve-requests", "0"])
    extra_idle = rep_idle["extra"]
    assert extra_idle["tokens_per_sec_per_chip"] == 0.0
    assert extra_idle["kv_pool_utilization"] == 0.0
    assert extra_idle["padding_waste_frac"] == 0.0
    assert extra_idle["scheduler_occupancy"] == 0.0
    assert extra_idle["p50_token_latency_ms"] == 0.0
    assert extra_idle["adapters"] == 0 and extra_idle["adapter_swaps"] == 0
    assert extra_idle["tokens_per_step"] == 0.0
    assert extra_idle["accept_rate"] == 0.0
    assert extra_idle["requests_shed"] == 0 and extra_idle["cancelled"] == 0
    assert extra_idle["deadline_misses"] == 0
    assert extra_idle["request_goodput_frac"] == 0.0  # nothing served
    assert extra_idle["ladder_stage"] == "normal"


@pytest.mark.slow
def test_bench_serve_prefix_share_smoke():
    """``--serve --prefix-share``: on the seeded shared-system-prompt CPU
    trace the prefix cache must actually reuse (prefill_tokens_skipped >
    0, hit rate > 0 with the scheduler-replay predicted twin within its
    registered tolerance), continuous-with-reuse must beat no-reuse on
    TTFT (virtual ticks — deterministic on CPU), tokens stay bitwise
    identical reuse on/off, and the replay stays recompile-free; with
    ``--disaggregate`` the pair's tokens match the fused engine and
    page_transfer_bytes equals the dcn accounting model exactly."""
    rep = _run(["bench.py", "--serve", "--batch", "4", "--serve-requests",
                "10", "--prefix-share", "0.8", "--disaggregate"])
    extra = rep["extra"]
    assert extra["prefix_cache"] == "on"
    assert extra["prefix_hit_rate"] > 0.0
    assert extra["prefill_tokens_skipped"] > 0
    assert extra["prefix_reuse_token_parity"] is True
    # reuse beats no-reuse on TTFT (the acceptance comparison, in ticks)
    assert extra["ttft_p50_ticks"] < extra["ttft_no_reuse_p50_ticks"]
    row = extra["twins"]["prefix_cache.hit_rate"]
    assert row["rel_err"] <= row["tolerance"], row
    assert extra["compiles_measured"] == 0
    # the disaggregated slice: parity + the exact byte twin
    dis = extra["disaggregated"]
    assert dis["token_parity_vs_fused"] is True
    assert dis["page_transfers"] > 0
    assert dis["compiles_prefill"] == 0 and dis["compiles_decode"] == 0
    assert extra["page_transfer_bytes"] == \
        extra["transfer_accounting"]["page_transfer_bytes"] > 0
    assert extra["twins"]["transfer.page_bytes"]["rel_err"] == 0.0


@pytest.mark.slow
def test_bench_serve_fleet_smoke():
    """``--serve --fleet 2``: the same seeded trace routed across two
    replicas — merged tokens BITWISE equal to the single fused engine in
    the same report, goodput 1.0, zero post-warmup compiles per replica,
    and the shared-preamble trace actually routes by prefix affinity;
    with ``--disaggregate`` each replica is a prefill→decode pair and KV
    pages cross the wire."""
    rep = _run(["bench.py", "--serve", "--batch", "4", "--serve-requests",
                "10", "--prefix-share", "0.8", "--fleet", "2"])
    fleet = rep["extra"]["fleet"]
    assert fleet["replicas"] == fleet["alive"] == 2
    assert fleet["policy"] == "affinity"
    assert fleet["token_parity_vs_fused"] is True
    assert fleet["goodput_frac"] == 1.0
    assert fleet["completed"] == fleet["requests"] > 0
    assert fleet["compiles_measured"] == 0
    assert fleet["routed_by_prefix"] > 0
    assert len(fleet["per_replica"]) == 2

    # fleet of disaggregated pairs with adapters + speculation: the
    # previously-forbidden combination rides the split per replica
    rep2 = _run(["bench.py", "--serve", "--batch", "4", "--serve-requests",
                 "10", "--prefix-share", "0.8", "--fleet", "2",
                 "--disaggregate", "--adapters", "2", "--speculate", "2"])
    fleet2 = rep2["extra"]["fleet"]
    assert fleet2["token_parity_vs_fused"] is True
    assert fleet2["goodput_frac"] == 1.0
    assert fleet2["compiles_measured"] == 0
    assert fleet2["page_transfer_bytes"] > 0
    assert fleet2["adapter_pool_hit_rate"] > 0
    assert set(fleet2["compiles_warmup_by_role"]) >= {"prefill", "decode"}


@pytest.mark.slow
def test_bench_serve_prefix_all_armed_strict_compiles():
    """The acceptance gate: reuse + speculation + adapters ALL armed on one
    replay — strict_compiles holds post-warmup (the harness raises on any
    mid-traffic compile, so the bench completing IS the pin) and the
    prefix block still measures real reuse."""
    # 16 requests at share 0.9: tenant-keyed hashing splits the preambles
    # across 3 adapter classes, so the trace needs enough arrivals for
    # same-tenant repeats to land hits
    rep = _run(["bench.py", "--serve", "--batch", "4", "--serve-requests",
                "16", "--prefix-share", "0.9", "--speculate", "3",
                "--adapters", "2"])
    extra = rep["extra"]
    assert extra["prefix_cache"] == "on"
    assert extra["speculate"] == "ngram"
    assert extra["adapters"] > 0
    assert extra["compiles_measured"] == 0
    assert extra["prefill_tokens_skipped"] > 0
    assert extra["prefix_reuse_token_parity"] is True


@pytest.mark.slow
def test_bench_serve_speculate_smoke():
    """``--serve --speculate``: the speculative run must beat the
    speculate-off run's tokens/step (1.0, the plain-decode floor) on the
    seeded CPU trace, the accept-rate twin agrees (predicted trace replay
    vs measured) within its declared tolerance, the replay stays
    recompile-free across the verify bucket ladder, and the idle-trace
    report keeps every speculate field zeros-clean."""
    rep = _run(["bench.py", "--serve", "--batch", "8", "--speculate"])
    extra = rep["extra"]
    assert extra["speculate"] == "ngram" and extra["speculate_k"] == 4
    assert extra["tokens_per_step"] > 1.0          # beats speculate-off's 1.0
    assert extra["accept_rate"] > 0.0
    assert extra["verify_steps"] > 0
    assert extra["compiles_measured"] == 0
    # the TwinRegistry rows: registered and within the declared tolerance
    for name in ("speculate.accept_rate", "speculate.tokens_per_step"):
        row = extra["twins"][name]
        assert row["status"] in ("ok", "warn"), (name, row)
        assert row["measured"] > 0
        assert row["rel_err"] <= row["tolerance"], (name, row)
    # verify bucket programs join the predicted program set
    assert extra["programs_predicted"] == len(extra["prefill_buckets"]) + 3 + 1
    # idle trace with speculation armed: zeros-clean
    rep_idle = _run(["bench.py", "--serve", "--batch", "8", "--speculate",
                     "--serve-requests", "0"])
    ei = rep_idle["extra"]
    assert ei["accept_rate"] == ei["tokens_per_step"] == 0.0
    assert ei["draft_overhead_frac"] == 0.0 and ei["speculative_rollbacks"] == 0


@pytest.mark.slow
def test_bench_serve_adapters_smoke():
    """``--serve --adapters N`` (multi-tenant batched LoRA): the adapter
    fields measure real traffic — hot-swaps happen (the pool is undersized
    on purpose), the predicted/measured hit-rate twins agree on the seeded
    trace, the pool ladder rides along, the replay stays recompile-free for
    the mixed tenant set, and the batched einsum beats the per-adapter-loop
    twin on tokens/s (the acceptance criterion's CPU proxy)."""
    rep = _run(["bench.py", "--serve", "--batch", "8", "--adapters", "3"])
    extra = rep["extra"]
    assert extra["adapters"] == 3
    assert extra["adapter_requests"] > 0
    assert extra["adapter_swaps"] > 0
    assert extra["adapter_swap_bytes"] > 0
    assert 0.0 < extra["adapter_pool_hit_rate"] <= 1.0
    # the LRU-replay predicted twin tracks the measured rate (divergence =
    # in-flight pinning/eviction reorder, bounded on the seeded trace)
    assert abs(extra["adapter_pool_hit_rate"]
               - extra["adapter_pool_hit_rate_predicted"]) < 0.3
    assert extra["adapter_pool"]["pool_bytes"] > 0
    assert extra["adapter_pool"]["swap_s_pred"] > 0
    # one fixed-shape program set for ANY tenant mix: zero post-warmup
    # compiles even with hot-swaps mid-traffic
    assert extra["compiles_measured"] == 0
    # the S-LoRA win: batched multi-adapter decode beats serving the same
    # trace one tenant at a time
    assert extra["per_adapter_loop"]["groups"] > 1
    assert extra["batched_speedup_vs_loop"] > 1.0


@pytest.mark.slow
def test_bench_plan_audit_hook():
    """``--plan N --audit`` embeds the graft-lint jaxpr-audit summary for
    the selected step: a tiny train step traced through the real
    prepare_train_step machinery with the selected optimizer (pure
    abstract trace — CPU-safe, nothing executes on device)."""
    rep = _run(["bench.py", "--plan", "8", "--batch", "8", "--audit"])
    audit = rep["extra"]["audit"]
    assert audit["ok"] is True
    assert audit["error"] == 0 and audit["warning"] == 0
    assert "rules" in audit and "suppressed" in audit
    # the compiled twin rides next to the trace audit: the same canonical
    # step AOT-compiled and audited at the executable level (GL301-303),
    # with the per-program cost row the predicted-MFU math feeds on
    compiled = rep["extra"]["compiled_audit"]
    assert compiled["ok"] is True and compiled["error"] == 0
    assert len(compiled["programs"]) == 1
    prog = compiled["programs"][0]
    assert prog["hbm"]["total"] > 0 and prog["flops"] > 0
    assert prog["aliased_bytes"] > 0  # the donated state actually aliased

    # audit rides along on the inference plan flavor too
    rep_inf = _run(["bench.py", "--plan", "8", "--batch", "8",
                    "--plan-task", "infer", "--audit"])
    assert rep_inf["extra"]["audit"]["ok"] is True

    # without --audit the plan stays audit-free (no accidental cost)
    rep_plain = _run(["bench.py", "--plan", "8", "--batch", "8"])
    assert "audit" not in rep_plain["extra"]


@pytest.mark.slow
def test_host_compute_probe_quiet_box_gate():
    """The probe enforces the quiet-box precondition and carries the gate
    report (loadavg + calibration vs the 1.71 GiB/s baseline) in its JSON;
    on a loaded box it refuses without --force.  CPU backends run the same
    chain with the baseline comparison non-binding.  --force here: loadavg
    is host-wide, so a busy CI box would otherwise flip the refusal path
    and flake this smoke — the gate report is emitted either way, which is
    what the assertions pin."""
    rep = _run(["benchmarks/host_compute_probe.py", "--gib", "0.05", "--force"])
    gate = rep["quiet_box"]
    assert "load" in gate and "calibration" in gate
    assert gate["baseline_gibs"] == 1.71
    assert gate["calibration"]["gibs"] > 0
    assert rep["aggregate_gib_s"] > 0


@pytest.mark.slow
def test_t131k_probe_cpu_components_run():
    # matmul + offload skeleton run on any backend (--cpu forces the CPU
    # backend); flash needs the TPU
    for comp in ("matmul", "offload"):
        rep = _run(["benchmarks/t131k_probe.py", "--seq-len", "512",
                    "--component", comp, "--cpu"])
        assert rep["component"] == comp and "value" in rep


@pytest.mark.slow
def test_bench_dcn_fields_always_emitted():
    """dcn_bytes / dcn_bytes_flat / dcn_overlap_frac ride EVERY train report
    (the always-emitted-twins contract): zeros-clean on a mesh without a
    dcn axis, and populated — with the hierarchical schedule strictly under
    the flat twin, PowerSGD under the dense slab — in both --dcn-compress
    states on a simulated 2-slice mesh."""
    # no dcn axis: fields present, zeros-clean
    rep = _run(["bench.py", "--iters", "2", "--batch", "8"])
    extra = rep["extra"]
    assert extra["dcn_bytes"] == 0 and extra["dcn_bytes_flat"] == 0
    assert extra["dcn_overlap_frac"] == 0.0
    assert extra["dcn_comm"]["hierarchical"] is False

    # 2-slice mesh, dense DCN hop (--dcn-compress off)
    rep_dense = _run(["bench.py", "--iters", "2", "--batch", "8",
                      "--dcn-slices", "2", "--dcn-compress", "off"])
    dense = rep_dense["extra"]
    assert dense["dcn_comm"]["hierarchical"] is True
    assert dense["dcn_comm"]["compression"] is None
    assert 0 < dense["dcn_bytes"] < dense["dcn_bytes_flat"]
    assert 0.0 <= dense["dcn_overlap_frac"] <= 1.0

    # PowerSGD DCN codec (--dcn-compress on): strictly fewer bytes again
    rep_c = _run(["bench.py", "--iters", "2", "--batch", "8",
                  "--dcn-slices", "2", "--dcn-compress", "on"])
    comp = rep_c["extra"]
    assert comp["dcn_comm"]["compression"] == "powersgd"
    assert 0 < comp["dcn_bytes"] < dense["dcn_bytes"]
    assert comp["dcn_bytes_flat"] == dense["dcn_bytes_flat"]


STANDARD_TWIN_NAMES = (
    "offload_transfer.overlap_frac", "tp_comm.overlap_frac",
    "dcn_comm.dcn_bytes", "kv_pool.utilization", "adapter_pool.hit_rate",
    "goodput.goodput_frac", "compiles.steady_state",
)


@pytest.mark.slow
def test_bench_telemetry_fields_always_emitted():
    """schema_version / telemetry_overhead_frac / the unified twins block
    ride EVERY bench report (train, serve and idle flavors), zeros-clean
    when nothing recorded — the always-emitted contract plus the canonical
    seven twin rows with per-twin rel_err and drift status."""
    rep = _run(["bench.py", "--iters", "2", "--batch", "8"])
    extra = rep["extra"]
    assert extra["schema_version"] == 1
    assert extra["telemetry_overhead_frac"] == 0.0  # telemetry off: free
    twins = extra["twins"]
    for name in STANDARD_TWIN_NAMES:
        assert name in twins, name
        row = twins[name]
        assert set(row) >= {"predicted", "measured", "rel_err", "status",
                            "units", "tolerance"}, row
        assert row["status"] in ("idle", "ok", "warn", "error")
    # the clean train run: goodput + compiles twins agree exactly
    assert twins["goodput.goodput_frac"]["status"] == "ok"
    assert twins["compiles.steady_state"]["rel_err"] == 0.0
    # subsystems the run never touched stay zeros-clean idle rows
    assert twins["kv_pool.utilization"]["status"] == "idle"
    assert twins["kv_pool.utilization"]["measured"] == 0.0

    # --telemetry on: the timeline summary + a measured overhead fraction,
    # and the loss is bitwise identical to the telemetry-off run
    rep_t = _run(["bench.py", "--iters", "2", "--batch", "8",
                  "--telemetry", "on"])
    extra_t = rep_t["extra"]
    assert extra_t["timeline"]["step_dispatch"]["count"] > 0
    assert 0.0 <= extra_t["telemetry_overhead_frac"] < 0.5
    assert extra_t["loss"] == extra["loss"]

    # serve flavor: same contract, kv-pool twin populated by the replay
    rep_s = _run(["bench.py", "--serve", "--batch", "8"])
    extra_s = rep_s["extra"]
    assert extra_s["schema_version"] == 1
    assert extra_s["telemetry_overhead_frac"] == 0.0  # tracing off
    assert extra_s["trace_spans"] == 0
    s_twins = extra_s["twins"]
    for name in STANDARD_TWIN_NAMES:
        assert name in s_twins, name
    assert s_twins["kv_pool.utilization"]["measured"] > 0
    assert s_twins["compiles.steady_state"]["status"] == "ok"


@pytest.mark.slow
def test_bench_serve_trace_requests(tmp_path):
    """--serve --trace-requests FILE: the exported Chrome trace validates,
    spans were recorded, overhead is measured, and the serving numbers
    (tokens, schedule, compiles) are identical to the untraced run of the
    same seeded trace (telemetry is bitwise-invisible)."""
    from accelerate_tpu.telemetry import validate_chrome_trace

    trace_file = str(tmp_path / "serve_trace.json")
    rep = _run(["bench.py", "--serve", "--batch", "8",
                "--trace-requests", trace_file])
    extra = rep["extra"]
    assert extra["trace_spans"] > 0
    assert extra["telemetry_overhead_frac"] > 0.0
    assert extra["trace_file"] == trace_file
    chrome = json.loads(Path(trace_file).read_text())
    assert validate_chrome_trace(chrome) == []
    names = {e["name"] for e in chrome["traceEvents"] if e["ph"] != "M"}
    assert {"submit", "queued", "admit", "prefill_chunk", "retire",
            "schedule", "host_sync"} <= names
    # tracing never compiled a program mid-replay (strict_compiles held)
    assert extra["compiles_measured"] == 0

    rep_off = _run(["bench.py", "--serve", "--batch", "8"])
    # same seeded trace, identical serving outcome fields
    for field in ("generated_tokens", "prompt_tokens", "engine_steps",
                  "decode_steps", "prefill_steps", "evictions", "completed"):
        assert extra[field] == rep_off["extra"][field], field


@pytest.mark.slow
def test_bench_fp8_smoke():
    """``--fp8`` (shorthand for --precision fp8): the train bench runs the
    delayed-scaling recipe end to end on CPU — loss finite, the amax
    history window reported (the always-emitted field), and the
    steady-state recompile guard still green (the delayed-scaling state
    update must not re-key the jit cache between steps)."""
    rep = _run(["bench.py", "--fp8", "--iters", "2", "--batch", "8",
                "--no-selftest"])
    extra = rep["extra"]
    assert extra["precision"] == "fp8"
    assert extra["fp8_amax_history_len"] >= 1
    assert extra["loss"] > 0
    assert extra["twins"]["compiles.steady_state"]["status"] == "ok"

    # bf16 default: the fp8 field still rides the report, zeros-clean
    rep_bf16 = _run(["bench.py", "--iters", "2", "--batch", "8",
                     "--no-selftest"])
    assert rep_bf16["extra"]["precision"] == "bf16"
    assert rep_bf16["extra"]["fp8_amax_history_len"] == 0


@pytest.mark.slow
def test_bench_serve_kv_quant_smoke():
    """``--serve --kv-dtype int8``: the quantized KV page pool serves the
    seeded trace end to end — strict_compiles holds (warmup compiles every
    program, the replay then measures ZERO compile events over quantized
    pages), the kv_quant.page_bytes twin is EXACT (allocated pool arrays
    vs the kv_page_bytes model, tolerance 0.0), and the capacity ladder
    reports the quantized pool's token-capacity multiple."""
    rep = _run(["bench.py", "--serve", "--batch", "8", "--kv-dtype", "int8"])
    extra = rep["extra"]
    assert extra["kv_dtype"] == "int8"
    assert extra["completed"] == extra["requests"] > 0
    assert extra["tokens_per_sec_per_chip"] > 0
    assert extra["compiles_measured"] == 0  # strict_compiles over int8 pages
    row = extra["twins"]["kv_quant.page_bytes"]
    assert row["status"] == "ok" and row["rel_err"] == 0.0, row
    assert row["predicted"] == row["measured"] > 0
    assert extra["kv_pool"]["kv_dtype"] == "int8"
    assert extra["kv_pool"]["capacity_vs_bf16"] > 1.5
    assert extra["kv_pool_capacity_ladder"]["int8"] == \
        extra["kv_pool"]["capacity_vs_bf16"]


@pytest.mark.slow
def test_bench_serve_kv_quant_disaggregate_transfer_twin():
    """``--serve --kv-dtype int8 --disaggregate``: quantized pages travel
    the prefill→decode wire (codes + per-page scales), the pair's greedy
    tokens match the fused engine BITWISE, and the transfer.page_bytes
    twin is exact at the roughly-halved quantized wire unit."""
    rep = _run(["bench.py", "--serve", "--batch", "4", "--serve-requests",
                "6", "--kv-dtype", "int8", "--disaggregate"])
    extra = rep["extra"]
    assert extra["disaggregated"]["token_parity_vs_fused"] is True
    row = extra["twins"]["transfer.page_bytes"]
    assert row["status"] == "ok" and row["predicted"] == row["measured"] > 0
    # the quantized wire unit is well under the bf16 one for this geometry
    from accelerate_tpu.models import LlamaConfig
    from accelerate_tpu.serving.paged_cache import kv_page_bytes

    cfg = LlamaConfig.tiny()
    page_size = 4  # the CPU-tiny serve geometry bench.py pins
    assert extra["transfer_accounting"]["bytes_per_page"] == \
        kv_page_bytes(cfg, page_size, 2, "int8")
    assert kv_page_bytes(cfg, page_size, 2, "int8") < \
        kv_page_bytes(cfg, page_size, 2)


@pytest.mark.slow
def test_fp8_quality_harness_runs():
    """The fp8-vs-bf16 loss-envelope harness (benchmarks/fp8_quality.py,
    the sr_quality.py discipline): identical Zipf stream, held-out batch,
    both envelope numbers emitted.  The documented 240-step envelope
    (docs/performance.md) comes from the full run; this smoke pins the
    harness stays executable."""
    rep = _run(["benchmarks/fp8_quality.py", "--cpu", "--steps", "4",
                "--eval-every", "2"])
    assert rep["metric"] == "fp8_quality_shuffled_stream"
    assert rep["scaling"] == "delayed"
    assert rep["model"] == "tiny-cpu" and rep["backend"] == "cpu"
    assert rep["final_held_out_gap_pct"] is not None
    assert rep["train_envelope_max_pct"] >= 0.0


def test_serve_trace_reduces_a_recorded_device_timeline(tmp_path):
    """benchmarks/serve_trace.py --reduce: window, busy and idle share come
    off the DEVICE clock of the trace (``device_offset_ps``), and each op is
    booked to the program whose span holds it.  The recording half needs the
    chip; the reduction is pinned here on a hand-made trace: two decode
    programs of 4 ms, 2 ms apart, each a 1 ms copy, a 2 ms paged-attention
    kernel and a 1 ms matmul."""
    import gzip

    ms = 10**9  # ps
    meta = [
        {"ph": "M", "pid": 3, "name": "process_name", "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 7, "name": "process_name", "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name", "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name", "args": {"name": "XLA Ops"}},
    ]
    event = lambda tid, name, start, dur, **args: {
        "ph": "X", "pid": 3, "tid": tid, "name": name, "ts": 0, "dur": 0,
        "args": {"device_offset_ps": str(start), "device_duration_ps": str(dur), **args}}
    events = [{"ph": "X", "pid": 7, "tid": 1, "name": "python", "ts": 0, "dur": 5, "args": {}}]
    for start in (10 * ms, 16 * ms):
        events += [
            event(2, "jit_decode(123)", start, 4 * ms),
            event(3, "copy.1", start, ms, long_name="%copy.1 = bf16[32,256,64,128]{3,0,2,1} copy(%p)"),
            event(3, "self_attn.6", start + ms, 2 * ms, long_name=(
                "%self_attn.6 = bf16[16,32,1,128]{3,2,1,0:T(2,128)} custom-call(s32[256]{0} %x)")),
            event(3, "fusion.9", start + 3 * ms, ms, long_name="%fusion.9 = bf16[16,4096]{1,0} fusion(%y)"),
        ]
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    with gzip.open(run / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": meta + events}, f)

    rep = _run(["benchmarks/serve_trace.py", "--reduce", str(tmp_path)])
    assert (rep["window_ms"], rep["busy_ms"], rep["idle_share"]) == (10.0, 8.0, 0.2)
    assert rep["programs"] == {"jit_decode(123)": {
        "runs": 2, "median_ms": 4.0,
        "class_ms_per_run": {"flash_attention": 2.0, "copy": 1.0, "matmul": 1.0},
        "kernel_call_median_ms": {"bf16[16,32,1,128]": 2.0}}}
