"""Traffic-replay harness: seeded traces, serving metrics, and the
static-batching baseline.

The replay contract: replay a **seeded request trace**
(Poisson arrivals in virtual engine-step time, mixed prompt/output lengths)
through a :class:`~.engine.ServingEngine` and ALWAYS emit the serving
fields — tokens/s/chip, p50/p99 per-token latency, KV-pool utilization
(predicted + measured, CheckFreq-style twins), padding-waste fraction, and
scheduler occupancy — zeros when the trace is empty, so BENCH_*.json can
track them across rounds.

The **static-batching baseline** is the CPU-measurable proxy for the
continuous-batching win: it re-runs the same per-request work (actual
prompt and generated lengths from the measured run) through the
fixed-batch schedule ``generate()`` implies — pad every prompt to the
batch max, decode until the LAST sequence finishes, only then start the
next batch — and counts scheduled vs useful token-slots.  Padding waste
and scheduled-token efficiency compare directly; wall-clock tokens/s needs
a chip to differ meaningfully, the slot arithmetic does not.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from .scheduler import Request


def synthesize_trace(
    seed: int,
    n_requests: int,
    *,
    vocab_size: int = 256,
    mean_interarrival_steps: float = 2.0,
    prompt_len_range: tuple = (4, 24),
    new_tokens_range: tuple = (2, 16),
    adapters: int = 0,
    deadline_range: Optional[tuple] = None,
    prefix_share: float = 0.0,
    shared_prefixes: int = 2,
    shared_prefix_len: int = 0,
) -> list[Request]:
    """A deterministic request trace: Poisson arrivals (exponential gaps in
    virtual engine-step time) with uniformly mixed prompt/output lengths.
    Same seed -> same trace, always (the scheduler-determinism contract).

    With ``adapters=N`` each request draws a tenant ``adapter_id`` uniformly
    from ``0..N`` — id 0 rows serve the base model, so every multi-tenant
    trace mixes no-adapter traffic in (the id-0 bitwise contract's coverage).
    With ``deadline_range=(lo, hi)`` each request draws a per-request
    ``deadline_ticks`` uniformly — the deadline-pressure traffic the
    overload tests replay.

    With ``prefix_share=P`` each request opens, with probability ``P``,
    with one of ``shared_prefixes`` seeded **system preambles** of
    ``shared_prefix_len`` tokens (default: the middle of
    ``prompt_len_range``, so preambles span full pages at the test
    geometries) — the shared-system-prompt traffic mix the prefix cache's
    hit rate is measured on (``prefix_share``).  The
    per-request tail stays unique, so shared traffic still exercises the
    copy-on-write fork.
    """
    rng = np.random.default_rng(seed)
    if prefix_share and not shared_prefix_len:
        shared_prefix_len = (prompt_len_range[0] + prompt_len_range[1]) // 2
    preambles = [
        tuple(int(x) for x in rng.integers(1, vocab_size, shared_prefix_len))
        for _ in range(shared_prefixes if prefix_share else 0)
    ]
    trace = []
    t = 0.0
    for uid in range(n_requests):
        t += rng.exponential(mean_interarrival_steps)
        p_len = int(rng.integers(prompt_len_range[0], prompt_len_range[1] + 1))
        n_new = int(rng.integers(new_tokens_range[0], new_tokens_range[1] + 1))
        prompt = tuple(int(x) for x in rng.integers(1, vocab_size, p_len))
        if preambles and rng.random() < prefix_share:
            pre = preambles[int(rng.integers(0, len(preambles)))]
            prompt = pre + prompt
        adapter_id = int(rng.integers(0, adapters + 1)) if adapters > 0 else 0
        deadline = (int(rng.integers(deadline_range[0], deadline_range[1] + 1))
                    if deadline_range is not None else 0)
        trace.append(Request(uid=uid, prompt=prompt, max_new_tokens=n_new,
                             arrival_step=int(t), adapter_id=adapter_id,
                             deadline_ticks=deadline))
    return trace


def _percentile_ms(samples: list, q: float) -> float:
    if not samples:
        return 0.0
    return round(float(np.percentile(np.asarray(samples), q)) * 1e3, 3)


def predicted_pool_utilization(trace: list[Request], *, num_slots: int,
                               num_pages: int, page_size: int,
                               pages_per_slot: int, prefill_chunk: int) -> float:
    """CheckFreq-style *predicted* twin of the measured KV-pool utilization:
    a model-free replay of the scheduler arithmetic over the trace,
    assuming every request runs to its full ``max_new_tokens`` (the
    prediction error vs the measured twin is exactly the EOS-early-exit
    traffic the trace cannot know about)."""
    if not trace:
        return 0.0
    import dataclasses as _dc

    from .scheduler import ContinuousBatchingScheduler

    sched = ContinuousBatchingScheduler(
        num_slots, num_pages, page_size, pages_per_slot, prefill_chunk,
        (prefill_chunk,),
    )
    # page arithmetic only — adapter routing plays no part in the pool
    # utilization model, so the replay strips tenant ids
    pending = [_dc.replace(r, adapter_id=0)
               for r in sorted(trace, key=lambda r: (r.arrival_step, r.uid))]
    i, steps, page_step_sum = 0, 0, 0
    while True:
        while i < len(pending) and pending[i].arrival_step <= steps:
            sched.submit(pending[i])
            i += 1
        if sched.idle() and i >= len(pending):
            break
        sched.admit()
        action = sched.next_action()
        if action[0] == "prefill":
            slot, start, chunk = action[1], action[2], action[3]
            survived, _ = sched.plan_prefill_evictions(slot, chunk)
            if survived:
                sched.note_prefill(slot, chunk)
                st = sched.slots[slot]
                if st.prefill_done:
                    st.tokens.append(0)
                    if len(st.tokens) >= st.request.max_new_tokens:
                        sched.finish(slot)
        elif action[0] == "decode":
            active, _ = sched.plan_evictions(action[1])
            if active:
                sched.note_decode(sched.decode_page_need(active))
                done = []
                for s in active:
                    st = sched.slots[s]
                    st.tokens.append(0)
                    if len(st.tokens) >= st.request.max_new_tokens:
                        done.append(s)
                for s in done:
                    sched.finish(s)
        page_step_sum += sched.used_pages
        steps += 1
        if steps > 1_000_000:  # pragma: no cover - trace arithmetic safety net
            break
    return round(page_step_sum / max(steps, 1) / num_pages, 4)


class _AnyAdapters:
    """Duck-typed adapter shim for prediction replays: every tenant is
    known, pin-able and free — the replay models PAGE arithmetic, not
    adapter-pool contention, but must keep tenant ids flowing so the
    prefix hash chain stays adapter-keyed (cross-tenant prompts never
    alias)."""

    refcount: dict = {}

    def known(self, tid):
        return True

    def can_pin(self, tid):
        return True

    def pin(self, tid):
        return 0, False

    def unpin(self, tid):
        return None

    def prefetch(self, tid):
        return None


def predicted_prefix_hit_rate(trace: list[Request], *, num_slots: int,
                              num_pages: int, page_size: int,
                              pages_per_slot: int, prefill_chunk: int) -> float:
    """CheckFreq-style *predicted* twin of the measured prefix hit rate: a
    model-free replay of the REAL scheduler arithmetic over the trace (the
    :func:`predicted_pool_utilization` pattern) with a virtual
    :class:`~.prefix_cache.PrefixCache` armed — slot concurrency (two
    identical prompts prefilling at once cannot share), LRU reclaim under
    pool pressure, and eviction churn all replay exactly.  Insertions use
    synthetic page ids (the count arithmetic is what matters; no device
    exists here).  The prediction error vs the measured twin is the
    execution traffic the virtual clock cannot see: EOS early exits
    (requests that finish before their modeled decode length frees pages
    earlier) and fault-injected flushes."""
    if not trace:
        return 0.0
    import dataclasses as _dc

    from .prefix_cache import PrefixCache
    from .scheduler import ContinuousBatchingScheduler

    prefix = PrefixCache(page_size)
    sched = ContinuousBatchingScheduler(
        num_slots, num_pages, page_size, pages_per_slot, prefill_chunk,
        (prefill_chunk,), prefix=prefix,
    )
    sched.adapters = _AnyAdapters()
    pending = [_dc.replace(r, deadline_ticks=0)
               for r in sorted(trace, key=lambda r: (r.arrival_step, r.uid))]
    next_page = [0]

    def insert(st):
        hashes = prefix.block_hashes(st.request.prompt, st.request.adapter_id)
        k = len(st.shared_pages)
        if len(hashes) > k:
            ids = list(range(next_page[0], next_page[0] + len(hashes) - k))
            next_page[0] += len(ids)
            st.shared_pages.extend(prefix.insert_owned(hashes[k:], ids))

    i, steps = 0, 0
    while True:
        sched.tick = steps
        while i < len(pending) and pending[i].arrival_step <= steps:
            sched.submit(pending[i])
            i += 1
        if sched.idle() and i >= len(pending):
            break
        sched.admit()
        prefix.pending_free.clear()  # no device: the push is virtual
        action = sched.next_action()
        if action[0] == "prefill":
            slot, start, chunk = action[1], action[2], action[3]
            survived, _ = sched.plan_prefill_evictions(slot, chunk)
            if survived:
                sched.note_prefill(slot, chunk)
                st = sched.slots[slot]
                if st.prefill_done:
                    insert(st)
                    st.tokens.append(0)
                    if len(st.tokens) >= st.request.max_new_tokens:
                        sched.finish(slot)
        elif action[0] == "decode":
            active, _ = sched.plan_evictions(action[1])
            if active:
                sched.note_decode(sched.decode_page_need(active), active)
                done = []
                for s in active:
                    st = sched.slots[s]
                    st.tokens.append(0)
                    if len(st.tokens) >= st.request.max_new_tokens:
                        done.append(s)
                for s in done:
                    sched.finish(s)
        prefix.pending_free.clear()
        steps += 1
        if steps > 1_000_000:  # pragma: no cover - trace arithmetic safety net
            break
    return prefix.hit_rate()


def replay(engine, trace: list[Request], *, strict_compiles: bool = True,
           slo_monitor=None, verify_invariants: bool = False) -> dict:
    """Run the trace through the engine and compose the serving report.
    Every field is always present (zeros on an empty/idle trace).

    The engine is warmed first (``engine.warmup()`` — every fixed-shape
    program compiles before the clock starts), so the report's CheckFreq
    twins ``compiles_predicted``/``compiles_measured`` count POST-warmup
    compile events: the bucket-ladder contract predicts exactly zero, and a
    measured compile mid-replay is a recompile a production deploy would
    eat under traffic.  With ``strict_compiles`` (default) the harness
    fails its report loudly in that case instead of publishing numbers a
    recompile stall just poisoned.

    Telemetry: the serving twins (KV-pool utilization, adapter-pool hit
    rate, steady-state compiles) are recorded into the central
    :func:`~accelerate_tpu.telemetry.twin_registry`; with the engine's
    request tracing on (``ServingEngine.trace``) the report's
    ``telemetry_overhead_frac``/``trace_spans`` fields are measured (zeros
    otherwise — tracing off costs nothing and changes no token).  Pass an
    :class:`~accelerate_tpu.telemetry.SLOMonitor` as ``slo_monitor`` to
    feed it the replay's per-token latency and TTFT samples.

    Overload/resilience fields ride every report zeros-clean:
    ``requests_shed`` / ``deadline_misses`` / ``cancelled`` /
    ``pages_reclaimed_on_cancel`` / ``request_goodput_frac`` (completed over
    completed + deliberately retired) / ``transfer_retries`` (the adapter
    hot-swap path's absorbed transient failures) / the degradation ladder's
    stage and engagement count.  With ``verify_invariants=True`` the full
    resource contract (:func:`~.overload.verify_serving_invariants`) is
    checked after the run and any violation raises.
    """
    import time

    compiles_warmup = engine.warmup() if not engine.warmed_up else 0
    compiles_before = engine.compile_events
    tracer = getattr(engine, "trace", None)
    overhead_before = tracer.recorder.overhead_s if tracer is not None else 0.0
    sp = getattr(engine, "speculator", None)
    draft_before = sp.draft_time_s if sp is not None else 0.0
    t0 = time.perf_counter()
    results = engine.run(trace)
    wall_s = time.perf_counter() - t0
    compiles_measured = engine.compile_events - compiles_before
    if strict_compiles and compiles_measured > 0:
        raise RuntimeError(
            f"{compiles_measured} compile event(s) fired after warmup during "
            f"the serving replay (warmup compiled {compiles_warmup}): a "
            "mid-traffic recompile — some program shape is not pinned to "
            "the bucket ladder (chase with JAX_LOG_COMPILES=1, or pass "
            "strict_compiles=False to report anyway)"
        )
    if verify_invariants:
        from .overload import verify_serving_invariants

        problems = verify_serving_invariants(engine)
        if problems:
            raise RuntimeError(
                "serving invariants violated after replay: " + "; ".join(problems)
            )
    m = engine.metrics
    p = engine.plugin
    import jax

    n_chips = jax.device_count()
    scheduled = m["scheduled_decode_slots"] + m["prefill_scheduled_tokens"]
    useful = m["useful_decode_tokens"] + m["prefill_useful_tokens"]
    work_steps = m["decode_steps"] + m["verify_steps"] + m["prefill_steps"]
    total_steps = work_steps + m["idle_steps"]
    gen = m["generated_tokens"]
    predicted_util = predicted_pool_utilization(
        trace, num_slots=p.num_slots, num_pages=p.num_pages,
        page_size=p.page_size, pages_per_slot=p.pages_per_slot,
        prefill_chunk=p.prefill_chunk,
    )
    measured_util = round(m["page_step_sum"] / max(total_steps, 1) / p.num_pages, 4)
    # the serving rows of the central twin registry (telemetry/twins.py);
    # bench --serve renders registry.drift_report() as the `twins` block
    from ..telemetry import twin_registry

    reg = twin_registry()
    reg.record("kv_pool.utilization", predicted=predicted_util,
               measured=measured_util, source="serving/harness.replay")
    reg.record("compiles.steady_state", predicted=0,
               measured=compiles_measured, source="serving/harness.replay")
    spec_fields = _speculate_fields(engine, trace, results, wall_s,
                                    draft_before=draft_before)
    if slo_monitor is not None and getattr(engine, "slo", None) is not slo_monitor:
        # a monitor already attached to the engine (attach_slo) saw every
        # sample live — re-feeding it here would double-count quantiles and
        # re-fire trips into the report being assembled
        slo_monitor.observe_many("token_latency_s", engine.token_gaps_s)
        slo_monitor.observe_many("ttft_s", engine.ttft_s)
    # overhead as THIS replay's recording cost over THIS replay's wall (a
    # reused traced engine's earlier overhead must not inflate the ratio)
    overhead_s = (tracer.recorder.overhead_s - overhead_before
                  if tracer is not None else 0.0)
    telemetry_fields = {
        "telemetry_overhead_frac": (
            round(min(1.0, overhead_s / wall_s), 6) if wall_s > 0 else 0.0
        ),
        "trace_spans": tracer.recorder.recorded if tracer is not None else 0,
    }
    return {
        "requests": len(trace),
        "completed": len(results),
        "interrupted": engine.interrupted,
        "prompt_tokens": m["prompt_tokens"],
        "generated_tokens": gen,
        "wall_s": round(wall_s, 4),
        "tokens_per_sec": round(gen / wall_s, 2) if wall_s > 0 else 0.0,
        "tokens_per_sec_per_chip": round(gen / wall_s / n_chips, 2) if wall_s > 0 else 0.0,
        "p50_token_latency_ms": _percentile_ms(engine.token_gaps_s, 50),
        "p99_token_latency_ms": _percentile_ms(engine.token_gaps_s, 99),
        "ttft_p50_ms": _percentile_ms(engine.ttft_s, 50),
        # TTFT in virtual engine ticks — the deterministic twin wall clocks
        # cannot give on CPU (the prefix cache's with/without-reuse
        # comparison pins on this)
        "ttft_p50_ticks": (
            round(float(np.percentile(np.asarray(engine.ttft_ticks), 50)), 2)
            if engine.ttft_ticks else 0.0
        ),
        "kv_pool_utilization": measured_util,
        "kv_pool_utilization_predicted": predicted_util,
        "kv_pool_peak_utilization": round(m["peak_used_pages"] / p.num_pages, 4),
        "padding_waste_frac": round(1.0 - useful / scheduled, 4) if scheduled else 0.0,
        "scheduled_token_efficiency": round(useful / scheduled, 4) if scheduled else 0.0,
        "scheduler_occupancy": round(work_steps / max(total_steps, 1), 4),
        "engine_steps": total_steps,
        "decode_steps": m["decode_steps"],
        "prefill_steps": m["prefill_steps"],
        "idle_steps": m["idle_steps"],
        "evictions": m["evictions"],
        "prefill_buckets": list(p.prefill_buckets),
        "num_slots": p.num_slots,
        # CheckFreq twins for the recompile guard: post-warmup the bucket
        # ladder predicts zero compiles; measured is the monitoring stream
        "compiles_predicted": 0,
        "compiles_measured": compiles_measured,
        "compiles_warmup": compiles_warmup,
        # decode + release + first-token sampler, plus — with speculation —
        # one verify program per bucket and the draft provider's own
        # program, plus — with prefix caching — adopt + push_free + the COW
        # release replacing the plain one (net +2)
        "programs_predicted": len(p.prefill_buckets) + 3 + (
            len(p.speculate_buckets) + engine.speculator.provider.programs
            if engine.speculator is not None else 0
        ) + (2 if engine.prefix is not None else 0),
        **spec_fields,
        # prefix-cache + disaggregation fields — ALWAYS present, zeros when
        # the cache is off / no transport is attached
        **_prefix_fields(engine, trace),
        **telemetry_fields,
        # overload-control + cancellation fields — ALWAYS present, zeros on
        # a clean run (the resilience analog of the goodput block)
        **_overload_fields(engine, trace),
        # multi-tenant adapter fields — ALWAYS present (zeros without an
        # AdapterStore), with the predicted/measured pool-hit-rate twins
        **_adapter_fields(engine, trace),
        "results": results,
    }


def _overload_fields(engine, trace: list[Request]) -> dict:
    """The always-emitted overload/cancellation block of the serving report
    (zeros-clean on a clean run): shed/deadline/cancel counters, pages
    reclaimed by cancellation, request-level goodput (completed over
    completed + deliberately retired), the adapter path's absorbed transfer
    retries, and the degradation ladder's standing.  The serving twins
    record their measured side always; the predicted side is the clean-run
    model (zero faults, goodput 1.0) and is only recorded when no fault
    plan is active — a chaos soak records its own predictions."""
    from ..resilience.faults import active_fault_plan
    from ..telemetry import twin_registry

    sched = engine.sched
    completed = len(engine.results)
    retired = len(sched.retired_uids)
    goodput = (round(completed / (completed + retired), 4)
               if completed + retired else 0.0)
    store = getattr(engine, "adapters", None)
    retries = int(store.stats.transfer_retries) if store is not None else 0
    reg = twin_registry()
    measured = {
        "serving.requests_shed": sched.requests_shed,
        "serving.deadline_misses": sched.deadline_misses,
        "serving.cancelled": sched.cancelled,
        "serving.pages_reclaimed_on_cancel": sched.pages_reclaimed_on_cancel,
        "serving.request_goodput_frac": goodput,
    }
    # the zero-events clean-run model only applies when nothing could
    # legitimately shed or expire: no fault plan, no overload knobs armed,
    # no per-request deadlines in the trace — intended admission-control
    # shedding must never read as a twin "error"
    clean_predictions = (
        active_fault_plan() is None
        and not sched.max_queue and not sched.kv_shed_watermark
        and not sched.default_deadline_ticks and not sched.shed_armed
        and not any(r.deadline_ticks for r in trace)
    )
    for name, value in measured.items():
        reg.record_measured(name, value, source="serving/harness._overload_fields")
        if clean_predictions:
            pred = (1.0 if name.endswith("request_goodput_frac") and trace
                    else 0.0)
            reg.record_predicted(name, pred,
                                 source="serving/harness clean-run model")
    return {
        "requests_shed": sched.requests_shed,
        "deadline_misses": sched.deadline_misses,
        "cancelled": sched.cancelled,
        "pages_reclaimed_on_cancel": sched.pages_reclaimed_on_cancel,
        "request_goodput_frac": goodput,
        "transfer_retries": retries,
        "ladder_stage": engine.ladder.stage,
        "ladder_engagements": engine.ladder.engagements,
    }


def _prefix_fields(engine, trace: list[Request]) -> dict:
    """The always-emitted prefix-cache block of the serving report
    (zeros-clean with the cache off — the idle contract):

    - ``prefix_hit_rate`` — index-served cacheable pages over cacheable
      pages demanded at admission, counted once per request (measured),
      with the ``_predicted`` twin from the model-free scheduler replay
      (:func:`predicted_prefix_hit_rate` — concurrency and LRU reclaim
      modeled exactly; the prediction error is EOS-early-exit and
      fault-flush traffic the virtual clock cannot see);
    - ``pages_shared_peak`` — peak physical pages aliased by > 1 holder;
    - ``cow_forks`` — admissions that shared a proper prefix then wrote
      their own divergent pages;
    - ``prefill_tokens_skipped`` — prompt tokens never recomputed;
    - ``page_transfer_bytes`` (+pages/transfers) — the disaggregation
      slice's measured wire bytes (``transfer.page_bytes`` twin; zero
      unless a :class:`~.transfer.PagedKVTransport` streamed this engine).
    """
    m = engine.metrics
    prefix = getattr(engine, "prefix", None)
    fields = {
        "prefix_cache": "on" if prefix is not None else "off",
        "prefix_hit_rate": 0.0,
        "prefix_hit_rate_predicted": 0.0,
        "pages_shared_peak": 0,
        "cow_forks": 0,
        "prefill_tokens_skipped": 0,
        "prefix_evictions": 0,
        "page_transfers": m["page_transfers"],
        "page_transfer_pages": m["page_transfer_pages"],
        "page_transfer_bytes": m["page_transfer_bytes"],
    }
    if prefix is None:
        return fields
    from ..telemetry import twin_registry

    rep = prefix.report()
    fields.update(
        prefix_hit_rate=rep["prefix_hit_rate"],
        pages_shared_peak=rep["pages_shared_peak"],
        cow_forks=rep["cow_forks"],
        prefill_tokens_skipped=rep["prefill_tokens_skipped"],
        prefix_evictions=rep["prefix_evictions"],
    )
    p = engine.plugin
    predicted = predicted_prefix_hit_rate(
        trace, num_slots=p.num_slots, num_pages=p.num_pages,
        page_size=p.page_size, pages_per_slot=p.pages_per_slot,
        prefill_chunk=p.prefill_chunk,
    )
    fields["prefix_hit_rate_predicted"] = predicted
    twin_registry().record(
        "prefix_cache.hit_rate", predicted=predicted,
        measured=rep["prefix_hit_rate"],
        source="serving/harness._prefix_fields",
    )
    return fields


def _speculate_fields(engine, trace: list[Request], results: dict,
                      wall_s: float, draft_before: float = 0.0) -> dict:
    """The always-emitted speculative-decode block of the serving report
    (zeros-clean when speculation is off or the trace is idle):

    - ``accept_rate`` — accepted drafts / drafted tokens (measured), with
      the ``_predicted`` twin from the model-free trace replay
      (:func:`~.speculate.predicted_acceptance` over the MEASURED streams —
      the prediction error is the eviction/recompute re-decode traffic).
      The replay only runs for host-side providers (``provider.programs ==
      0``): replaying a draft MODEL would re-run the whole decode at batch
      1 on device just to fill a report field, so the draft-model twin
      stays idle (measured side only);
    - ``tokens_per_step`` — decode tokens emitted per slot per
      decode/verify pass (exactly 1.0 for plain decode; > 1.0 is the
      speculative win), same predicted twin;
    - ``draft_overhead_frac`` — THIS replay's host drafting time over its
      wall clock (``draft_before`` anchors the delta: a reused warmed
      engine's earlier drafting must not inflate the ratio);
    - ``speculative_rollbacks`` — pages rolled back off rejected drafts.

    Both twins are recorded into the central registry
    (``speculate.accept_rate`` / ``speculate.tokens_per_step``)."""
    m = engine.metrics
    lanes = m["decode_lane_passes"]
    measured_tps = round(m["decode_emitted_tokens"] / lanes, 4) if lanes else 0.0
    drafted = m["draft_tokens"]
    measured_accept = round(m["accepted_draft_tokens"] / drafted, 4) if drafted else 0.0
    sp = engine.speculator
    fields = {
        "speculate": engine.speculate_mode,
        "speculate_k": sp.k if sp is not None else 0,
        "accept_rate": measured_accept,
        "accept_rate_predicted": 0.0,
        "tokens_per_step": measured_tps,
        "tokens_per_step_predicted": 0.0,
        "draft_overhead_frac": 0.0,
        "speculative_rollbacks": m["speculative_rollbacks"],
        "verify_steps": m["verify_steps"],
        "drafted_tokens": drafted,
        "accepted_draft_tokens": m["accepted_draft_tokens"],
    }
    if sp is None:
        return fields
    from ..telemetry import twin_registry

    from .speculate import predicted_acceptance

    draft_s = sp.draft_time_s - draft_before
    fields["draft_overhead_frac"] = (
        round(min(1.0, draft_s / wall_s), 6) if wall_s > 0 else 0.0
    )
    reg = twin_registry()
    if sp.provider.programs == 0:  # model-free drafting: the replay is free
        pred = predicted_acceptance(trace, results, sp.provider, sp.k)
        fields["accept_rate_predicted"] = pred["accept_rate"]
        fields["tokens_per_step_predicted"] = pred["tokens_per_step"]
        reg.record("speculate.accept_rate", predicted=pred["accept_rate"],
                   measured=measured_accept,
                   source="serving/harness._speculate_fields")
        reg.record("speculate.tokens_per_step",
                   predicted=pred["tokens_per_step"], measured=measured_tps,
                   source="serving/harness._speculate_fields")
    else:
        reg.record("speculate.accept_rate", measured=measured_accept,
                   source="serving/harness._speculate_fields")
        reg.record("speculate.tokens_per_step", measured=measured_tps,
                   source="serving/harness._speculate_fields")
    return fields


def _adapter_fields(engine, trace: list[Request]) -> dict:
    """The always-emitted multi-tenant block of the serving report: pool
    hit rate (measured + the LRU-replay predicted twin), swap count/bytes,
    and the tenant census of the trace.  Zeros-clean when the engine runs
    single-tenant."""
    store = getattr(engine, "adapters", None)
    tenant_ids = [r.adapter_id for r in sorted(trace, key=lambda r: (r.arrival_step, r.uid))]
    if store is None:
        return {
            "adapters": 0, "adapter_requests": 0,
            "adapter_pool_slots": 0, "lora_rank": 0,
            "adapter_pool_hit_rate": 0.0,
            "adapter_pool_hit_rate_predicted": 0.0,
            "adapter_swaps": 0, "adapter_swap_bytes": 0,
        }
    from ..telemetry import twin_registry
    from .adapters import predicted_adapter_hit_rate

    predicted_hit = predicted_adapter_hit_rate(tenant_ids, store.plugin.pool_slots)
    twin_registry().record(
        "adapter_pool.hit_rate", predicted=predicted_hit,
        measured=store.hit_rate(), source="serving/harness._adapter_fields",
    )
    return {
        "adapters": len({t for t in tenant_ids if t}),
        "adapter_requests": sum(1 for t in tenant_ids if t),
        "adapter_pool_slots": store.plugin.pool_slots,
        "lora_rank": store.plugin.rank,
        "adapter_pool_hit_rate": store.hit_rate(),
        "adapter_pool_hit_rate_predicted": predicted_hit,
        "adapter_swaps": store.swaps,
        "adapter_swap_bytes": store.swap_bytes,
    }


def chaos_replay(engine_factory: Callable[[], object], trace: list[Request],
                 plan, *, max_restarts: int = 8, verify_invariants: bool = True,
                 strict_compiles: bool = True,
                 baseline_parity: bool = True) -> dict:
    """Seeded chaos soak: replay ``trace`` under a
    :class:`~accelerate_tpu.resilience.FaultPlan` of serving faults
    (cancellation storms, deadline storms, adapter-transfer failures,
    preempt-at-tick / preempt-mid-verify), restarting a fresh engine after
    every drain, until the traffic is fully disposed of (completed, shed or
    cancelled).

    The acceptance pin this function exists for: **surviving requests'
    greedy tokens are BITWISE identical to a fault-free replay of the same
    surviving set** — faults may change *which* requests complete, never
    *what* a completed request says.  After every engine (drained or done)
    the full resource contract runs
    (:func:`~.overload.verify_serving_invariants` — free-page mirror exact,
    zero leaked pages, adapter refcounts balanced), and post-warmup compile
    events stay at zero per engine (``strict_compiles``) — a fault must
    never push the engine off its warmed program set.

    ``engine_factory`` builds a fresh engine per life (the process-shared
    jit cache makes restarts cheap).  Returns the soak report: surviving
    ``results``, ``token_parity``, restart/fault/retirement counters, and
    ``invariant_problems`` (empty on a healthy engine).
    """
    import dataclasses as _dc

    from ..resilience.faults import fault_plan as _fault_plan
    from .overload import verify_serving_invariants

    results: dict[int, list] = {}
    restarts = 0
    compiles_measured = 0
    invariant_problems: list[str] = []
    counters = {"requests_shed": 0, "deadline_misses": 0, "cancelled": 0,
                "pages_reclaimed_on_cancel": 0, "transfer_retries": 0}
    pending = [_dc.replace(r) for r in
               sorted(trace, key=lambda r: (r.arrival_step, r.uid))]
    with _fault_plan(plan):
        while pending:
            engine = engine_factory()
            engine.warmup()
            before = engine.compile_events
            engine.run(pending)
            compiles_measured += engine.compile_events - before
            results.update(engine.results)
            sched = engine.sched
            counters["requests_shed"] += sched.requests_shed
            counters["deadline_misses"] += sched.deadline_misses
            counters["cancelled"] += sched.cancelled
            counters["pages_reclaimed_on_cancel"] += sched.pages_reclaimed_on_cancel
            store = getattr(engine, "adapters", None)
            if store is not None:
                counters["transfer_retries"] += int(store.stats.transfer_retries)
            if verify_invariants:
                invariant_problems.extend(verify_serving_invariants(engine))
            if not engine.interrupted:
                break
            # drained: a fresh engine serves the remainder (arrivals rebased
            # — the drain consumed the virtual clock the originals were
            # keyed on; relative order is preserved by uid)
            pending = [_dc.replace(r, arrival_step=0)
                       for r in engine.remaining_requests()]
            restarts += 1
            if restarts > max_restarts:
                raise RuntimeError(
                    f"chaos replay exceeded {max_restarts} restarts with "
                    f"{len(pending)} requests still pending"
                )
    if strict_compiles and compiles_measured > 0:
        raise RuntimeError(
            f"{compiles_measured} post-warmup compile event(s) during the "
            "chaos soak: a fault pushed the engine off its warmed program set"
        )
    if invariant_problems:
        raise RuntimeError(
            "serving invariants violated during the chaos soak: "
            + "; ".join(invariant_problems)
        )
    token_parity = True
    if baseline_parity and results:
        # fault-free replay of the SAME surviving set (no plan installed):
        # deadlines dropped — the baseline measures what the survivors SAY,
        # and a deadline re-expiring in the quieter baseline schedule would
        # change which requests complete, not their tokens
        survivors = [
            _dc.replace(r, arrival_step=0, deadline_ticks=0)
            for r in sorted(trace, key=lambda r: r.uid) if r.uid in results
        ]
        baseline = engine_factory()
        # the baseline must serve the surviving set UNCONDITIONALLY: its
        # admission controls disarm, because a bounded queue, a pressure
        # watermark or a default deadline would shed/expire survivors the
        # chaos run completed (all rebased to arrival 0) and fail the
        # parity pin spuriously — the pin is about tokens, not policy
        baseline.sched.max_queue = 0
        baseline.sched.kv_shed_watermark = 0.0
        baseline.sched.default_deadline_ticks = 0
        baseline.warmup()
        base_results = baseline.run(survivors)
        token_parity = base_results == results
    from ..telemetry import twin_registry

    total = len(trace)
    twin_registry().record_measured(
        "serving.request_goodput_frac",
        round(len(results) / total, 4) if total else 0.0,
        source="serving/harness.chaos_replay",
    )
    return {
        "requests": total,
        "completed": len(results),
        "survivor_frac": round(len(results) / total, 4) if total else 0.0,
        "restarts": restarts,
        "faults_fired": len(plan.fired),
        "compiles_measured": compiles_measured,
        "token_parity": token_parity,
        "invariant_problems": invariant_problems,
        **counters,
        "results": results,
    }


def static_batching_report(per_request: list, num_slots: int) -> dict:
    """Slot-arithmetic for the fixed-batch schedule ``generate()`` implies.

    ``per_request``: ``(prompt_len, generated_len)`` pairs in arrival order
    — use the MEASURED lengths from the continuous run so both schedules
    account identical work.  Batches of ``num_slots`` run start-to-finish:
    prompts pad to the batch max, decode runs until the batch's longest
    generation finishes.  Every batch is the full ``num_slots`` wide — both
    schedules drive the SAME fixed-shape jitted decode program (the shape-
    bucket contract); static batching just cannot refill a lane until the
    whole batch retires.
    """
    if not per_request:
        return {"padding_waste_frac": 0.0, "scheduled_token_efficiency": 0.0,
                "scheduled_token_slots": 0, "useful_tokens": 0, "batches": 0}
    scheduled = useful = 0
    batches = [per_request[i:i + num_slots] for i in range(0, len(per_request), num_slots)]
    for batch in batches:
        max_prompt = max(p for p, _ in batch)
        max_gen = max(g for _, g in batch)
        scheduled += (max_prompt + max_gen) * num_slots
        useful += sum(p + g for p, g in batch)
    return {
        "padding_waste_frac": round(1.0 - useful / scheduled, 4),
        "scheduled_token_efficiency": round(useful / scheduled, 4),
        "scheduled_token_slots": scheduled,
        "useful_tokens": useful,
        "batches": len(batches),
    }
