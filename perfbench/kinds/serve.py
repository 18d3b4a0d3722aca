"""Kind ``serve_open`` / ``serve_closed``: the paged serving engine under a
replayed trace, one process, one thread.

The loop is the load generator AND the server's driver: between ticks it
submits every request that has come due on the wall clock (open loop) or tops
the engine's queue up to ``num_slots`` waiting requests in list order (closed
loop), then calls ``engine.step()``, which blocks on the tick's tokens.  A
tick's tokens are stamped with the host clock after that call.  Times are
relative to the window's opening; the ramp runs at negative times and is part
of set-up."""

from __future__ import annotations

import time

import numpy as np

from perfbench import window as W
from perfbench.traffic import build_trace, prompt_tokens

SAMPLE_REQUESTS = 4        # finished requests compared with the reference, longest included


def serve_loop(engine, trace, prompts, *, kind, seconds, t_open, drain_s, num_slots, page_size,
               tracer=None, trace_last_s=0.0):
    """Replay ``trace`` against ``engine``; the window opens at ``t_open`` on
    ``time.perf_counter`` (the ramp runs until then).  Returns (ticks, due,
    submitted): ``due`` maps uid -> due time of every request submitted."""
    from accelerate_tpu.serving import Request

    clock = time.perf_counter
    ticks, due, nxt = [], {}, 0
    tracing = False

    def submit(r, due_s):
        engine.add_request(Request(uid=r.uid, prompt=prompts[r.uid], max_new_tokens=r.output_len))
        due[r.uid] = due_s

    while True:
        now = clock() - t_open
        if kind == "serve_open":
            while nxt < len(trace) and trace[nxt].due_s <= now and trace[nxt].due_s < seconds:
                submit(trace[nxt], trace[nxt].due_s)
                nxt += 1
        elif now < seconds:
            while len(engine.sched.waiting) < num_slots and nxt < len(trace):
                submit(trace[nxt], now)
                nxt += 1
            if nxt >= len(trace):
                raise RuntimeError("the closed-loop request list ran out inside the run; "
                                   "raise num_requests in the traffic file")
        if tracer is not None and not tracing and now >= seconds - trace_last_s:
            tracer.start()
            tracing = True
        if now >= seconds:
            if tracing:
                tracer.stop()
                tracing, tracer = False, None
            first = W.first_token_times(ticks)
            waiting = [u for u, d in due.items() if d < seconds and u not in first]
            if kind != "serve_open" or not waiting or now >= seconds + drain_s:
                break
        if engine.idle():
            nxt_due = trace[nxt].due_s if nxt < len(trace) else float("inf")
            wake = min(nxt_due, seconds) if now < seconds else now
            if wake > now:
                time.sleep(min(wake - now, 0.05))
            if now >= seconds:
                break
            continue
        before = {s: (st.request.uid, st.kv_tokens) for s, st in engine.sched.slots.items()}
        t0 = clock()
        ev = engine.step()
        t1 = clock()
        tick = {"start": t0 - t_open, "end": t1 - t_open, "kind": ev["type"],
                "prompt_tokens": 0, "emitted": (), "active": 0, "bucket": 0, "kv_pages": 0,
                "waiting": len(engine.sched.waiting), "traced": tracing}
        if ev["type"] == "decode":
            slots = ev.get("slots", ())
            tick["emitted"] = tuple(before[s][0] for s in slots)
            tick["active"] = len(slots)
            tick["kv_pages"] = sum(-(-(before[s][1] + 1) // page_size) for s in slots)
        elif ev["type"] == "prefill" and "slot" in ev:
            tick["prompt_tokens"], tick["bucket"] = ev["chunk"], ev["bucket"]
            st = engine.sched.slots.get(ev["slot"])
            if st is not None and st.prefill_done and len(st.tokens) == 1:
                tick["emitted"] = (st.request.uid,)
        ticks.append(tick)
    return ticks, due, nxt


def reference_gaps(ctx, weights, sample, results, prompts, width, quant=None):
    """For each sampled request, one reference forward over prompt + served
    tokens.  Returns (widest gap by which a served token's reference logit
    lies below the reference's best, tokens compared); with ``quant`` (the
    control) the token judged at each position is the one the lower precision
    puts first instead of the served one.  Every sequence is padded to
    ``width`` (the engine's per-sequence capacity): one program shape, compiled
    once and found in the cache by every later run."""
    import jax.numpy as jnp

    ref = ctx.reference
    arr = np.zeros((len(sample), width), np.int32)
    for row, uid in enumerate(sample):
        ids = list(prompts[uid]) + list(results[uid][:-1])
        arr[row, :len(ids)] = ids
    logits = ref.forward_logits(weights, ctx.cfg, ctx.layers, jnp.asarray(arr))
    low = None if quant is None else \
        ref.forward_logits(weights, ctx.cfg, ctx.layers, jnp.asarray(arr), quant=quant)
    widest, compared = 0.0, 0
    for row, uid in enumerate(sample):
        served, n_p = results[uid], len(prompts[uid])
        span = slice(n_p - 1, n_p - 1 + len(served))      # the positions that predict served tokens
        rows = logits[row, span]
        judged = jnp.asarray(served, jnp.int32) if low is None else jnp.argmax(low[row, span], axis=-1)
        gaps = jnp.max(rows, axis=-1) - jnp.take_along_axis(rows, judged[:, None], axis=-1)[:, 0]
        widest = max(widest, float(jnp.max(gaps)))
        compared += len(served)
    return widest, compared


def pick_sample(seed, finished: dict, lengths: dict, k: int = SAMPLE_REQUESTS):
    """``k`` finished requests drawn from the seed, the longest among them."""
    uids = sorted(finished)
    longest = max(uids, key=lambda u: (lengths[u], u))
    rest = [u for u in uids if u != longest]
    rng = np.random.default_rng([seed, 11])
    picked = [int(u) for u in rng.choice(rest, size=min(k - 1, len(rest)), replace=False)]
    return [longest] + picked


def build(ctx, spec):
    """Weights, engine, warm-up (this cell's programs only), trace, prompts."""
    import jax

    from perfbench.weights import make_weights

    fam = ctx.family
    weights = make_weights(fam.weight_shapes(ctx.cfg, ctx.layers), ctx.seed)
    jax.block_until_ready(weights)
    ctx.mark("weights")
    engine = fam.build_engine(ctx.cfg, ctx.layers, spec["engine"], weights, ctx.rehearse)
    t0 = time.perf_counter()
    compiles = engine.warmup()
    jax.block_until_ready(engine.cache)
    ctx.record["warmup_compile_s"] = time.perf_counter() - t0
    ctx.record["warmup_compiles"] = compiles
    ctx.mark("warmup")
    return weights, engine


def run(ctx):
    from accelerate_tpu.serving import verify_serving_invariants

    spec = ctx.sized(ctx.traffic)
    kind, seconds = spec["kind"], ctx.seconds
    weights, engine = build(ctx, spec)
    trace = build_trace(spec)
    vocab = ctx.cfg["vocab_size"]
    horizon = [r for r in trace if kind == "serve_closed" or r.due_s < seconds]
    prompts = {r.uid: prompt_tokens(ctx.seed, r.uid, r.prompt_len, vocab) for r in horizon}
    lengths = {r.uid: r.prompt_len + r.output_len for r in trace}
    ctx.mark("trace")
    if ctx.trace:
        engine.enable_tracing(capacity=1 << 20)
    compiles_before = engine.compile_events
    ctx.open_window(after_s=spec["ramp_s"])          # set-up ends where the ramp does
    t_open = ctx.t_open
    ticks, due, submitted = serve_loop(
        engine, trace, prompts, kind=kind, seconds=seconds, t_open=t_open,
        drain_s=spec["drain_s"], num_slots=spec["engine"]["num_slots"],
        page_size=spec["engine"]["page_size"],
        tracer=ctx.tracer if ctx.trace else None, trace_last_s=spec["trace_seconds"])
    compiles = engine.compile_events - compiles_before
    ctx.read_memory_peak()

    e2e, checks, facts = {}, [], {}
    if kind == "serve_open":
        ttft, failed_uids = W.ttft_ms(ticks, due, seconds, seconds + spec["drain_s"])
        tpot = W.tpot_ms(ticks, seconds)
        attempted = len(ttft)
        e2e["tpot_p90_ms"] = W.percentile(list(tpot.values()), 90)
        facts = {"ttft_mean_ms": sum(ttft.values()) / len(ttft),      # recorded, not judged:
                 "ttft_p90_ms": W.percentile(list(ttft.values()), 90)}  # metrics/ttft_*.py
        ctx.record.update(ttft_ms=ttft, tpot_ms=tpot)
    else:
        first = W.first_token_times(ticks)
        done_before = {u for u, toks in engine.results.items()
                       if u in first and _last_token(ticks, u) <= 0.0}
        attempted = len(due) - len(done_before)
        failed_uids = []
        e2e["serve_tokens_per_s"] = W.tokens_per_s(ticks, seconds)
    lost = engine.sched.requests_shed + engine.sched.cancelled
    failed = len(failed_uids) + lost
    ctx.record.update(ticks=ticks, due=due, num_slots=spec["engine"]["num_slots"],
                      host_window_s=seconds - (spec["trace_seconds"] if ctx.trace else 0.0),
                      engine_metrics=dict(engine.metrics), engine_spec=spec["engine"],
                      spans=host_spans(engine, ticks, t_open))
    inside = W.in_window(ticks, seconds)
    ctx.say(phase="host", **host_facts(inside))
    ctx.say(phase="window", ticks=len(inside), decode_ticks=sum(t["kind"] == "decode" for t in inside),
            prefill_ticks=sum(t["kind"] == "prefill" for t in inside), submitted=submitted,
            finished=len(engine.results), evictions=engine.metrics["evictions"],
            backlog_quarter=W.backlog(ticks, due, seconds / 4),
            backlog_end=W.backlog(ticks, due, seconds), **facts)

    # correctness, after the window: compiles, invariants, served tokens vs the reference
    violations = verify_serving_invariants(engine)
    checks.append(("compiles_in_window", compiles, 0))
    checks.append(("invariant_violations", len(violations), 0))
    checks.append(("requests_failed", failed, 0))
    finished = {u: t for u, t in engine.results.items() if len(t) == lengths[u] - len(prompts[u])}
    if finished:
        sample = pick_sample(ctx.seed, finished, lengths)
        t0 = time.perf_counter()
        width = spec["engine"]["pages_per_slot"] * spec["engine"]["page_size"]
        gap, compared = reference_gaps(ctx, weights, sample, finished, prompts, width)
        ctx.say(phase="reference", requests=len(sample), tokens_compared=compared,
                seconds=time.perf_counter() - t0)
        checks.append(("served_token_logit_gap", gap, ctx.limits["served_token_logit_gap"]))
    else:
        checks.append(("requests_finished", 0, None))
    return dict(attempted=attempted, failed=failed, end_to_end=e2e, checks=checks)


def host_spans(engine, ticks, t_open):
    """(name, start, end) on ``time.perf_counter``: the engine's own phase
    spans (``schedule``, ``dispatch:*``, ``host_sync``) and one ``step`` per
    traced tick, for the attribution of the device's idle gaps."""
    if engine.trace is None:
        return []
    spans = [(ev[1], ev[4], ev[4] + ev[5]) for ev in engine.trace.recorder.events()
             if ev[0] == "X" and ev[3] == "engine"]
    return spans + [("step", t_open + t["start"], t_open + t["end"]) for t in ticks if t["traced"]]


def host_facts(inside) -> dict:
    """Facts about the host during the window, printed beside the numbers so
    that a slow machine can be told from a slow program: a tick's time on the
    host's clock by kind (the device's part of it repeats to the second
    digit), stalls (the three longest ticks with their start in the window),
    and the time between ticks, which is this loop's own.
    (``/proc/stat`` and the load average read 0 on the chip's machine.)"""
    facts = {}
    for kind in ("decode", "prefill"):
        wall = sorted(t["end"] - t["start"] for t in inside if t["kind"] == kind)
        if wall:
            median = wall[len(wall) // 2]
            facts[f"{kind}_tick_wall_ms"] = {
                "median": median * 1e3, "p99": W.percentile(wall, 99) * 1e3, "max": wall[-1] * 1e3,
                "over_1p5x_median": sum(w > 1.5 * median for w in wall)}
    longest = sorted(inside, key=lambda t: t["start"] - t["end"])[:3]   # a stall shows here, with its time
    facts["longest_ticks"] = [[t["kind"], t["start"], (t["end"] - t["start"]) * 1e3] for t in longest]
    between = sorted(b["start"] - a["end"] for a, b in zip(inside, inside[1:]))
    if between:
        facts["between_ticks_ms"] = {"median": between[len(between) // 2] * 1e3,
                                     "sum": sum(between) * 1e3, "max": between[-1] * 1e3}
    return facts


def _last_token(ticks, uid):
    return max(t["end"] for t in ticks if uid in t["emitted"])
